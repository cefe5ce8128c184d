"""Reduce a profiler trace (``.xplane.pb``) to device busy and idle time,
per-op device time and idle gaps labelled by the host annotation open
at the time.

The benchmark wraps its own calls into the program in
``jax.profiler.TraceAnnotation`` spans (:data:`LABELS`) and the measured
window in one named ``window``.  Busy time on a device is the union of
the intervals in which one of its operations ran; idle is the rest of
the window.  An idle gap is labelled by the innermost benchmark
annotation that covers its midpoint, or ``other``.
"""

from __future__ import annotations

import re
from collections import defaultdict
from pathlib import Path

LABELS = ("window", "sweep", "cache_clear", "oracle", "generate", "prefill",
          "decode")
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
#: lines of a device plane whose events are single operations; the first
#: one present is used (module lines would count each op twice)
OP_LINES = ("XLA Ops", "Ops")


def find_xplane(log_dir: str | Path) -> Path:
    found = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load_events(path: str | Path) -> dict:
    """``{"devices": {plane: [(start_ns, end_ns, op)]}, "annotations":
    [(start_ns, end_ns, label)]}`` from one xplane file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    devices, annotations = {}, []
    for plane in data.planes:
        lines = {line.name: line for line in plane.lines}
        if _DEVICE_PLANE.match(plane.name):
            op_line = next((lines[n] for n in OP_LINES if n in lines), None)
            if op_line is None:
                continue
            devices[plane.name] = [
                (ev.start_ns, ev.start_ns + ev.duration_ns,
                 ev.name.split(" = ")[0]) for ev in op_line.events]
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                annotations += [(ev.start_ns, ev.start_ns + ev.duration_ns,
                                 ev.name) for ev in line.events
                                if ev.name in LABELS]
    return {"devices": devices, "annotations": annotations}


def _union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _label_at(t: float, annotations) -> str:
    best, width = "other", None
    for s, e, name in annotations:
        if name != "window" and s <= t <= e and (width is None
                                                 or e - s < width):
            best, width = name, e - s
    return best


def reduce(events: dict, top: int = 10) -> dict:
    """Busy and idle seconds of the traced window, averaged over the
    devices; the ``top`` device ops by time and idle time by label."""
    windows = [(s, e) for s, e, n in events["annotations"] if n == "window"]
    if not windows:
        raise ValueError("trace holds no 'window' annotation")
    w0, w1 = windows[0]
    window_s = (w1 - w0) / 1e9
    if not events["devices"]:
        raise ValueError("trace holds no device plane with operations")
    busy_s, ops, idle = [], defaultdict(float), defaultdict(float)
    n_dev = len(events["devices"])
    for evs in events["devices"].values():
        clipped = [(max(s, w0), min(e, w1), n) for s, e, n in evs
                   if e > w0 and s < w1]
        for s, e, n in clipped:
            ops[n] += (e - s) / 1e9 / n_dev
        busy = _union((s, e) for s, e, _ in clipped)
        busy_s.append(sum(e - s for s, e in busy) / 1e9)
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for gs, ge in zip(edges[::2], edges[1::2]):
            if ge > gs:
                idle[_label_at((gs + ge) / 2, events["annotations"])] += \
                    (ge - gs) / 1e9 / n_dev
    by_time = lambda d: sorted(([k, v] for k, v in d.items()),
                               key=lambda kv: -kv[1])[:top]
    return {"window_s": window_s, "busy_s": sum(busy_s) / n_dev,
            "devices": n_dev, "device_ops": by_time(ops),
            "idle_gaps": by_time(idle)}
