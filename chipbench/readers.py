"""Reductions shared by the per-metric readers in ``metrics/``.

``ctx`` is what ``run.py`` hands a reader: the window's length
(``seconds``), its completed steps, the runner's counters
(``runner``), the program's span rollup (``spans``, traced runs only)
and the reduced profiler trace (``trace``, traced runs only).  A reader
returns ``None`` when it finds nothing to read.
"""

from __future__ import annotations


def span_ms_per_step(ctx: dict, names) -> float | None:
    spans = ctx["spans"] or {}
    total = sum(spans[n]["total_s"] for n in names if n in spans)
    if not total or not ctx["steps"]:
        return None
    return total * 1e3 / ctx["steps"]


def d2h_bytes_per_step(ctx: dict) -> float | None:
    d = ctx["runner"]
    if not d.get("sweeps"):
        return None
    return d["transfer_bytes"] / d["sweeps"]


def device_busy_ms_per_step(ctx: dict) -> float | None:
    t = ctx["trace"]
    if not t or not t["busy_s"] or not ctx["steps"]:
        return None
    return t["busy_s"] * 1e3 / ctx["steps"]


def idle_share(ctx: dict) -> float | None:
    t = ctx["trace"]
    if not t or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def units_per_s(ctx: dict) -> float:
    return ctx["units"] / ctx["seconds"]
