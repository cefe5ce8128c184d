"""Serving driver: batched prefill + decode loop with sampling.

Slot-based batching: requests fill a fixed batch, prefill runs once for
the batch (left-padded to the longest prompt is avoided by equal-length
synthetic prompts; ragged admission is handled by the slot scheduler in
``ServeLoop.admit``), then the decode loop streams tokens until every
slot hits its budget.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b \
        --smoke --batch 4 --prompt-len 16 --gen 24

Admission (:meth:`ServeLoop.admit`): a request either takes a free
batch slot, waits in the FIFO backlog, or — when its deadline cannot be
met even by the optimistic wait estimate — is rejected up front, which
is strictly kinder than timing it out after queueing.  Counted under
``serve.admitted`` / ``serve.queued`` / ``serve.rejected``.

Resilience (:meth:`ServeLoop.generate_resilient`): the same
prefill/decode loop run through a retry/backoff dispatch wrapper fed by
a :class:`repro.faults.FaultInjector`.  Transient faults back off and
retry in place; sticky node losses escalate to a ``recover`` callback
(the elastic resize-and-restore path, ``runtime.elastic``) and the loop
continues on the shrunken fleet.  Availability (1 - downtime/wall),
MTTR and goodput-under-failure land in the ``repro.obs`` registry
(``runtime.availability``, ``faults.mttr``, ``runtime.goodput``); with
no injector the wrapper is bypassed and tokens are bitwise those of
:meth:`ServeLoop.generate`.

Telemetry: with ``REPRO_TRACE=1`` the loop records ``serve.prefill`` /
``serve.decode`` spans, attaches a :class:`repro.runtime.monitor.
StepMonitor` to the decode loop (per-step wall + straggler flags into
the ``runtime.*`` registry metrics), and exports a Chrome trace +
telemetry JSONL (``serve_trace.json`` / ``serve_telemetry.jsonl`` in
``REPRO_TRACE_DIR``).
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs, obs
from repro.core.compilecache import enable_compilation_cache
from repro.faults.trace import NodeLossError, TransientFault
from repro.models.common import Dist
from repro.models.lm import LM
from repro.obs import sync
from repro.runtime.elastic import make_mesh_from_devices
from repro.runtime.monitor import StepMonitor

_C_ADMITTED = obs.counter("serve.admitted")
_C_QUEUED = obs.counter("serve.queued")
_C_REJECTED = obs.counter("serve.rejected")
_G_SLOTS_FREE = obs.gauge("serve.slots_free")
_C_RETRIES = obs.counter("faults.retries")
_C_RECOVERIES = obs.counter("faults.recoveries")
_T_MTTR = obs.timer("faults.mttr")
_G_AVAIL = obs.gauge("runtime.availability")
_G_GOODPUT = obs.gauge("runtime.goodput")


def sample(logits: jax.Array, key: jax.Array, temperature: float = 0.8,
           top_k: int = 40) -> jax.Array:
    """logits: (B, V) -> (B,) int32."""
    logits = logits / jnp.maximum(temperature, 1e-4)
    if top_k > 0:
        kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
        logits = jnp.where(logits < kth, -1e30, logits)
    return jax.random.categorical(key, logits).astype(jnp.int32)


@dataclasses.dataclass(frozen=True)
class Request:
    """One admission-control unit: a request wanting a batch slot.

    ``deadline_s`` is the caller's tolerance for *service start* delay
    (time-to-first-token budget minus prefill), relative to the admit
    call; ``None`` waits forever.
    """

    id: str
    prompt_len: int
    n_gen: int = 1
    deadline_s: float | None = None


class ServeLoop:
    def __init__(self, lm: LM, batch: int, max_seq: int,
                 monitor: StepMonitor | None = None):
        self.lm = lm
        self.batch = batch
        self.max_seq = max_seq
        self.monitor = monitor
        self._decode = jax.jit(lm.decode_step, donate_argnums=(1,))
        self._prefill = jax.jit(
            lambda p, b: lm.prefill(p, b, max_seq=max_seq))

    # ---------------------------------------------------------------- #
    # admission control                                                 #
    # ---------------------------------------------------------------- #
    #: EWMA service estimate (seconds one slot stays occupied); 0 until
    #: measured, which makes the wait estimate optimistic — a request is
    #: only ever rejected on evidence, never on a cold default.
    est_request_s: float = 0.0

    @property
    def slots(self) -> dict:
        """req_id -> Request of the currently admitted batch slots."""
        if not hasattr(self, "_slots"):
            self._slots = {}
        return self._slots

    @property
    def backlog(self) -> "collections.deque[Request]":
        """FIFO of queued requests waiting for a slot."""
        if not hasattr(self, "_backlog"):
            self._backlog = collections.deque()
        return self._backlog

    def admit(self, req: Request) -> str:
        """Admission decision for one request: ``"admit"`` (a batch slot
        is free and taken), ``"queue"`` (joins the FIFO backlog) or
        ``"reject"`` (its deadline cannot be met even optimistically).

        The wait estimate for backlog position ``p`` is
        ``ceil((p + 1) / batch) * est_request_s`` — every ``batch``
        departures free a full wave of slots.  With ``est_request_s``
        unmeasured (0) the estimate is 0 and nothing is ever rejected:
        deadline-aware rejection needs evidence, not priors.
        """
        if req.prompt_len + req.n_gen > self.max_seq:
            _C_REJECTED.inc()
            return "reject"
        if req.id in self.slots or any(q.id == req.id for q in self.backlog):
            raise ValueError(f"duplicate request id {req.id!r}")
        free = self.batch - len(self.slots)
        if free > 0:
            self.slots[req.id] = req
            _C_ADMITTED.inc()
            _G_SLOTS_FREE.set(self.batch - len(self.slots))
            return "admit"
        est_wait = (math.ceil((len(self.backlog) + 1) / self.batch)
                    * self.est_request_s)
        if req.deadline_s is not None and est_wait > req.deadline_s:
            _C_REJECTED.inc()
            return "reject"
        self.backlog.append(req)
        _C_QUEUED.inc()
        return "queue"

    def release(self, req_id: str) -> Request | None:
        """Free ``req_id``'s slot and promote the oldest queued request
        into it (returned; ``None`` when the backlog is empty)."""
        if req_id not in self.slots:
            raise KeyError(f"unknown request id {req_id!r}")
        del self.slots[req_id]
        promoted = None
        if self.backlog:
            promoted = self.backlog.popleft()
            self.slots[promoted.id] = promoted
            _C_ADMITTED.inc()
        _G_SLOTS_FREE.set(self.batch - len(self.slots))
        return promoted

    def generate(self, params, prompts: np.ndarray, n_gen: int,
                 key=None, temperature: float = 0.8):
        """prompts: (B, S_prompt) int32 -> (B, n_gen) int32 + stats.

        With a :class:`StepMonitor` attached, every decode step is
        individually forced and timed (straggler detection needs honest
        per-step walls); without one the loop keeps jax's async
        pipelining and only forces the tail.
        """
        key = jax.random.PRNGKey(0) if key is None else key
        b, s_prompt = prompts.shape
        assert b == self.batch
        monitor = getattr(self, "monitor", None)
        t0 = time.time()
        with obs.span("serve.prefill", batch=b, prompt_len=s_prompt):
            logits, cache, pos = self._prefill(
                params, {"tokens": jnp.asarray(prompts)})
            # jax dispatch is async: without forcing the prefill outputs
            # the clock stops while the real work is still in flight and
            # the first decode step absorbs it
            sync((logits, cache))
        t_prefill = time.time() - t0
        out = []
        tok = sample(logits[:, 0], key, temperature)
        t1 = time.time()
        with obs.span("serve.decode", batch=b, n_gen=n_gen):
            for i in range(n_gen):
                if monitor is not None:
                    monitor.start()
                out.append(np.asarray(tok))
                logits, cache = self._decode(params, cache, tok,
                                             jnp.int32(s_prompt + i))
                key, sub = jax.random.split(key)
                tok = sample(logits[:, 0], sub, temperature)
                if monitor is not None:
                    sync(tok)
                    monitor.stop(step=i)
            # the last decode+sample is dispatch-only at this point:
            # force it before the clock stops so decode_tok_per_s is
            # honest
            sync(tok)
        t_decode = time.time() - t1
        tokens = np.stack(out, axis=1)
        stats = {
            "prefill_s": t_prefill,
            "decode_s": t_decode,
            "decode_tok_per_s": b * n_gen / max(t_decode, 1e-9),
        }
        return tokens, stats

    # ---------------------------------------------------------------- #
    # resilient dispatch                                                 #
    # ---------------------------------------------------------------- #
    def _dispatch_resilient(self, step: int, fn, injector, recover,
                            retries: int, backoff_s: float,
                            backoff_mult: float, sleep, tally: dict):
        """Run one dispatch unit under fault injection.

        ``injector.check(step)`` raises the step's scheduled faults
        *before* ``fn`` runs (so ``fn`` — which may donate buffers —
        executes at most once, on the attempt that passes).  Transients
        back off exponentially and retry in place; a sticky
        :class:`NodeLossError` first burns the same retry budget (the
        node may flap back) and then escalates to ``recover(err)``,
        which must repair the fleet (elastic replan/reshard/restore)
        and mark the node restored before the loop re-checks.  MTTR is
        detection -> first successful dispatch; the downtime it covers
        feeds availability.
        """
        attempts = 0
        recoveries = 0
        delay = backoff_s
        t_fail = None
        while True:
            try:
                injector.check(step)
                out = fn()
                if t_fail is not None:
                    repair = time.perf_counter() - t_fail
                    _T_MTTR.observe(repair)
                    tally["downtime_s"] += repair
                    tally["mttr_s"].append(repair)
                return out
            except TransientFault:
                t_fail = time.perf_counter() if t_fail is None else t_fail
                tally["faults"] += 1
                if attempts >= retries:
                    raise
                attempts += 1
                _C_RETRIES.inc()
                tally["retries"] += 1
                sleep(delay)
                delay *= backoff_mult
            except NodeLossError as e:
                t_fail = time.perf_counter() if t_fail is None else t_fail
                tally["faults"] += 1
                if attempts < retries:
                    # the node may only be flapping: cheaper to back off
                    # than to reshard the world
                    attempts += 1
                    _C_RETRIES.inc()
                    tally["retries"] += 1
                    sleep(delay)
                    delay *= backoff_mult
                    continue
                if recover is None or recoveries >= retries:
                    raise
                recoveries += 1
                with obs.span("serve.recover", step=step, node=e.node):
                    _C_RECOVERIES.inc()
                    tally["recoveries"] += 1
                    recover(e)
                attempts = 0
                delay = backoff_s

    def generate_resilient(self, params, prompts: np.ndarray, n_gen: int,
                           key=None, temperature: float = 0.8, *,
                           injector=None, recover=None, retries: int = 3,
                           backoff_s: float = 0.005,
                           backoff_mult: float = 2.0, sleep=time.sleep):
        """Fault-tolerant :meth:`generate`: same loop, every dispatch
        unit (prefill, then each decode step) run through
        :meth:`_dispatch_resilient` against ``injector`` (a
        ``repro.faults.FaultInjector``; step index 0 is prefill, decode
        step ``i`` checks as ``i + 1``).

        ``injector=None`` bypasses the wrapper entirely — tokens are
        bitwise :meth:`generate`'s — and an injector with an empty
        trace produces the same tokens through the wrapped path (fault
        handling never touches the PRNG stream).  Stats gain
        ``availability`` (1 - downtime/wall), ``goodput_tok_per_s``
        (generated tokens over the *whole* wall, recoveries included),
        ``mttr_s`` (mean repair time) and the fault/retry/recovery
        tallies; the same numbers land in the registry as
        ``runtime.availability`` / ``runtime.goodput`` /
        ``faults.mttr``.
        """
        t_wall0 = time.perf_counter()
        tally = {"faults": 0, "retries": 0, "recoveries": 0,
                 "downtime_s": 0.0, "mttr_s": []}
        if injector is None:
            tokens, stats = self.generate(params, prompts, n_gen,
                                          key=key, temperature=temperature)
        else:
            key = jax.random.PRNGKey(0) if key is None else key
            b, s_prompt = prompts.shape
            assert b == self.batch
            t0 = time.time()
            with obs.span("serve.prefill", batch=b, prompt_len=s_prompt,
                          resilient=True):
                logits, cache, pos = self._dispatch_resilient(
                    0, lambda: self._prefill(
                        params, {"tokens": jnp.asarray(prompts)}),
                    injector, recover, retries, backoff_s, backoff_mult,
                    sleep, tally)
                sync((logits, cache))
            t_prefill = time.time() - t0
            out = []
            tok = sample(logits[:, 0], key, temperature)
            t1 = time.time()
            with obs.span("serve.decode", batch=b, n_gen=n_gen,
                          resilient=True):
                for i in range(n_gen):
                    out.append(np.asarray(tok))
                    step_key, sub = jax.random.split(key)

                    def step(cache=cache, tok=tok, i=i):
                        lg, new_cache = self._decode(
                            params, cache, tok, jnp.int32(s_prompt + i))
                        return lg, new_cache

                    logits, cache = self._dispatch_resilient(
                        i + 1, step, injector, recover, retries,
                        backoff_s, backoff_mult, sleep, tally)
                    key = step_key
                    tok = sample(logits[:, 0], sub, temperature)
                sync(tok)
            t_decode = time.time() - t1
            tokens = np.stack(out, axis=1)
            stats = {
                "prefill_s": t_prefill,
                "decode_s": t_decode,
                "decode_tok_per_s": b * n_gen / max(t_decode, 1e-9),
            }
        wall = max(time.perf_counter() - t_wall0, 1e-9)
        availability = max(0.0, 1.0 - tally["downtime_s"] / wall)
        goodput = tokens.size / wall
        _G_AVAIL.set(availability)
        _G_GOODPUT.set(goodput)
        stats.update(
            wall_s=wall, availability=availability,
            goodput_tok_per_s=goodput, faults=tally["faults"],
            retries=tally["retries"], recoveries=tally["recoveries"],
            downtime_s=tally["downtime_s"],
            mttr_s=(sum(tally["mttr_s"]) / len(tally["mttr_s"])
                    if tally["mttr_s"] else 0.0))
        return tokens, stats


def build(arch: str, *, smoke: bool = False, batch: int = 4,
          prompt_len: int = 16, gen: int = 24, model_axis: int = 16,
          seed: int = 0, monitor: StepMonitor | None = None):
    """The serving stack :func:`main` runs: config, a ``(data, model)``
    mesh over every device when there is more than one, weights and
    prompts drawn from ``seed``, and the :class:`ServeLoop`.  Returns
    ``(loop, params, prompts)``."""
    cfg = configs.get_smoke(arch) if smoke else configs.get(arch)
    dist = Dist(mesh=None) if len(jax.devices()) == 1 else \
        Dist(mesh=make_mesh_from_devices(model_axis=model_axis))
    lm = LM(cfg, dist)
    params = lm.init(jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, cfg.vocab_size,
                           (batch, prompt_len)).astype(np.int32)
    loop = ServeLoop(lm, batch, prompt_len + gen, monitor=monitor)
    return loop, params, prompts


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b",
                    choices=configs.ARCH_IDS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=24)
    ap.add_argument("--model-axis", type=int, default=16)
    args = ap.parse_args(argv)

    enable_compilation_cache()
    monitor = StepMonitor() if obs.trace_enabled() else None
    loop, params, prompts = build(
        args.arch, smoke=args.smoke, batch=args.batch,
        prompt_len=args.prompt_len, gen=args.gen,
        model_axis=args.model_axis, monitor=monitor)
    tokens, stats = loop.generate(params, prompts, args.gen)
    print(f"[serve] batch={args.batch} prompt={args.prompt_len} "
          f"gen={args.gen}: prefill {stats['prefill_s']:.2f}s, "
          f"decode {stats['decode_tok_per_s']:.1f} tok/s")
    print(f"[serve] first request tokens: {tokens[0][:12].tolist()}...")
    if monitor is not None:
        stats["steps"] = monitor.summary()
        print(f"[serve] step monitor: {stats['steps']}")
    if obs.trace_enabled():
        stats["trace_files"] = obs.export_all(prefix="serve")
        print(f"[serve] trace: {stats['trace_files']}")
    return stats


if __name__ == "__main__":
    main()
