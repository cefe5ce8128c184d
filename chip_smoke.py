#!/usr/bin/env python3
"""Smoke run of the program's main paths on a TPU, in one process.

    python chip_smoke.py              # one chip: sweep, kernels, serve
    python chip_smoke.py --chips 4    # four chips: mesh serve

One chip:

* ``sweep``   -- ``dse.sweep_networks`` over the four tinyMLPerf networks
  on the 1620-design ``design_sweep.make_grid()`` grid with the ws+os
  dataflows, checked against ``dse.best_mapping_scalar`` on the host;
* ``kernels`` -- the Pallas ``dimc_mvm`` / ``aimc_mvm`` kernels compiled
  for the chip at the qwen1.5-0.5b projection shapes, checked against
  ``kernels.ref``;
* ``serve``   -- qwen1.5-0.5b at full published width through
  ``launch/serve.py``'s ``ServeLoop``, prefill checked against
  ``LM.forward``.

Four chips (``--chips 4``): glm4-9b at full width on the (1, 4) mesh
``serve.main`` builds.

Weights and inputs come from ``--seed``.  Timings printed here are
single-run smoke figures, not benchmarks.  Exits non-zero, without the
result line, when JAX finds no TPU or any check fails; on success the
last line of stdout is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

TINYMLPERF = ("deep_autoencoder", "resnet8", "ds_cnn", "mobilenet_v1_025")
#: (K, N) of qwen1.5-0.5b's projections (d_model=1024, d_ff=2816)
QWEN_KN = ((1024, 1024), (1024, 2816), (2816, 1024))
QWEN_M = (8, 256)


class CheckFailed(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise CheckFailed(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


# --------------------------------------------------------------------------- #
# one chip                                                                    #
# --------------------------------------------------------------------------- #
def tinymlperf_networks():
    from repro.core import workloads
    return [(n, getattr(workloads, n)()) for n in TINYMLPERF]


def phase_sweep(grid=None, networks=None, designs_per_net: int = 8,
                seed: int = 0) -> dict:
    """Fused sweep vs the scalar oracle on a seeded (layer, design)
    sample: winners must match exactly and every total must be finite;
    how many network totals are bitwise equal is reported, not
    required."""
    import numpy as np

    from benchmarks.design_sweep import make_grid
    from repro.core import dse, energy
    from repro.core.memory import MemoryModel

    grid = make_grid() if grid is None else grid
    networks = tinymlperf_networks() if networks is None else networks
    energy.grid_kernel_reset()
    t0 = time.perf_counter()
    results = dse.sweep_networks(networks, grid, schedules=("ws", "os"))
    wall = time.perf_counter() - t0
    for res in results:
        check(bool(np.isfinite(res.energy_fj).all()),
              f"sweep: non-finite energy total in {res.network}")
        check(bool((res.cycles > 0).all()),
              f"sweep: non-positive cycle total in {res.network}")

    rng = np.random.default_rng(seed)
    pairs = bitwise = 0
    max_rel = 0.0
    for (name, layers), res in zip(networks, results):
        eligible = [l for l in layers if l.imc_eligible]
        picks = rng.choice(len(grid), size=min(designs_per_net, len(grid)),
                           replace=False)
        for d in map(int, picks):
            macro = grid.macro_at(d)
            mem = MemoryModel(tech_nm=macro.tech_nm, vdd=macro.vdd)
            device = res.network_result(d)      # the chip's winners
            total = 0.0
            cycles = 0
            for layer, got in zip(eligible, device.layers):
                want = dse.best_mapping_scalar(layer, macro, mem,
                                               schedules=res.schedules)
                check(got == want,
                      f"sweep: winner differs for {name}/{layer.name} on "
                      f"design {d} ({grid.names[d]}): chip picked "
                      f"{got.cost.mapping}/{got.cost.schedule.name}, "
                      f"scalar {want.cost.mapping}/"
                      f"{want.cost.schedule.name}")
                total = total + want.total_energy_fj
                cycles = cycles + want.cost.cycles
                pairs += 1
            check(int(res.cycles[d]) == cycles,
                  f"sweep: cycle total differs for {name} on design {d}")
            got_e = float(res.energy_fj[d])
            bitwise += got_e == total
            max_rel = max(max_rel, abs(got_e - total) / abs(total))
    n_totals = len(networks) * min(designs_per_net, len(grid))
    out = {"designs": len(grid), "networks": len(networks),
           "pairs_checked": pairs, "totals_checked": n_totals,
           "totals_bitwise_fraction": bitwise / n_totals,
           "totals_max_rel_dev": max_rel,
           "grid_kernel_info": energy.grid_kernel_info(),
           "sweep_wall_s_smoke": wall}
    check(pairs >= 200, f"sweep: only {pairs} (layer, design) pairs "
          f"checked")
    return out


def _lowered_has_custom_call(fn, *args, **kw) -> bool:
    return "tpu_custom_call" in fn.lower(*args, **kw).as_text()


def phase_kernels(ms=QWEN_M, kns=QWEN_KN, interpret: bool = False,
                  seed: int = 0) -> dict:
    """DIMC must equal ``dimc_mvm_ref`` exactly; AIMC must meet
    ``aimc_mvm_ref`` within the tolerance of tests/kernels."""
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ref
    from repro.kernels.aimc_mvm import aimc_mvm
    from repro.kernels.dimc_mvm import dimc_mvm

    rng = np.random.default_rng(seed)
    out = {}
    for m in ms:
        for k, n in kns:
            tag = f"{m}x{k}x{n}"
            # DIMC: signed 8-bit inputs and weights (bi = bw = 8)
            x = jnp.asarray(rng.integers(-128, 128, (m, k)), jnp.int8)
            w = jnp.asarray(rng.integers(-128, 128, (k, n)), jnp.int8)
            if not interpret:
                check(_lowered_has_custom_call(dimc_mvm, x, w,
                                               interpret=False),
                      f"kernels: dimc {tag} lowered without tpu_custom_call")
            y = np.asarray(dimc_mvm(x, w, interpret=interpret))
            yr = np.asarray(ref.dimc_mvm_ref(x, w, 8, 8))
            check(np.array_equal(y, yr),
                  f"kernels: dimc {tag} differs from dimc_mvm_ref in "
                  f"{int((y != yr).sum())} elements")
            # AIMC: 4-bit DAC levels, signed 4-bit weights, 6-bit ADC
            xa = jnp.asarray(rng.integers(0, 16, (m, k)), jnp.int8)
            wa = jnp.asarray(rng.integers(-8, 8, (k, n)), jnp.int8)
            if not interpret:
                check(_lowered_has_custom_call(aimc_mvm, xa, wa,
                                               interpret=False),
                      f"kernels: aimc {tag} lowered without tpu_custom_call")
            ya = np.asarray(aimc_mvm(xa, wa, interpret=interpret))
            yar = np.asarray(ref.aimc_mvm_ref(xa, wa, 4, 4, 6, 256))
            dev = float(np.abs(ya - yar).max())
            check(np.allclose(ya, yar, rtol=1e-5, atol=1e-2),
                  f"kernels: aimc {tag} off aimc_mvm_ref by up to {dev}")
            out[tag] = {"dimc_exact": True, "aimc_max_abs_dev": dev}
    return out


def _prefill_vs_forward(loop, params, prompts) -> float:
    """Max |prefill logits - forward logits| at the first generated
    position, relative to the forward logits' max magnitude."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    lm = loop.lm
    vocab = lm.cfg.vocab_size
    tokens = jnp.asarray(prompts)
    logits_p, _, _ = loop._prefill(params, {"tokens": tokens})

    @jax.jit
    def forward_last(p, t):
        x, _ = lm.forward(p, {"tokens": t})
        return lm.logits_last(p, x[:, -1:])

    got = np.asarray(logits_p[:, 0, :vocab])
    want = np.asarray(forward_last(params, tokens)[:, 0, :vocab])
    check(bool(np.isfinite(got).all()), "serve: non-finite prefill logits")
    return float(np.abs(got - want).max() / np.abs(want).max())


#: bf16 compute: prefill and forward round the same ops in different
#: fusions, so their logits agree to a few bf16 ulps of the largest one
BF16_REL_TOL = 2e-2


def phase_serve(arch: str = "qwen1.5-0.5b", smoke: bool = False,
                batch: int = 4, prompt_len: int = 64, gen: int = 16,
                seed: int = 0) -> dict:
    """``ServeLoop.generate`` through ``launch/serve.py``'s own set-up;
    tokens must lie in the vocabulary, prefill must match forward."""
    import jax
    import numpy as np

    from repro.launch import serve

    loop, params, prompts = serve.build(arch, smoke=smoke, batch=batch,
                                        prompt_len=prompt_len, gen=gen,
                                        seed=seed)
    cfg = loop.lm.cfg
    key = jax.random.PRNGKey(seed)
    loop.generate(params, prompts, gen, key=key)        # compiles
    tokens, stats = loop.generate(params, prompts, gen, key=key)
    check(tokens.shape == (batch, gen), f"serve: tokens {tokens.shape}")
    check(bool(((tokens >= 0) & (tokens < cfg.vocab_size)).all()),
          "serve: token outside the vocabulary")
    rel = _prefill_vs_forward(loop, params, prompts)
    check(rel <= BF16_REL_TOL,
          f"serve: prefill logits off forward by {rel:.3g} (relative)")
    return {"arch": cfg.name, "d_model": cfg.d_model,
            "n_layers": cfg.n_layers, "vocab_size": cfg.vocab_size,
            "batch": batch, "prompt_len": prompt_len, "gen": gen,
            "prefill_vs_forward_rel_dev": rel,
            "prefill_s_smoke": stats["prefill_s"],
            "decode_tok_per_s_smoke": stats["decode_tok_per_s"]}


# --------------------------------------------------------------------------- #
# four chips                                                                  #
# --------------------------------------------------------------------------- #
def phase_mesh_serve(arch: str = "glm4-9b", smoke: bool = False,
                     batch: int = 4, prompt_len: int = 64, gen: int = 8,
                     seed: int = 0) -> dict:
    """``serve.main``'s mesh branch: weights spread over the devices,
    prefill on the mesh matching forward on the same mesh."""
    import jax
    import numpy as np

    from repro.launch import serve

    loop, params, prompts = serve.build(arch, smoke=smoke, batch=batch,
                                        prompt_len=prompt_len, gen=gen,
                                        seed=seed)
    mesh = loop.lm.dist.mesh
    check(mesh is not None, "mesh serve: serve.build made no mesh")
    leaves = jax.tree.leaves(params)
    param_bytes = sum(p.nbytes for p in leaves)
    held = {d: 0 for d in jax.devices()}
    for p in leaves:
        for sh in p.addressable_shards:
            held[sh.device] += sh.data.nbytes
    held = list(held.values())
    in_use = [(d.memory_stats() or {}).get("bytes_in_use")
              for d in jax.devices()]
    log(f"[mesh] weights {param_bytes} B; weight bytes per device {held}; "
        f"bytes_in_use per device {in_use}")
    check(max(held) < 0.5 * param_bytes,
          "mesh serve: one device holds half the weights or more")
    tokens, stats = loop.generate(params, prompts, gen,
                                  key=jax.random.PRNGKey(seed))
    check(bool(((tokens >= 0) & (tokens < loop.lm.cfg.vocab_size)).all()),
          "mesh serve: token outside the vocabulary")
    rel = _prefill_vs_forward(loop, params, prompts)
    check(rel <= BF16_REL_TOL,
          f"mesh serve: prefill logits off forward by {rel:.3g}")
    return {"arch": loop.lm.cfg.name,
            "mesh": dict(zip(mesh.axis_names, mesh.devices.shape)),
            "param_bytes": param_bytes, "weight_bytes_per_device": held,
            "bytes_in_use": in_use,
            "prefill_vs_forward_rel_dev": rel}


# --------------------------------------------------------------------------- #
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print("chip_smoke: the repo's src/repro is not next to this "
              "script", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devices[0].platform!r})", file=sys.stderr)
        return 3
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} devices", file=sys.stderr)
        return 3

    from repro.core.compilecache import enable_compilation_cache
    log(f"[setup] devices {len(devices)} x {devices[0].device_kind}; "
        f"compile cache {enable_compilation_cache()}")

    if args.chips == 1:
        phases = (("sweep", lambda: phase_sweep(seed=args.seed)),
                  ("kernels", lambda: phase_kernels(seed=args.seed)),
                  ("serve", lambda: phase_serve(seed=args.seed)))
    else:
        phases = (("mesh_serve", lambda: phase_mesh_serve(seed=args.seed)),)
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            out = fn()
        except CheckFailed as e:
            print(f"chip_smoke: FAILED {e}", file=sys.stderr)
            return 1
        log(f"[{name}] ok in {time.perf_counter() - t0:.1f}s: "
            f"{json.dumps(out)}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
