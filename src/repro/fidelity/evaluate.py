"""Design-axis batched accuracy evaluation (the accuracy-side twin of
``dse.sweep``).

:func:`evaluate_grid` takes the same ``designs.MacroBatch`` the cost
sweep takes and returns per-design accuracy under the configured
nonidealities, batching the work into as few jit calls as the padded
lattice allows:

* designs are first *deduplicated to numeric signatures* — knobs the
  datapath cannot see (cols, m_mux, adc sharing, tech, vdd) collapse,
  so e.g. every DIMC design at one (bi, bw) is evaluated once;
* signatures sharing the jit-static knobs (mode, rows, bi, bw,
  dac_res) form one *group*, evaluated in a single jit call vmapped
  over the traced ``adc_res`` axis and over noise-seed PRNG keys.

A 60-design AIMC x DIMC grid typically compiles a handful of group
calls.  Noise keys are derived from (group, position, seed) alone, so
results are deterministic for a given grid and seed.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.designs import MacroBatch

from .functional import IDEAL, ForwardFn, sqnr_db, top1_agreement
from .noise import FidelityConfig, NoiseSpec


@dataclasses.dataclass(frozen=True)
class FidelityResult:
    """Accuracy of one design point under one noise condition."""

    accuracy: float               # mean top-1 agreement vs float reference
    sqnr_db: float                # mean SQNR vs float reference [dB]
    n_seeds: int


@dataclasses.dataclass(frozen=True)
class FidelityGrid:
    """Per-design accuracy over a macro grid (indexed like MacroBatch).

    ``accuracy[d]`` is mean top-1 agreement with the float reference
    over the probe batch and noise seeds; ``sqnr_db[d]`` the matching
    signal-to-quantization-noise ratio.  ``n_jit_calls`` reports how far
    the signature dedup + static grouping compressed the evaluation.
    """

    designs: MacroBatch
    accuracy: np.ndarray          # (D,) in [0, 1]
    sqnr_db: np.ndarray           # (D,)
    noise: NoiseSpec
    n_seeds: int
    n_jit_calls: int

    def __len__(self) -> int:
        return len(self.accuracy)


def _design_cfg(designs: MacroBatch, d: int,
                noise: NoiseSpec) -> FidelityConfig:
    return FidelityConfig.from_macro(designs.macro_at(d), noise=noise)


def evaluate_design(forward: ForwardFn, cfg: FidelityConfig, *,
                    n_seeds: int = 1, seed: int = 0) -> FidelityResult:
    """Evaluate one design's accuracy (scalar oracle for the grid path)."""
    base = jax.random.fold_in(jax.random.PRNGKey(seed), 0)
    y_ref = forward(IDEAL, base)
    n = n_seeds if cfg.noise.enabled else 1
    # same (group=0, position=0, seed) key derivation as a 1-design grid
    keys = [jax.random.fold_in(jax.random.fold_in(
        jax.random.fold_in(base, 0), 0), s) for s in range(n)]
    accs, sqs = [], []
    for key in keys:
        y = forward(cfg, key)
        accs.append(float(top1_agreement(y, y_ref)))
        sqs.append(float(sqnr_db(y, y_ref)))
    return FidelityResult(accuracy=float(np.mean(accs)),
                          sqnr_db=float(np.mean(sqs)), n_seeds=n)


def evaluate_grid(forward: ForwardFn, designs: MacroBatch, *,
                  noise: NoiseSpec = NoiseSpec(), n_seeds: int = 1,
                  seed: int = 0) -> FidelityGrid:
    """Batched accuracy evaluation over a whole macro grid.

    ``forward`` is a workload closure from ``fidelity.functional``
    (:func:`~repro.fidelity.functional.tinyml_forward` /
    :func:`~repro.fidelity.functional.lm_dense_forward`).  DIMC designs
    are exact and noise-free, so all noise knobs apply to the AIMC
    designs only; ``n_seeds`` collapses to 1 when noise is off.
    """
    with obs.span("fidelity.evaluate_grid", designs=len(designs),
                  seeds=n_seeds) as sp:
        grid = _evaluate_grid_impl(forward, designs, noise, n_seeds, seed)
        sp.set(jit_calls=grid.n_jit_calls)
    return grid


_C_JIT_CALLS = obs.counter("fidelity.jit_calls")


def _evaluate_grid_impl(forward: ForwardFn, designs: MacroBatch,
                        noise: NoiseSpec, n_seeds: int,
                        seed: int) -> FidelityGrid:
    # persist the per-group jit executables across processes (no-op
    # after the first call; see core.compilecache)
    from repro.core.compilecache import enable_compilation_cache
    enable_compilation_cache()
    base = jax.random.fold_in(jax.random.PRNGKey(seed), 0)
    y_ref = forward(IDEAL, base)

    def metrics(cfg: FidelityConfig, key: jax.Array):
        y = forward(cfg, key)
        return top1_agreement(y, y_ref), sqnr_db(y, y_ref)

    n_eff = n_seeds if noise.enabled else 1
    n_designs = len(designs)

    # dedupe designs to numeric signatures the datapath can see
    sig_ids: list[int] = []                  # design -> signature index
    sig_cfgs: list[FidelityConfig] = []      # signature index -> config
    sig_index: dict[tuple, int] = {}
    for d in range(n_designs):
        cfg = _design_cfg(designs, d, noise)
        key = (cfg.static_signature(), int(cfg.adc_res))
        if key not in sig_index:
            sig_index[key] = len(sig_cfgs)
            sig_cfgs.append(cfg)
        sig_ids.append(sig_index[key])

    # group signatures by jit-static knobs; adc_res stays a traced axis
    groups: dict[tuple, list[int]] = {}
    for si, cfg in enumerate(sig_cfgs):
        groups.setdefault(cfg.static_signature(), []).append(si)

    sig_acc = np.zeros(len(sig_cfgs))
    sig_sqnr = np.zeros(len(sig_cfgs))
    n_calls = 0
    for gi, (_static, members) in enumerate(sorted(groups.items())):
        gkey = jax.random.fold_in(base, gi)
        template = sig_cfgs[members[0]]
        with obs.span("fidelity.group", group=gi, members=len(members),
                      mode=template.mode):
            if template.mode != "aimc":
                # exact digital path: deterministic, one eval per signature
                for si in members:
                    cfg = sig_cfgs[si]
                    a, s = jax.jit(lambda c=cfg: metrics(c, gkey))()
                    n_calls += 1
                    _C_JIT_CALLS.inc()
                    sig_acc[si], sig_sqnr[si] = float(a), float(s)
                continue
            adc = jnp.asarray([float(sig_cfgs[si].adc_res)
                               for si in members], jnp.float32)
            keys = jnp.stack([
                jnp.stack([jax.random.fold_in(jax.random.fold_in(gkey, p), s)
                           for s in range(n_eff)])
                for p in range(len(members))])      # (G, S, key)

            def one(adc_res, key, template=template):
                cfg = dataclasses.replace(template, adc_res=adc_res)
                return metrics(cfg, key)

            batched = jax.jit(jax.vmap(jax.vmap(one, in_axes=(None, 0)),
                                       in_axes=(0, 0)))
            a, s = batched(adc, keys)               # (G, S) each
            n_calls += 1
            _C_JIT_CALLS.inc()
            for i, si in enumerate(members):
                sig_acc[si] = float(jnp.mean(a[i]))
                sig_sqnr[si] = float(jnp.mean(s[i]))

    ids = np.asarray(sig_ids)
    return FidelityGrid(designs=designs, accuracy=sig_acc[ids],
                        sqnr_db=sig_sqnr[ids], noise=noise,
                        n_seeds=n_eff, n_jit_calls=n_calls)
