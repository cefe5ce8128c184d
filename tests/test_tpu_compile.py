"""Compile the main path's device programs for a TPU v5e that is
described, not attached.

The TPU compiler refuses what interpret mode and XLA:CPU accept: block
shapes off the tiling, too much VMEM, f64/int64 lowering gaps.  These
compiles guard the Pallas MVM kernels at qwen1.5-0.5b projection widths
and the f64 sweep kernels at modest shapes, with no chip.  The topology
is described inside a fixture (only one process may load the TPU
library, so never at import), and the persistent compile cache is off
around the compiles: an executable for a described chip cannot be read
back here.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import energy
from repro.kernels.aimc_mvm import aimc_mvm
from repro.kernels.dimc_mvm import dimc_mvm


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("kernel", ["dimc", "aimc"])
@pytest.mark.parametrize("m,k,n", [(8, 1024, 2816), (8, 2816, 1024)])
def test_mvm_kernel_compiles_to_mosaic(one_chip, kernel, m, k, n):
    fn = dimc_mvm if kernel == "dimc" else aimc_mvm
    x = _spec((m, k), jnp.int8, one_chip)
    w = _spec((k, n), jnp.int8, one_chip)
    compiled = fn.lower(x, w, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


D, C, S, U = 8, 256, 4, 3
N_F, N_I, N_L = (len(energy._DESIGN_F64_ROWS), len(energy._DESIGN_I64_ROWS),
                 len(energy._LANE_ROWS))


def _blocks(sharding):
    return (_spec((N_F, D), np.float64, sharding),
            _spec((N_I, D), np.int64, sharding),
            _spec((N_L, C), np.int64, sharding))


def test_raw_grid_kernel_compiles_f64(one_chip):
    """The raw grid kernel, fused with the term products, on the packed
    (float design block, int design block, lane block) arguments."""
    with jax.enable_x64(True):
        compiled = energy._reduced_fused_kernel(True).lower(
            *_blocks(one_chip)).compile()
    assert "f64" in compiled.as_text()
    terms = compiled.out_info
    assert len(terms) == 11
    assert all(t.shape == (D, C) and t.dtype == np.float64 for t in terms)


def test_reduce_argmin_kernel_compiles_f64(one_chip):
    with jax.enable_x64(True):
        terms = [_spec((D, C), np.float64, one_chip)] * 11
        _, i64, lanes = _blocks(one_chip)
        legal_rows = _spec((U, C), np.bool_, one_chip)
        compiled = energy._reduce_argmin_kernel("energy", S).lower(
            *terms, i64, lanes, legal_rows).compile()
    assert "f64" in compiled.as_text()
    best, total, cycles = compiled.out_info
    assert best.shape == total.shape == cycles.shape == (S, D)
    assert total.dtype == np.float64 and cycles.dtype == np.int64
