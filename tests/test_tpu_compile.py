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

from repro.core import designs, energy
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


def test_raw_grid_kernel_compiles_f64(one_chip):
    cst = energy._design_constants(designs.macro_grid(
        rows=(64, 256, 1024), cols=(128, 512), adc_bits=(4, 8),
        dac_bits=(1, 4), m_mux=(1, 16), tech_nm=(22,)))
    with jax.enable_x64(True):
        cols = [_spec((D, 1), np.asarray(v).dtype, one_chip)
                for v in cst.values()]
        tiles = [_spec((C,), np.int64, one_chip)] * 4 + [
            _spec((C,), np.bool_, one_chip)]
        alpha = _spec((), np.float64, one_chip)
        compiled = jax.jit(energy._raw_grid_kernel()).lower(
            *cols, *tiles, alpha).compile()
    assert len(cols) == 19
    assert "f64" in compiled.as_text()


def test_reduce_argmin_kernel_compiles_f64(one_chip):
    with jax.enable_x64(True):
        terms = [_spec((D, C), np.float64, one_chip)] * 11
        wt_ipt = _spec((C,), np.int64, one_chip)
        cc_per_input = _spec((D, 1), np.int64, one_chip)
        write_cycles = _spec((D, C), np.int64, one_chip)
        legal_rows = _spec((U, C), np.bool_, one_chip)
        design_class = _spec((D,), np.int32, one_chip)
        seg_ids = _spec((C,), np.int64, one_chip)
        seg_starts = _spec((S,), np.int64, one_chip)
        compiled = energy._reduce_argmin_kernel("energy", S).lower(
            *terms, wt_ipt, cc_per_input, write_cycles, legal_rows,
            design_class, seg_ids, seg_starts).compile()
    best, total, cycles = compiled.out_info
    assert best.shape == total.shape == cycles.shape == (S, D)
    assert total.dtype == np.float64 and cycles.dtype == np.int64
