"""DIMC BPBS kernel: bit-true vs the jnp oracle across shapes/dtypes."""

import numpy as np
import pytest
import jax.numpy as jnp
from repro.testing.hypocompat import (  # real hypothesis when installed
    given, settings, st)

from repro.kernels import ops, ref


@pytest.mark.parametrize("m,k,n", [
    (8, 16, 8), (64, 300, 96), (128, 512, 128), (33, 127, 65),
    (1, 1024, 16), (256, 64, 256),
])
@pytest.mark.parametrize("bi,bw", [(8, 8), (4, 4), (8, 4), (2, 8)])
def test_dimc_matches_int_matmul(m, k, n, bi, bw):
    rng = np.random.default_rng(m * 1000 + k + n + bi * 7 + bw)
    lo_i, hi_i = -(2 ** (bi - 1)), 2 ** (bi - 1)
    lo_w, hi_w = -(2 ** (bw - 1)), 2 ** (bw - 1)
    x = jnp.asarray(rng.integers(lo_i, hi_i, (m, k)), jnp.int32)
    w = jnp.asarray(rng.integers(lo_w, hi_w, (k, n)), jnp.int32)
    y = ops.dimc_matmul(x, w, bi=bi, bw=bw)
    np.testing.assert_array_equal(np.asarray(y),
                                  np.asarray(ref.matmul_int_ref(x, w)))
    np.testing.assert_array_equal(
        np.asarray(y), np.asarray(ref.dimc_mvm_ref(x, w, bi, bw)))


@pytest.mark.parametrize("bm,bn,bk", [(32, 32, 64), (128, 128, 512),
                                      (8, 128, 128)])
def test_dimc_block_shapes_equivalent(bm, bn, bk):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(-128, 128, (96, 200)), jnp.int32)
    w = jnp.asarray(rng.integers(-128, 128, (200, 72)), jnp.int32)
    y = ops.dimc_matmul(x, w, bm=bm, bn=bn, bk=bk)
    np.testing.assert_array_equal(np.asarray(y),
                                  np.asarray(ref.matmul_int_ref(x, w)))


@given(st.integers(1, 24), st.integers(1, 48), st.integers(1, 24),
       st.integers(2, 8))
@settings(max_examples=20, deadline=None)
def test_dimc_property_random_shapes(m, k, n, bits):
    rng = np.random.default_rng(m + 31 * k + 7 * n + bits)
    lo, hi = -(2 ** (bits - 1)), 2 ** (bits - 1)
    x = jnp.asarray(rng.integers(lo, hi, (m, k)), jnp.int32)
    w = jnp.asarray(rng.integers(lo, hi, (k, n)), jnp.int32)
    y = ops.dimc_matmul(x, w, bi=bits, bw=bits, bm=8, bn=8, bk=16)
    np.testing.assert_array_equal(np.asarray(y),
                                  np.asarray(ref.matmul_int_ref(x, w)))


def test_unsigned_inputs_mode():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.integers(0, 256, (16, 64)), jnp.int32)
    w = jnp.asarray(rng.integers(-128, 128, (64, 16)), jnp.int32)
    y = ops.dimc_matmul(x, w, bi=8, bw=8, signed_inputs=False)
    np.testing.assert_array_equal(np.asarray(y),
                                  np.asarray(ref.matmul_int_ref(x, w)))


def test_weight_plane_recombination_identity():
    rng = np.random.default_rng(5)
    w = jnp.asarray(rng.integers(-8, 8, (32, 16)), jnp.int32)
    planes = ref.weight_bit_planes(w, 4)
    recon = sum((-(1 << j) if j == 3 else (1 << j)) * p
                for j, p in enumerate(planes))
    np.testing.assert_array_equal(np.asarray(recon), np.asarray(w))


def _pallas_operand_shapes(fn, *args):
    """Shapes the ``pallas_call`` inside ``fn`` receives."""
    import jax

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                return [tuple(v.aval.shape) for v in eqn.invars]
            for sub in jax.core.jaxprs_in_params(eqn.params):
                found = walk(sub)
                if found:
                    return found
        return None

    return walk(jax.make_jaxpr(fn)(*args).jaxpr)


@pytest.mark.parametrize("m,k,n,bk", [(8, 2816, 256, 512),
                                      (16, 600, 128, 512),
                                      (8, 600, 64, 256)])
def test_dimc_ragged_k_zero_padded(m, k, n, bk):
    """K not a multiple of ``bk`` (qwen1.5-0.5b's d_ff=2816 with the
    default bk=512): the kernel zero-pads the reduction axis to whole
    blocks instead of reading past the array — out-of-bounds block
    contents are unspecified on the TPU — and stays exact."""
    rng = np.random.default_rng(k + n)
    x = jnp.asarray(rng.integers(-128, 128, (m, k)), jnp.int8)
    w = jnp.asarray(rng.integers(-128, 128, (k, n)), jnp.int8)
    y = ops.dimc_matmul(x, w, bk=bk)
    np.testing.assert_array_equal(np.asarray(y),
                                  np.asarray(ref.dimc_mvm_ref(x, w, 8, 8)))
    kp = -(-k // bk) * bk
    shapes = _pallas_operand_shapes(
        lambda a, b: ops.dimc_matmul(a, b, bk=bk), x, w)
    assert shapes == [(m, kp), (kp, n)], shapes
