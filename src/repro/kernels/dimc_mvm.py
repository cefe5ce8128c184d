"""Pallas TPU kernel: DIMC bit-parallel/bit-serial (BPBS) integer MVM.

TPU-native rethink of the paper's DIMC datapath (DESIGN.md §3): the
adder-tree accumulation of (input-bit x weight-plane) partial products
maps onto MXU matmuls over VMEM-resident tiles — one matmul per input
bit plane, unrolled inside the kernel so the MXU pipeline stays busy,
with the shift-add recombination running on the VPU as the epilogue.
The result is *bit-true* equal to the digital adder tree (int32).

Grid: (M/bm, N/bn, K/bk); the K axis is innermost so each output tile
is revisited with accumulation in the out ref (initialized at k==0) —
the same weight-stationary schedule the DIMC macro itself uses.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _dimc_kernel(x_ref, w_ref, o_ref, *, bi: int, bw: int,
                 signed_inputs: bool):
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.int32)
    w = w_ref[...].astype(jnp.int32)
    ux = x & ((1 << bi) - 1)
    uw = w & ((1 << bw) - 1)

    # Bit-parallel weights: the two's-complement planes recombine exactly
    # to the weight value (the adder tree's shift-add identity) — done
    # once on the VPU, truncating to bw bits.
    wv = jnp.zeros(w.shape, jnp.float32)
    for j in range(bw):
        wp = ((uw >> j) & 1).astype(jnp.float32)
        sj = -(1 << j) if j == bw - 1 else (1 << j)
        wv = wv + sj * wp

    acc = jnp.zeros_like(o_ref)
    # Bit-serial input loop (unrolled): one MXU pass per input bit plane;
    # magnitudes stay <= bk * 2^bw, exact in f32 accumulation.
    for i in range(bi):
        xp = ((ux >> i) & 1).astype(jnp.float32)
        si = -(1 << i) if (signed_inputs and i == bi - 1) else (1 << i)
        prod = jax.lax.dot_general(
            xp, wv, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc = acc + si * prod.astype(jnp.int32)
    o_ref[...] += acc


@functools.partial(jax.jit, static_argnames=(
    "bi", "bw", "signed_inputs", "bm", "bn", "bk", "interpret"))
def dimc_mvm(x: jax.Array, w: jax.Array, *, bi: int = 8, bw: int = 8,
             signed_inputs: bool = True, bm: int = 128, bn: int = 128,
             bk: int = 512, interpret: bool = False) -> jax.Array:
    """BPBS integer MVM: x (M,K) int8/int32, w (K,N) int8/int32 -> int32.

    Block shapes are MXU-aligned (multiples of (8,128)); VMEM working set
    is bm*bk + bk*bn + bm*bn 4-byte words — (128,128,512) ≈ 0.6 MB.

    K is padded up to a multiple of ``bk`` with zeros: a partial block
    on the reduction axis would otherwise read past the array, whose
    contents are unspecified on the TPU.  Zero columns add nothing to
    the adder tree.  Ragged M/N edges need no padding — out-of-bounds
    rows and columns only reach output elements that are never stored.
    """
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    if k % bk:
        pad = bk - k % bk
        x = jnp.pad(x, ((0, 0), (0, pad)))
        w = jnp.pad(w, ((0, pad), (0, 0)))
        k = k + pad
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn), k // bk)
    kernel = functools.partial(_dimc_kernel, bi=bi, bw=bw,
                               signed_inputs=signed_inputs)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        interpret=interpret,
    )(x, w)
