"""Fused W8A16 dequant × matmul Pallas TPU kernel.

The paper's inference path dequantizes a layer then matmuls; on TPU the
fusion is the perf win: int8 weights stream HBM→VMEM (half the bytes of
bf16) and dequantization happens on the fly per VMEM tile, so the MXU never
waits on a dense bf16 weight materialization.

Math trick (beyond-paper, exact): with per-output-channel affine
``w = (q - z)·s``,

    y[m,n] = Σ_k x[m,k]·w[n,k]
           = s[n]·( Σ_k x[m,k]·q[n,k]  −  z[n]·Σ_k x[m,k] )

so the hot loop is a pure int8-as-bf16 MXU matmul (q ≤ 255 is exact in
bf16), plus one running row-sum of x; the affine epilogue applies once per
output tile.  No per-element dequant multiply inside the K loop at all.

Grid: (M/bm, N/bn, K/bk), K innermost; accumulators live in VMEM scratch.

This kernel serves mode='quant' (dense uint8 weights) and the legacy
two-step compressed path.  For mode='compressed' the serving hot path is
``fused_decode_matmul.py``, which runs the SAME grid and affine-epilogue
math but decodes each (bn, bk) weight tile from its compressed blocks
inside the kernel — possible because ``core.blocked_codec`` lays blocks
out tile-major, one whole number of blocks per (tile_n, tile_k) tile.
Keep the two epilogues in sync: both compute y = s·(Σ x·q − z·Σ x) with
q exact in bf16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BK = 512


def _kernel(x_ref, wq_ref, scale_ref, zero_ref, o_ref, acc_ref, sumx_ref):
    k_idx = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        sumx_ref[...] = jnp.zeros_like(sumx_ref)

    x = x_ref[...].astype(jnp.bfloat16)                  # (bm, bk)
    # Mosaic casts uint8 only to 32-bit ints; ≤255 stays exact in bf16.
    q = wq_ref[...].astype(jnp.int32).astype(jnp.bfloat16)   # (bn, bk)
    acc_ref[...] += jax.lax.dot_general(
        x, q, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)              # (bm, bn)
    sumx_ref[...] += jnp.sum(x.astype(jnp.float32), axis=1, keepdims=True)

    @pl.when(k_idx == nk - 1)
    def _epilogue():
        s = scale_ref[...].reshape(1, -1)                # (1, bn)
        z = zero_ref[...].reshape(1, -1)                 # (1, bn)
        o_ref[...] = (s * (acc_ref[...] - sumx_ref[...] * z)
                      ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "out_dtype",
                                             "interpret"))
def dequant_matmul(x: jax.Array, wq: jax.Array, scale: jax.Array,
                   zero: jax.Array, *, bm: int = DEFAULT_BM,
                   bn: int = DEFAULT_BN, bk: int = DEFAULT_BK,
                   out_dtype=jnp.float32, interpret: bool = False):
    """y = x @ dequant(wq).T  — see ref.dequant_matmul for semantics.

    x: (M, K) float; wq: (N, K) uint8; scale/zero: (N, 1) f32.
    Shapes must tile evenly by (bm, bn, bk); ``ops.py`` pads otherwise.
    """
    m, kdim = x.shape
    n, k2 = wq.shape
    assert kdim == k2, (x.shape, wq.shape)
    bm = min(bm, m)
    bn = min(bn, n)
    bk = min(bk, kdim)
    assert m % bm == 0 and n % bn == 0 and kdim % bk == 0, \
        (m, n, kdim, bm, bn, bk)

    grid = (m // bm, n // bn, kdim // bk)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bk), lambda i, j, k: (j, k)),
            pl.BlockSpec((bn, 1), lambda i, j, k: (j, 0)),
            pl.BlockSpec((bn, 1), lambda i, j, k: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32),
                        pltpu.VMEM((bm, 1), jnp.float32)],
        interpret=interpret,
    )(x, wq, scale, zero)
