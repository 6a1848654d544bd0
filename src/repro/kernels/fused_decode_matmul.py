"""Fused decode→dequant→matmul Pallas TPU megakernel.

Tiny-QMoE's premise is that compressed weights stay compressed until the
last possible moment.  The two-step path (``dict_decode`` then
``dequant_matmul``) betrays that on the hot loop: it writes the full dense
(N, K) uint8 weight to HBM and reads it back for the matmul — 2·N·K bytes
of HBM traffic per layer call plus a full dense-weight peak-memory spike.
This kernel fuses the dictionary decode into the matmul tile loop, exactly
as QMoE fuses its Huffman-style decode into the GPU GEMM:

  grid (M/bm, N/tile_n, G, K/(G·tile_k)), K innermost (G = optional
  column-group axis for shard-local TiledPackedLinear stacks, 1 for a
  plain PackedLinear).  Each grid step
    1. streams the ``bpt = tile_n·tile_k / block_weights`` compressed
       blocks covering the current (tile_n, tile_k) weight tile into VMEM
       (codes + literal words; the decode LUT is resident in VMEM for the
       whole launch as (R, 128) int32 words, ≤ 256 KiB),
    2. decodes them in-register with ``dict_decode.decode_words`` — the
       same lane-gather decode as the standalone decode kernel,
    3. feeds the decoded uint8 tile straight into the bf16 MXU matmul with
       the affine epilogue of ``dequant_matmul._kernel``:

           y = s · (Σ_k x·q − z·Σ_k x)      (q ≤ 255 exact in bf16)

The decoded weight never touches HBM: weight traffic drops from 2·N·K
bytes to the compressed payload, and peak working set is the compressed
planes + one VMEM tile.  This relies on the tile-major block layout of
``core.blocked_codec.encode_blocked_tiled`` — tile (j, k) of the
(N/tile_n, K/tile_k) grid owns the contiguous block rows
[t·bpt, (t+1)·bpt), t = j·n_kt + k — so the BlockSpec index maps below can
address a tile's blocks as one rectangular slab.

Decoded words arrive with rows and columns permuted within each tile (see
:func:`_decode_tile`); the jitted wrappers permute x's columns, the affine
rows and the output columns to match, so callers see the plain semantics.

``grouped_fused_decode_matmul`` is the MoE variant: the grid grows a
leading expert (plane) axis so one launch sweeps a whole stacked expert
weight — the capacity-gathered token blocks (E, cap, K) against the
stacked tile-major planes (E, nb, slots) — and each grid step decodes one
(expert, tile_n, tile_k) block in VMEM inside the MXU loop.  Dense expert
weights, the dominant byte class of every QMoE-style model, never touch
HBM.

Oracles: ``ref.fused_decode_matmul`` / ``ref.grouped_fused_decode_matmul``
(same strip-wise structure in f32).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dict_decode import decode_words, literal_words, lut_words

DEFAULT_BM = 128


def _decode_tile(codes_ref, lit_ref, lut_ref, work_ref, tn, tk, s):
    """Decode one (tile_n, tile_k) weight tile from its compressed blocks.
    The uint8 values live only in VMEM.

    The tile comes back *permuted*, in the order the word layout gives for
    free: row ``g·bpt + b`` holds weight row ``b·rpb + g`` (block b spans
    ``rpb`` rows of the tile) and column ``j·L + c`` holds weight column
    ``c·S + j`` (byte j of word c; L = tile_k / S words per row).  The
    wrappers permute x, the affine rows and the output to match, so no
    in-kernel byte interleave or cross-sublane reshape is needed."""
    codes = codes_ref[...].reshape(codes_ref.shape[-2:])  # (bpt, W)
    lits = lit_ref[...].reshape(lit_ref.shape[-2:])       # (bpt, cap')
    words = decode_words(codes, lits, lut_ref, work_ref)  # (bpt, W) int32
    row_words = tk // s
    rows = []
    for g in range(words.shape[1] // row_words):
        wg = words[:, g * row_words:(g + 1) * row_words]
        planes = [(wg >> (8 * j)) & 0xFF for j in range(s)]
        rows.append(planes[0] if s == 1 else
                    jnp.concatenate(planes, axis=1))      # (bpt, tk)
    q = rows[0] if len(rows) == 1 else jnp.concatenate(rows, axis=0)
    assert q.shape == (tn, tk), (q.shape, tn, tk)
    return q                                              # int32 ≤ 255


def _accumulate(x, q, acc_ref, sumx_ref):
    """MXU matmul against a decoded tile + running x row-sums for the
    affine epilogue: y = s · (Σ_k x·q − z·Σ_k x)  (q ≤ 255 exact in bf16)."""
    xb = x.astype(jnp.bfloat16)                           # (bm, tk)
    acc_ref[...] += jax.lax.dot_general(
        xb, q.astype(jnp.bfloat16), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)               # (bm, tn)
    sumx_ref[...] += jnp.sum(xb.astype(jnp.float32), axis=1, keepdims=True)


def _rows_per_block(codes, tile_n, tile_k, s):
    """How many weight rows of a tile one compressed block spans."""
    slots = codes.shape[-1]
    row_words = tile_k // s
    assert tile_k % s == 0 and slots % row_words == 0, (
        "fused layout needs whole weight rows per block",
        codes.shape, tile_n, tile_k, s)
    rpb = slots // row_words
    assert tile_n % rpb == 0, (tile_n, rpb)
    return rpb


def _permute_x(x, tile_k, s):
    """(..., K) columns → per-tile (byte, word) order (see _decode_tile)."""
    lead, k = x.shape[:-1], x.shape[-1]
    x = x.reshape(lead + (k // tile_k, tile_k // s, s))
    return jnp.swapaxes(x, -1, -2).reshape(lead + (k,))


def _permute_rows(v, tile_n, rpb):
    """(..., N, 1) affine rows → per-tile (g, b) order (see _decode_tile)."""
    lead, n = v.shape[:-2], v.shape[-2]
    v = v.reshape(lead + (n // tile_n, tile_n // rpb, rpb))
    return jnp.swapaxes(v, -1, -2).reshape(lead + (n, 1))


def _unpermute_cols(y, tile_n, rpb):
    """Inverse of :func:`_permute_rows` on the output's last axis."""
    lead, n = y.shape[:-1], y.shape[-1]
    y = y.reshape(lead + (n // tile_n, rpb, tile_n // rpb))
    return jnp.swapaxes(y, -1, -2).reshape(lead + (n,))


def _kernel(x_ref, codes_ref, lit_ref, lut_ref, scale_ref, zero_ref, o_ref,
            acc_ref, sumx_ref, work_ref, *, s):
    g_idx = pl.program_id(2)
    k_idx = pl.program_id(3)
    ng = pl.num_programs(2)
    nk = pl.num_programs(3)

    @pl.when((g_idx == 0) & (k_idx == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        sumx_ref[...] = jnp.zeros_like(sumx_ref)

    tn, tk = scale_ref.shape[0], x_ref.shape[1]
    q = _decode_tile(codes_ref, lit_ref, lut_ref, work_ref, tn, tk, s)
    _accumulate(x_ref[...], q, acc_ref, sumx_ref)

    @pl.when((g_idx == ng - 1) & (k_idx == nk - 1))
    def _epilogue():
        sc = scale_ref[...].reshape(1, -1)                # (1, tn)
        z = zero_ref[...].reshape(1, -1)                  # (1, tn)
        o_ref[...] = (sc * (acc_ref[...] - sumx_ref[...] * z)
                      ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("shape", "tile_n", "tile_k",
                                             "bm", "out_dtype", "interpret"))
def fused_decode_matmul(x: jax.Array, codes: jax.Array, literals: jax.Array,
                        lut: jax.Array, scale: jax.Array, zero: jax.Array, *,
                        shape: tuple, tile_n: int, tile_k: int,
                        bm: int = DEFAULT_BM, out_dtype=jnp.float32,
                        interpret: bool = False) -> jax.Array:
    """y = x @ dequant(decode(codes, literals)).T without a dense weight.

    x: (M, K) float, M % bm == 0; codes/literals: tile-major planes for the
    dense ``shape = (N, K)`` weight; scale/zero: (N, 1) f32.  ``nlit`` is
    not needed (the escape-rank gather never selects past a block's
    escapes, as in ``dict_decode``).

    Column groups (the shard-local 2D-TP case): codes may carry a leading
    group axis — ``codes (G, nb, slots)``, ``literals (G, nb, cap, S)`` —
    where group g holds the tile-major planes of the (N, K/G) sub-weight
    covering x columns [g·K/G, (g+1)·K/G).  The grid grows a group
    dimension between N-tiles and K-strips, so the accumulator sweeps
    every (g, k) strip of an output tile before the affine epilogue fires
    once — one kernel launch per device for a whole TiledPackedLinear
    shard (a stack of column-tile planes), no per-tile HBM round trips.
    2-D codes are treated as G = 1.
    """
    n, kdim = shape
    m, k2 = x.shape
    assert k2 == kdim, (x.shape, shape)
    if codes.ndim == 2:
        codes = codes[None]
        literals = literals[None]
    groups = codes.shape[0]
    assert kdim % groups == 0, (shape, groups)
    kg = kdim // groups
    assert n % tile_n == 0 and kg % tile_k == 0, (shape, groups,
                                                  tile_n, tile_k)
    bm = min(bm, m)
    assert m % bm == 0, (m, bm)
    nnt, nkt = n // tile_n, kg // tile_k
    _, nb, slots = codes.shape
    s = literals.shape[3]
    bpt = nb // (nnt * nkt)
    assert bpt * nnt * nkt == nb and bpt * slots * s == tile_n * tile_k, (
        codes.shape, literals.shape, shape, tile_n, tile_k)
    rpb = _rows_per_block(codes, tile_n, tile_k, s)
    lits = literal_words(literals)
    lutw = lut_words(lut)
    capw = lits.shape[2]

    grid = (m // bm, nnt, groups, nkt)
    y = pl.pallas_call(
        functools.partial(_kernel, s=s),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, tile_k), lambda i, j, g, k: (i, g * nkt + k)),
            pl.BlockSpec((1, bpt, slots),
                         lambda i, j, g, k: (g, j * nkt + k, 0)),
            pl.BlockSpec((1, bpt, capw),
                         lambda i, j, g, k: (g, j * nkt + k, 0)),
            pl.BlockSpec(lutw.shape, lambda i, j, g, k: (0, 0)),  # resident
            pl.BlockSpec((tile_n, 1), lambda i, j, g, k: (j, 0)),
            pl.BlockSpec((tile_n, 1), lambda i, j, g, k: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, tile_n), lambda i, j, g, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, tile_n), jnp.float32),
                        pltpu.VMEM((bm, 1), jnp.float32),
                        pltpu.VMEM((bpt, slots), jnp.int32)],
        interpret=interpret,
    )(_permute_x(x, tile_k, s), codes, lits, lutw,
      _permute_rows(scale, tile_n, rpb), _permute_rows(zero, tile_n, rpb))
    return _unpermute_cols(y, tile_n, rpb)


def _grouped_kernel(x_ref, codes_ref, lit_ref, lut_ref, scale_ref, zero_ref,
                    o_ref, acc_ref, sumx_ref, work_ref, *, s):
    k_idx = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        sumx_ref[...] = jnp.zeros_like(sumx_ref)

    tn, tk = scale_ref.shape[1], x_ref.shape[2]
    q = _decode_tile(codes_ref, lit_ref, lut_ref, work_ref, tn, tk, s)
    _accumulate(x_ref[...].reshape(x_ref.shape[-2:]), q, acc_ref, sumx_ref)

    @pl.when(k_idx == nk - 1)
    def _epilogue():
        sc = scale_ref[...].reshape(1, -1)                # (1, tn)
        z = zero_ref[...].reshape(1, -1)                  # (1, tn)
        o_ref[...] = (sc * (acc_ref[...] - sumx_ref[...] * z)
                      ).astype(o_ref.dtype).reshape(o_ref.shape)


@functools.partial(jax.jit, static_argnames=("shape", "tile_n", "tile_k",
                                             "bm", "out_dtype", "interpret"))
def grouped_fused_decode_matmul(x: jax.Array, codes: jax.Array,
                                literals: jax.Array, lut: jax.Array,
                                scale: jax.Array, zero: jax.Array, *,
                                shape: tuple, tile_n: int, tile_k: int,
                                bm: int = DEFAULT_BM, out_dtype=jnp.float32,
                                interpret: bool = False) -> jax.Array:
    """y[e] = x[e] @ dequant(decode(codes[e], literals[e])).T per expert.

    One launch for a whole MoE expert stack: x is the capacity-gathered
    token block (E, M, K), M % bm == 0 after the caller's padding; codes
    (E, nb, slots) / literals (E, nb, cap, S) are the stacked tile-major
    planes of the per-expert dense ``shape = (N, K)`` weights (uniform
    literal capacity across the stack); scale/zero (E, N, 1) f32.

    The grid is (E, M/bm, N/tile_n, K/tile_k) with the expert (plane) axis
    outermost: each step streams the compressed blocks of one
    (expert, tile_n, tile_k) weight tile into VMEM, decodes them
    in-register, and feeds the uint8 tile straight into the MXU — the same
    per-tile pipeline as :func:`fused_decode_matmul`, swept across expert
    planes, so dense expert weights never exist in HBM and peak HBM stays
    "compressed experts + gathered activations + one VMEM tile".
    """
    n, kdim = shape
    e, m, k2 = x.shape
    assert k2 == kdim, (x.shape, shape)
    assert codes.ndim == 3 and codes.shape[0] == e, (codes.shape, x.shape)
    assert n % tile_n == 0 and kdim % tile_k == 0, (shape, tile_n, tile_k)
    bm = min(bm, m)
    assert m % bm == 0, (m, bm)
    nnt, nkt = n // tile_n, kdim // tile_k
    _, nb, slots = codes.shape
    s = literals.shape[3]
    bpt = nb // (nnt * nkt)
    assert bpt * nnt * nkt == nb and bpt * slots * s == tile_n * tile_k, (
        codes.shape, literals.shape, shape, tile_n, tile_k)
    rpb = _rows_per_block(codes, tile_n, tile_k, s)
    lits = literal_words(literals)
    lutw = lut_words(lut)
    capw = lits.shape[2]

    grid = (e, m // bm, nnt, nkt)
    y = pl.pallas_call(
        functools.partial(_grouped_kernel, s=s),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, tile_k), lambda ei, i, j, k: (ei, i, k)),
            pl.BlockSpec((1, bpt, slots),
                         lambda ei, i, j, k: (ei, j * nkt + k, 0)),
            pl.BlockSpec((1, bpt, capw),
                         lambda ei, i, j, k: (ei, j * nkt + k, 0)),
            pl.BlockSpec(lutw.shape, lambda ei, i, j, k: (0, 0)),  # resident
            pl.BlockSpec((1, tile_n, 1), lambda ei, i, j, k: (ei, j, 0)),
            pl.BlockSpec((1, tile_n, 1), lambda ei, i, j, k: (ei, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bm, tile_n),
                               lambda ei, i, j, k: (ei, i, j)),
        out_shape=jax.ShapeDtypeStruct((e, m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, tile_n), jnp.float32),
                        pltpu.VMEM((bm, 1), jnp.float32),
                        pltpu.VMEM((bpt, slots), jnp.int32)],
        interpret=interpret,
    )(_permute_x(x, tile_k, s), codes, lits, lutw,
      _permute_rows(scale, tile_n, rpb), _permute_rows(zero, tile_n, rpb))
    return _unpermute_cols(y, tile_n, rpb)
