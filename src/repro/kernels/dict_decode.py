"""Blocked dictionary-decode Pallas TPU kernel, and the decode core it
shares with the fused matmul kernels.

The paper decompresses "layer by layer" on CPU; the TPU-native version
decodes *per VMEM tile* so decompression overlaps the surrounding matmuls
(DESIGN.md §2).  The decode LUT stays resident in VMEM for every grid step,
codes/literals stream through per block-chunk.

Mosaic lowers no gather from a table larger than one vreg row, so the
decode works on 32-bit *words* — one len-4 gram packed little-endian into
an int32 — and builds every lookup from in-vreg lane gathers
(``take_along_axis`` over 128 lanes):

  * dictionary slots: the LUT is resident as an (R, 128) word array.  The
    kernel takes one code vreg (8 block rows × 128 slots) at a time, in a
    loop over the vregs of a VMEM work ref, splits it once into
    ``hi = code >> 7`` and ``lo = code & 127``, and sweeps all R rows for
    it with its accumulator the only loop carry: row r broadcasts across
    the sublanes, a lane gather by ``lo`` reads it, and a select keeps it
    where ``hi == r``.  Every gather of the sweep shares ``lo``, so its
    lane pattern is set once per loop step, not once per row, and the
    accumulator stays in a register.  A loop step takes up to 128 rows: a
    lane gather's result comes back from the XLU long after it is issued,
    so a step issues many gathers before it waits on the first.  R ≤ 512
    for a full 64k dictionary (256 KiB).
  * literal slots: a block's escape grams are packed as ``cap`` words;
    ``rank = cumsum(is_escape) - 1`` (a triangular MXU matmul per 128-lane
    chunk — Mosaic has no cumsum) selects the row by the same
    gather-and-select over the literal chunks.

The decoded words are bit-identical to ``ref.dict_decode``'s bytes.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.codec import ESCAPE

DEFAULT_CHUNK = 16
LANES = 128
_SUBLANES = 8
_SWEEP_ROWS = 128


# ---------------------------------------------------------------------------
# Word packing (run by the jitted wrappers, outside the kernels).
# ---------------------------------------------------------------------------

def to_words(grams: jax.Array) -> jax.Array:
    """(..., S) uint8 grams → (...) int32 words, byte j at bits 8j.
    S ≤ 4 (the codec's len-4 grams fill one 32-bit word)."""
    s = grams.shape[-1]
    assert s <= 4, grams.shape
    g = grams.astype(jnp.int32)
    w = g[..., 0]
    for j in range(1, s):
        w = w | (g[..., j] << (8 * j))
    return w


def from_words(words: jax.Array, s: int) -> jax.Array:
    """Inverse of :func:`to_words`: (...) int32 → (..., S) uint8."""
    return jnp.stack([(words >> (8 * j)) & 0xFF for j in range(s)],
                     axis=-1).astype(jnp.uint8)


def lut_words(lut: jax.Array) -> jax.Array:
    """(n_codes, S) uint8 LUT → (R, 128) int32, R a multiple of 8; padded
    rows decode to 0 and are never selected by a valid code."""
    w = to_words(lut)
    per = LANES * _SUBLANES
    w = jnp.pad(w, (0, (-w.shape[0]) % per))
    return w.reshape(-1, LANES)


def literal_words(literals: jax.Array) -> jax.Array:
    """(..., cap, S) uint8 literal plane → (..., cap') int32 words, cap'
    rounded up to whole 128-lane chunks."""
    w = to_words(literals)
    pad = [(0, 0)] * (w.ndim - 1) + [(0, (-w.shape[-1]) % LANES)]
    return jnp.pad(w, pad)


# ---------------------------------------------------------------------------
# In-kernel decode core.
# ---------------------------------------------------------------------------

def _lane_chunks(a, width):
    return [a[:, i:i + width] for i in range(0, a.shape[1], width)]


def _escape_rank(is_esc, width):
    """Per-row inclusive cumsum of ``is_esc`` minus 1, as lane chunks.

    A 0/1 row chunk times an upper-triangular ones matrix on the MXU is its
    prefix count (exact: bf16 0/1 inputs, f32 sums ≤ slots); a running
    carry adds the counts of earlier chunks."""
    tri = (jax.lax.broadcasted_iota(jnp.int32, (width, width), 0)
           <= jax.lax.broadcasted_iota(jnp.int32, (width, width), 1))
    tri = tri.astype(jnp.float32).astype(jnp.bfloat16)
    carry = jnp.zeros((is_esc[0].shape[0], 1), jnp.float32)
    out = []
    for e in is_esc:
        cs = jax.lax.dot_general(
            e.astype(jnp.float32).astype(jnp.bfloat16), tri,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) + carry
        out.append(cs.astype(jnp.int32) - 1)
        carry = cs[:, width - 1:width]
    return out


def _lut_sweep(code, lut_ref):
    """Dictionary words of one code vreg: every LUT row in turn, the
    vreg's accumulator the only loop carry.  ``hi``/``lo`` are taken once,
    so each row's lane gather reuses the vreg's lane pattern.  A loop step
    takes ``_SWEEP_ROWS`` rows, or the largest power of two that divides
    R if that is fewer."""
    hi, lo = code >> 7, code & (LANES - 1)
    n_rows = lut_ref.shape[0]
    step = math.gcd(n_rows, _SWEEP_ROWS)

    def lut_step(i, acc):
        blk = lut_ref[pl.ds(i * step, step), :]
        for t in range(step):
            row = jnp.broadcast_to(blk[t:t + 1, :], (code.shape[0], LANES))
            acc = jnp.where(hi == i * step + t,
                            jnp.take_along_axis(row, lo, axis=1), acc)
        return acc

    return jax.lax.fori_loop(0, n_rows // step, lut_step, jnp.zeros_like(code))


def _lut_sweeps(work_ref, lut_ref, height, width):
    """Replace each (height, width) code vreg of ``work_ref`` by its
    dictionary words.  The vregs take turns in a loop, not unrolled, so
    the row sweep's code appears once in the kernel and compiles about as
    fast as an 8-row sweep."""
    rows, slots = work_ref.shape
    n_chunks = slots // width

    def one_vreg(j, carry):
        g = pl.multiple_of((j // n_chunks) * height, height)
        c = pl.multiple_of((j % n_chunks) * width, width)
        idx = (pl.ds(g, height), pl.ds(c, width))
        work_ref[idx] = _lut_sweep(work_ref[idx], lut_ref)
        return carry

    jax.lax.fori_loop(0, rows // height * n_chunks, one_vreg, 0)


def decode_words(codes, lit_words, lut_ref, work_ref):
    """Decode one group of blocks to int32 words.

    codes: (rows, W) integer codes, one block per row; lit_words:
    (rows, cap') int32, cap' a multiple of 128; lut_ref: the resident
    (R, 128) int32 LUT ref; work_ref: a (rows, W) int32 VMEM ref the
    dictionary sweep overwrites.  Returns (rows, W) int32 words."""
    codes = codes.astype(jnp.int32)
    rows, slots = codes.shape
    # 128-lane chunks; a row that is no whole number of them (small
    # shapes, interpret mode only) is one chunk
    width = LANES if slots % LANES == 0 else slots
    # code vregs: 8-row sublane groups of each chunk (all rows if they
    # are no whole number of groups)
    height = _SUBLANES if rows % _SUBLANES == 0 else rows
    code_c = _lane_chunks(codes, width)
    esc_c = [c == ESCAPE for c in code_c]
    work_ref[...] = codes
    _lut_sweeps(work_ref, lut_ref, height, width)
    from_dict = _lane_chunks(work_ref[...], width)

    rank_c = _escape_rank(esc_c, width)
    from_lit = [jnp.zeros_like(c) for c in code_c]
    for q, src in enumerate(_lane_chunks(lit_words, LANES)):
        from_lit = [jnp.where((rk >> 7) == q,
                              jnp.take_along_axis(src, rk & (LANES - 1),
                                                  axis=1), a)
                    for a, rk in zip(from_lit, rank_c)]
    words = [jnp.where(e, li, d)
             for e, li, d in zip(esc_c, from_lit, from_dict)]
    return words[0] if len(words) == 1 else jnp.concatenate(words, axis=1)


# ---------------------------------------------------------------------------
# Standalone decode kernel (the two-step path's first step).
# ---------------------------------------------------------------------------

def _kernel(codes_ref, lit_ref, lut_ref, o_ref):
    o_ref[...] = decode_words(codes_ref[...], lit_ref[...], lut_ref, o_ref)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def dict_decode(codes: jax.Array, literals: jax.Array, nlit: jax.Array,
                lut: jax.Array, *, chunk: int = DEFAULT_CHUNK,
                interpret: bool = False) -> jax.Array:
    """Decode (nb, slots) uint16 codes → (nb, slots·S) uint8 weights.

    ``nlit`` is carried for format completeness (the rank-gather clips past
    it harmlessly: rank rows beyond nlit are never selected because their
    slots are non-escape).
    """
    nb, slots = codes.shape
    s = literals.shape[2]
    chunk = min(chunk, nb)
    assert nb % chunk == 0, (nb, chunk)
    lits = literal_words(literals)
    lutw = lut_words(lut)
    words = pl.pallas_call(
        _kernel,
        grid=(nb // chunk,),
        in_specs=[
            pl.BlockSpec((chunk, slots), lambda b: (b, 0)),
            pl.BlockSpec((chunk, lits.shape[1]), lambda b: (b, 0)),
            pl.BlockSpec(lutw.shape, lambda b: (0, 0)),   # LUT resident
        ],
        out_specs=pl.BlockSpec((chunk, slots), lambda b: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, slots), jnp.int32),
        interpret=interpret,
    )(codes, lits, lutw)
    return from_words(words, s).reshape(nb, slots * s)
