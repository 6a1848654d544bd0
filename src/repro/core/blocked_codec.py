"""TPU-parallel blocked dictionary codec — the hardware adaptation.

The paper's escape stream (``codec.py``) decodes serially: the position of
codeword *i* depends on how many escapes precede it.  On a TPU that is a
non-starter — decode must be a data-parallel gather.  This module keeps the
paper's *dictionary* (same tables, same len-4 byte grams) but re-lays the
stream into a fixed-rate blocked format:

  per tensor, blocks of ``block_weights`` quantized uint8 weights
    codes:    uint16[n_blocks, slots]   slot = one len-S gram; ESCAPE literal
    literals: uint8 [n_blocks, lit_cap, S]  escape grams, packed per block
    nlit:     int32 [n_blocks]          how many escapes in each block

Every block decodes independently: ``rank = cumsum(is_escape) - 1`` inside
the block recovers each escape's literal row.  All three planes are
rectangular → shardable with a plain PartitionSpec on the block axis, and
encode aligns block boundaries to TP shard boundaries (``shard_blocks``).

``decode_blocked_jnp`` is the pure-jnp oracle; the Pallas VMEM kernel lives
in ``repro.kernels.dict_decode``.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from .codec import ESCAPE, DEFAULT_SEQ_LEN, as_index

DEFAULT_BLOCK_WEIGHTS = 4096


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class BlockedCompressed:
    """One tensor in the blocked format (+ shared LUT reference)."""

    codes: jax.Array      # uint16[n_blocks, slots]
    literals: jax.Array   # uint8[n_blocks, lit_cap, S]
    nlit: jax.Array       # int32[n_blocks]
    lut: jax.Array        # uint8[n_codes, S] — usually shared across tensors
    orig_len: int         # static
    shape: tuple          # static
    seq_len: int = DEFAULT_SEQ_LEN

    def tree_flatten(self):
        return ((self.codes, self.literals, self.nlit, self.lut),
                (self.orig_len, self.shape, self.seq_len))

    @classmethod
    def tree_unflatten(cls, aux, children):
        codes, literals, nlit, lut = children
        orig_len, shape, seq_len = aux
        return cls(codes, literals, nlit, lut, orig_len, shape, seq_len)

    @property
    def payload_nbytes(self) -> int:
        """Bytes for this tensor, excluding the (shared) LUT."""
        return int(self.codes.size * 2 + self.literals.size + self.nlit.size * 4)

    @property
    def slots(self) -> int:
        return self.codes.shape[1]


def build_lut(table: dict, seq_len: int = DEFAULT_SEQ_LEN) -> np.ndarray:
    """Dense decode LUT from a {gram-tuple -> code} table (codec.py builder).

    Row ``code`` holds the gram. Row for ESCAPE never exists (codes are dense
    in [0, len(table))), but we pad one zero row so LUT[code] is always safe.
    """
    n = len(table)
    lut = np.zeros((max(n, 1) + 1, seq_len), dtype=np.uint8)
    for seq, code in table.items():
        lut[code] = np.asarray(seq, dtype=np.uint8)
    return lut


def encode_blocked(weights: np.ndarray, table,
                   lut: np.ndarray | None = None,
                   block_weights: int = DEFAULT_BLOCK_WEIGHTS,
                   seq_len: int = DEFAULT_SEQ_LEN) -> BlockedCompressed:
    """Encode a uint8 tensor into the blocked format (host-side numpy).
    ``table`` is the {gram: code} dict or a prebuilt ``codec.GramIndex``."""
    assert block_weights % seq_len == 0
    flat = np.ascontiguousarray(weights).reshape(-1).astype(np.uint8)
    orig_len = flat.size
    slots_pb = block_weights // seq_len

    pad = (-orig_len) % block_weights
    if pad:
        flat = np.concatenate([flat, np.zeros(pad, np.uint8)])
    grams = flat.reshape(-1, seq_len)
    n_blocks = len(grams) // slots_pb

    index = as_index(table, seq_len)
    codes_flat = index.lookup(grams, ESCAPE).astype(np.uint16)

    codes = codes_flat.reshape(n_blocks, slots_pb)
    esc = codes == ESCAPE
    nlit = esc.sum(axis=1).astype(np.int32)
    lit_cap = int(nlit.max()) if n_blocks else 0
    lit_cap = max(lit_cap, 1)
    literals = np.zeros((n_blocks, lit_cap, seq_len), dtype=np.uint8)
    # each escape's row is its rank among its block's escapes
    rank = np.cumsum(esc, axis=1) - 1
    blk, slot = np.nonzero(esc)
    literals[blk, rank[blk, slot]] = grams.reshape(
        n_blocks, slots_pb, seq_len)[blk, slot]

    if lut is None:
        lut = build_lut(index.table, seq_len)
    return BlockedCompressed(
        codes=jnp.asarray(codes), literals=jnp.asarray(literals),
        nlit=jnp.asarray(nlit), lut=jnp.asarray(lut),
        orig_len=orig_len, shape=tuple(weights.shape), seq_len=seq_len)


def decode_blocked_jnp(bc: BlockedCompressed) -> jax.Array:
    """Pure-jnp parallel decode — oracle for the Pallas kernel.

    Fully vectorized: dictionary gather + per-block escape-rank gather.
    """
    nb, slots = bc.codes.shape
    s = bc.seq_len
    codes = bc.codes.astype(jnp.int32)
    is_esc = codes == ESCAPE
    # Dictionary path: LUT gather (escape rows read row 0 harmlessly).
    safe = jnp.where(is_esc, 0, codes)
    from_dict = bc.lut[safe]                              # (nb, slots, s)
    # Literal path: rank of each escape within its block recovers its row.
    rank = jnp.cumsum(is_esc.astype(jnp.int32), axis=1) - 1
    rank = jnp.clip(rank, 0, bc.literals.shape[1] - 1)
    from_lit = jax.vmap(lambda lit, r: lit[r])(bc.literals, rank)  # (nb, slots, s)
    out = jnp.where(is_esc[:, :, None], from_lit, from_dict)
    return out.reshape(-1)[: bc.orig_len]


def decode_to(bc: BlockedCompressed, scale: jax.Array, zero: jax.Array,
              dtype=jnp.bfloat16) -> jax.Array:
    """Decode + dequantize to a dense float tensor of the original shape.

    ``scale``/``zero`` follow the per-channel row layout of
    ``QuantConfig(granularity='per_channel')`` against ``bc.shape``.
    """
    flat = decode_blocked_jnp(bc).astype(jnp.float32)
    x = flat.reshape(bc.shape)
    # scale/zero broadcast: (rows, 1) against (rows, cols)
    if scale.ndim == x.ndim - 1 or (scale.ndim == 2 and x.ndim == 2):
        x = (x - zero) * scale
    else:
        x = (x - zero.reshape(-1)) * scale.reshape(-1)
    return x.astype(dtype)


def blocked_nbytes(bc: BlockedCompressed, include_lut: bool = False) -> int:
    n = bc.payload_nbytes
    if include_lut:
        n += int(bc.lut.size)
    return n


# ---------------------------------------------------------------------------
# Tile-aligned layout for the fused decode→dequant→matmul megakernel.
#
# The fused kernel (repro.kernels.fused_decode_matmul) decodes only the
# compressed blocks covering the current (tile_n, tile_k) weight tile inside
# the matmul grid.  That requires each tile to map to a whole number of
# blocks: we re-order the dense (N, K) stream *tile-major* — tile (j, k)
# (row-major over the (N/tile_n, K/tile_k) tile grid) is flattened
# contiguously, so its blocks are the contiguous row range
# [t·bpt, (t+1)·bpt) of the codes/literals planes, with t = j·n_kt + k and
# bpt = tile_n·tile_k / block_weights.
# ---------------------------------------------------------------------------

DEFAULT_TILE_N = 128   # matches dequant_matmul.DEFAULT_BN
DEFAULT_TILE_K = 512   # matches dequant_matmul.DEFAULT_BK
LANES = 128
SUBLANES = 8
# Largest whole-dim tile taken when no 128-aligned divisor exists (e.g.
# deepseek's 576-row wkv_a): the decoded int32 tile must fit in VMEM.
MAX_TILE_WEIGHTS = 1 << 19


def _pow2_divisor(n: int, cap: int) -> int:
    """Largest power of two that divides ``n`` and is ≤ ``cap``."""
    d = n & (-n)  # largest power-of-2 factor
    return min(d, 1 << (cap.bit_length() - 1))


def _lane_tile(dim: int, cap: int) -> int:
    """A tile of ``dim`` the TPU kernel can block: the largest power-of-two
    divisor up to ``cap`` when that is a whole number of 128-lane vregs,
    else the whole dim (a block may always span its array's full dim)."""
    t = _pow2_divisor(dim, cap)
    return t if t % LANES == 0 or t == dim else dim


def fused_block_weights(tile_n: int, tile_k: int,
                        block_weights: int = DEFAULT_BLOCK_WEIGHTS,
                        seq_len: int = DEFAULT_SEQ_LEN):
    """Block size of the fused layout for a (tile_n, tile_k) tile: whole
    weight rows of the tile (``tile_k`` times a power of two dividing
    ``tile_n``), up to the ``block_weights`` cap, and small enough that a
    tile holds a multiple of 8 blocks where it can (the kernel blocks the
    planes by whole sublane groups) — the single source of truth for the
    layout's actual block size.  None when a row is not a whole number of
    ``seq_len`` grams."""
    if tile_k % seq_len:
        return None
    r = _pow2_divisor(tile_n, max(1, block_weights // tile_k))
    while r > 1 and (tile_n // r) % SUBLANES:
        r //= 2
    return tile_k * r


def choose_fused_tiles(shape: tuple, block_weights: int = DEFAULT_BLOCK_WEIGHTS,
                       seq_len: int = DEFAULT_SEQ_LEN,
                       max_tile_n: int = DEFAULT_TILE_N,
                       max_tile_k: int = DEFAULT_TILE_K,
                       shards: tuple = (1, 1)):
    """Pick (tile_n, tile_k, block_weights) for the fused-kernel layout.

    Tiles are divisors of (N, K) — never round-ups, so no padding is ever
    needed and decoded bytes are bit-identical to the linear layout's —
    that the TPU kernel can block: 128-lane multiples up to the kernel's
    default matmul block, or the whole dim (see :func:`_lane_tile`).  A
    narrow ``tile_k`` grows ``tile_n`` toward the default tile volume so a
    tile still holds a sublane-aligned number of blocks.  Returns None when
    no such tile fits ``MAX_TILE_WEIGHTS`` or holds whole grams (fused
    layout unavailable; ``engine.build_serve_params`` then stores the
    weight quant-only).

    ``shards=(sn, sk)``: intended mesh sharding of the dense dims.  Tiles
    are chosen to divide the *per-shard* dims (n/sn, k/sk) so the
    shard-mapped fused path can split the tile-major block axis along
    whole out-tile bands (see ``kernels.ops``); a per-shard divisor also
    divides the full dim, so the single-device fused path is unaffected.
    A shard count that does not divide its dim is ignored (that axis
    cannot take the sharded fused path anyway).
    """
    n, k = int(shape[0]), int(shape[1])
    if n <= 0 or k <= 0:
        return None
    sn, sk = int(shards[0]) or 1, int(shards[1]) or 1
    if sn > 1 and n % sn == 0:
        n //= sn
    if sk > 1 and k % sk == 0:
        k //= sk
    tk = _lane_tile(k, max_tile_k)
    tn = _lane_tile(n, max(max_tile_n, max_tile_n * max_tile_k // tk))
    if tn * tk > MAX_TILE_WEIGHTS:
        return None
    bw = fused_block_weights(tn, tk, block_weights, seq_len)
    if bw is None:
        return None
    return tn, tk, bw


def tile_stream(w2d: np.ndarray, tile_n: int, tile_k: int) -> np.ndarray:
    """Re-order a (N, K) array into the tile-major flat byte stream."""
    n, k = w2d.shape
    assert n % tile_n == 0 and k % tile_k == 0, (w2d.shape, tile_n, tile_k)
    return (np.ascontiguousarray(w2d)
            .reshape(n // tile_n, tile_n, k // tile_k, tile_k)
            .transpose(0, 2, 1, 3).reshape(-1))


def untile_flat(flat, shape: tuple, tile_n: int, tile_k: int):
    """Inverse of :func:`tile_stream` for (..., N·K) flats (jnp or numpy)."""
    n, k = shape
    lead = flat.shape[:-1]
    x = flat.reshape(lead + (n // tile_n, k // tile_k, tile_n, tile_k))
    x = jnp.moveaxis(x, -3, -2) if isinstance(flat, jax.Array) else \
        np.moveaxis(x, -3, -2)
    return x.reshape(lead + (n, k))


def encode_blocked_tiled(weights2d: np.ndarray, table,
                         lut: np.ndarray | None = None,
                         tile_n: int = DEFAULT_TILE_N,
                         tile_k: int = DEFAULT_TILE_K,
                         block_weights: int = DEFAULT_BLOCK_WEIGHTS,
                         seq_len: int = DEFAULT_SEQ_LEN) -> BlockedCompressed:
    """Encode a (N, K) uint8 tensor in the fused-kernel tile-major layout.

    ``block_weights`` is a *cap*: the actual block size is whole tile rows
    (see :func:`fused_block_weights`), so a tile always holds a whole
    number of blocks and a block a whole number of rows.
    """
    n, k = weights2d.shape
    bw = fused_block_weights(tile_n, tile_k, block_weights, seq_len)
    assert bw is not None and (tile_n * tile_k) % bw == 0, (
        tile_n, tile_k, bw, seq_len)
    stream = tile_stream(np.asarray(weights2d, dtype=np.uint8),
                         tile_n, tile_k)
    bc = encode_blocked(stream, table, lut=lut, block_weights=bw,
                        seq_len=seq_len)
    assert bc.orig_len == n * k  # tiles divide exactly; no codec padding
    return dataclasses.replace(bc, shape=(n, k))


def shard_aligned_block_weights(tensor_cols: int, tp_shards: int,
                                block_weights: int = DEFAULT_BLOCK_WEIGHTS,
                                seq_len: int = DEFAULT_SEQ_LEN) -> int:
    """Pick a block size so TP shard boundaries coincide with block
    boundaries: shard_size % block == 0 when possible, else shrink block to
    gcd alignment (never below seq_len)."""
    shard = tensor_cols // tp_shards if tp_shards and tensor_cols % tp_shards == 0 else tensor_cols
    b = min(block_weights, max(seq_len, shard))
    while shard % b and b > seq_len:
        b //= 2
    return max(b - (b % seq_len), seq_len)
