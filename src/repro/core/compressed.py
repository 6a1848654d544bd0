"""Compressed parameter containers — how models carry Tiny-QMoE weights.

A linear weight in ``mode='compressed'`` serving is stored as a
:class:`PackedLinear`: blocked-codec planes (codes/literals/nlit) plus the
quantizer's per-channel (scale, zero).  The decode LUT is *shared* across the
whole model (one dictionary per model, as in the paper) and passed alongside
the params, so stacking layers for ``lax.scan`` never duplicates it.

Three weight modes, matching the paper's evaluation triple:
  dense      — bf16 weights (paper's uncompressed row)
  quant      — int8 payload + scale/zero (paper's "Quantized" row)
  compressed — PackedLinear (paper's "Compressed" row)
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from . import blocked_codec as bcdc
from .blocked_codec import BlockedCompressed, DEFAULT_BLOCK_WEIGHTS
from .codec import DEFAULT_SEQ_LEN
from .quant import QuantConfig, quantize

WeightMode = str  # 'dense' | 'quant' | 'compressed'


@jax.tree_util.register_pytree_with_keys_class
@dataclasses.dataclass
class QuantLinear:
    """int8 weight + per-channel affine params (mode='quant')."""

    values: jax.Array   # uint8[out, in] (or [L, out, in] stacked)
    scale: jax.Array    # f32[out, 1]
    zero: jax.Array     # f32[out, 1]

    def tree_flatten_with_keys(self):
        ga = jax.tree_util.GetAttrKey
        return (((ga("values"), self.values), (ga("scale"), self.scale),
                 (ga("zero"), self.zero)), ())

    def tree_flatten(self):
        return (self.values, self.scale, self.zero), ()

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    def materialize(self, dtype=jnp.bfloat16) -> jax.Array:
        return ((self.values.astype(jnp.float32) - self.zero) * self.scale
                ).astype(dtype)


@jax.tree_util.register_pytree_with_keys_class
@dataclasses.dataclass
class PackedLinear:
    """Blocked-compressed int8 weight + quantizer params (mode='compressed').

    Shapes (single layer):
      codes    uint16[nb, slots]
      literals uint8 [nb, cap, S]
      nlit     int32 [nb]
      scale    f32   [out, 1]
      zero     f32   [out, 1]
    Stacked layer variants carry a leading L dim on every plane.

    Registered *with keys* so partition rules see ".../w_gate/codes" paths —
    plain node registration loses the names and every plane silently
    replicates (51 GiB/dev of codes at llama3-405b; §Perf iteration 4).
    """

    codes: jax.Array
    literals: jax.Array
    nlit: jax.Array
    scale: jax.Array
    zero: jax.Array
    shape: tuple          # static (out, in) of the dense weight
    seq_len: int = DEFAULT_SEQ_LEN
    # consumer contracts the model-sharded dim (wo/w_down): the decoded
    # dense weight must reshard (u8 bytes) instead of the activations
    # (§Perf P2); set from the partition rule table at build/spec time.
    row_parallel: bool = False
    # Fused-kernel tile layout (core.blocked_codec tile-major ordering):
    # tile_n > 0 means blocks are grouped per (tile_n, tile_k) weight tile
    # so the fused decode→dequant→matmul megakernel can stream them; 0 =
    # linear layout (two-step decode path only).
    tile_n: int = 0
    tile_k: int = 0

    def tree_flatten_with_keys(self):
        ga = jax.tree_util.GetAttrKey
        return (((ga("codes"), self.codes), (ga("literals"), self.literals),
                 (ga("nlit"), self.nlit), (ga("scale"), self.scale),
                 (ga("zero"), self.zero)),
                (self.shape, self.seq_len, self.row_parallel,
                 self.tile_n, self.tile_k))

    def tree_flatten(self):
        return ((self.codes, self.literals, self.nlit, self.scale, self.zero),
                (self.shape, self.seq_len, self.row_parallel,
                 self.tile_n, self.tile_k))

    @classmethod
    def tree_unflatten(cls, aux, children):
        codes, literals, nlit, scale, zero = children
        shape, seq_len, row_parallel, tile_n, tile_k = aux
        return cls(codes, literals, nlit, scale, zero, shape, seq_len,
                   row_parallel, tile_n, tile_k)

    @property
    def payload_nbytes(self) -> int:
        return int(self.codes.size * 2 + self.literals.size + self.nlit.size * 4)

    def degather(self) -> "PackedLinear":
        """Reshard planes to model-axis-only before decoding.

        FSDP-stored planes shard (data×model); without this, SPMD decodes
        locally and then all-gathers the DEQUANTIZED f32 dense weight over
        the data axis — 3.25 GiB/layer on llama3-405b decode, 410 GiB/step
        (§Perf D1).  Constraining the planes first moves the gather onto
        the compressed u16/u8 bytes (~7× fewer, and it IS the paper's
        point: ship compressed bytes, decode close to compute).
        """
        from repro.sharding.partition import constrain

        def on_block_axis(x, rank):
            # keep the pod dim in the plane sharding: the degather then
            # spans only the in-pod data axis (ICI), never the cross-pod
            # DCN links — each pod decodes its row range and the small
            # activation combine crosses pods instead (§Perf D1b).
            lead = x.ndim - rank
            return constrain(x, *([None] * lead), ("pod", "model"),
                             *([None] * (rank - 1)))

        return PackedLinear(
            codes=on_block_axis(self.codes, 2),
            literals=on_block_axis(self.literals, 3),
            nlit=on_block_axis(self.nlit, 1),
            scale=self.scale, zero=self.zero,
            shape=self.shape, seq_len=self.seq_len,
            row_parallel=self.row_parallel,
            tile_n=self.tile_n, tile_k=self.tile_k)

    def materialize_int8(self, lut: jax.Array) -> jax.Array:
        """Decode only (uint8 codes of the quantized weight).  Handles
        arbitrary leading (stacked layer/expert) dims: blocks decode
        independently, so (..., nb, slots) reshapes to (-1, slots)."""
        self = self.degather()
        lead = self.codes.shape[:-2]
        nb, slots = self.codes.shape[-2:]
        cap = self.literals.shape[-2]
        n_dense = int(np.prod(self.shape))
        codes = self.codes.reshape(-1, slots)
        lits = self.literals.reshape(-1, cap, self.seq_len)
        nlit = self.nlit.reshape(-1)
        bc = BlockedCompressed(codes, lits, nlit, lut,
                               orig_len=codes.shape[0] * slots * self.seq_len,
                               shape=(), seq_len=self.seq_len)
        flat = bcdc.decode_blocked_jnp(bc)
        per = nb * slots * self.seq_len
        flat = flat.reshape((-1, per))[:, :n_dense]
        if self.tile_n:  # undo the fused-kernel tile-major ordering
            return bcdc.untile_flat(flat.reshape(lead + (n_dense,)),
                                    tuple(self.shape),
                                    self.tile_n, self.tile_k)
        return flat.reshape(lead + tuple(self.shape))

    def materialize(self, lut: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
        """Decode + dequantize to the dense weight (any leading dims)."""
        w = self.materialize_int8(lut).astype(jnp.float32)
        return ((w - self.zero) * self.scale).astype(dtype)


@jax.tree_util.register_pytree_with_keys_class
@dataclasses.dataclass
class TiledPackedLinear:
    """2D-sharded compressed weight: column tiles on the data axis.

    The plain PackedLinear FSDPs its block axis across (data×model) and
    must gather the planes on every use — at decode that streams the whole
    compressed model over ICI per token (§Perf D1/D2).  Here the dense
    (out, in) weight is split into ``tiles`` column groups; each tile is
    encoded separately, the tile axis shards on (pod, data) and the block
    axis on model, so every device permanently owns a (out/model ×
    in/data) compressed tile: NO weight collective at use time.  The
    matmul contracts x's feature dim against the data axis (activation
    reshard, ~MB) — classic 2D tensor parallelism, applied to the paper's
    compressed format.

    Plane names carry a ``_t`` suffix so partition rules can tell tiled
    planes from stacked-expert PackedLinear planes of equal rank.

    Shapes (single layer):
      codes_t    uint16[tiles, nb, slots]
      literals_t uint8 [tiles, nb, cap, S]
      nlit_t     int32 [tiles, nb]
      scale/zero f32   [out, 1]

    ``tile_n/tile_k > 0``: each column tile is encoded in the fused-kernel
    tile-major layout (``blocked_codec.encode_blocked_tiled`` over the
    (out, in/tiles) sub-weight), so the shard-mapped fused megakernel can
    run each device's resident tile without materializing it; 0 = linear
    per-tile layout (dense-materialize 2D-TP path only).
    """

    codes: jax.Array
    literals: jax.Array
    nlit: jax.Array
    scale: jax.Array
    zero: jax.Array
    shape: tuple          # static (out, in) of the dense weight
    seq_len: int = DEFAULT_SEQ_LEN
    tile_n: int = 0
    tile_k: int = 0

    def tree_flatten_with_keys(self):
        ga = jax.tree_util.GetAttrKey
        return (((ga("codes_t"), self.codes),
                 (ga("literals_t"), self.literals),
                 (ga("nlit_t"), self.nlit), (ga("scale"), self.scale),
                 (ga("zero"), self.zero)),
                (self.shape, self.seq_len, self.tile_n, self.tile_k))

    def tree_flatten(self):
        return ((self.codes, self.literals, self.nlit, self.scale,
                 self.zero),
                (self.shape, self.seq_len, self.tile_n, self.tile_k))

    @classmethod
    def tree_unflatten(cls, aux, children):
        codes, literals, nlit, scale, zero = children
        shape, seq_len, tile_n, tile_k = aux
        return cls(codes, literals, nlit, scale, zero, shape, seq_len,
                   tile_n, tile_k)

    @property
    def tiles(self) -> int:
        return self.codes.shape[-3]

    @property
    def payload_nbytes(self) -> int:
        return int(self.codes.size * 2 + self.literals.size +
                   self.nlit.size * 4)

    def materialize_int8(self, lut: jax.Array) -> jax.Array:
        """Decode every tile locally → dense (..., out, in) uint8 whose in
        dim is tile-sharded (no plane collectives)."""
        lead = self.codes.shape[:-3]
        tiles, nb, slots = self.codes.shape[-3:]
        cap = self.literals.shape[-2]
        out, in_full = self.shape
        in_t = in_full // tiles
        codes = self.codes.reshape(-1, slots)
        lits = self.literals.reshape(-1, cap, self.seq_len)
        nlit = self.nlit.reshape(-1)
        bc = BlockedCompressed(codes, lits, nlit, lut,
                               orig_len=codes.shape[0] * slots * self.seq_len,
                               shape=(), seq_len=self.seq_len)
        flat = bcdc.decode_blocked_jnp(bc)
        per_tile = nb * slots * self.seq_len
        flat = flat.reshape((-1, tiles, per_tile))[..., : out * in_t]
        if self.tile_n:  # undo the per-tile fused tile-major ordering
            flat = bcdc.untile_flat(flat, (out, in_t), self.tile_n,
                                    self.tile_k)
        w = flat.reshape(lead + (tiles, out, in_t))
        w = jnp.moveaxis(w, -3, -2)                      # (..., out, tiles, in_t)
        return w.reshape(lead + (out, in_full))

    def materialize(self, lut: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
        w = self.materialize_int8(lut).astype(jnp.float32)
        return ((w - self.zero) * self.scale).astype(dtype)


def encode_tiled_planes(vals: np.ndarray, table: dict, lut: np.ndarray,
                        tiles: int,
                        block_weights: int = DEFAULT_BLOCK_WEIGHTS,
                        tile=None, shards: tuple = (1, 1)):
    """Encode a quantized (out, in) uint8 tensor as per-column-tile planes.

    Returns ``(bcs, tile_n, tile_k)`` — one BlockedCompressed per column
    tile (literal caps NOT yet unified; callers pad to a shared cap).
    ``tile=(tn, tk)`` or ``"auto"`` selects the fused-kernel tile-major
    layout per tile; ``shards=(model_shards, 1)`` makes the auto choice
    divide the per-model-shard out dim (see
    :func:`blocked_codec.choose_fused_tiles`).  ``tile=None`` keeps the
    legacy linear per-tile layout (tile_n = tile_k = 0).
    """
    out, in_full = vals.shape
    assert in_full % tiles == 0, (vals.shape, tiles)
    in_t = in_full // tiles
    if tile == "auto":
        picked = bcdc.choose_fused_tiles((out, in_t), block_weights,
                                         shards=shards)
        tile = picked[:2] if picked else None
    bw = min(block_weights, ((out * in_t) // DEFAULT_SEQ_LEN)
             * DEFAULT_SEQ_LEN) or DEFAULT_SEQ_LEN
    bcs = []
    for t in range(tiles):
        sub = np.ascontiguousarray(vals[:, t * in_t:(t + 1) * in_t])
        if tile is not None:
            bcs.append(bcdc.encode_blocked_tiled(
                sub, table, lut=lut, tile_n=tile[0], tile_k=tile[1],
                block_weights=bw))
        else:
            bcs.append(bcdc.encode_blocked(sub, table, lut=lut,
                                           block_weights=bw))
    tn, tk = tile if tile is not None else (0, 0)
    return bcs, tn, tk


def pad_literals(literals: jax.Array, cap: int) -> jax.Array:
    """Pad a (..., cur_cap, S) literal plane up to a uniform capacity."""
    cur = literals.shape[-2]
    if cur > cap:
        raise ValueError(f"lit_cap {cap} < needed {cur}")
    if cur == cap:
        return literals
    widths = [(0, 0)] * literals.ndim
    widths[-2] = (0, cap - cur)
    return jnp.pad(literals, widths)


def pack_linear_tiled(w: jax.Array, table: dict, lut: np.ndarray,
                      tiles: int, qcfg: QuantConfig | None = None,
                      block_weights: int = DEFAULT_BLOCK_WEIGHTS,
                      lit_cap: int | None = None,
                      tile=None, shards: tuple = (1, 1)) -> TiledPackedLinear:
    """Quantize + encode each column tile separately (host side).

    ``tile``/``shards`` select the fused tile-major per-tile layout (see
    :func:`encode_tiled_planes`); the default keeps the linear layout.
    """
    ql = quantize_linear(w, qcfg)
    bcs, tn, tk = encode_tiled_planes(
        np.asarray(ql.values, dtype=np.uint8), table, lut, tiles,
        block_weights=block_weights, tile=tile, shards=shards)
    cap = lit_cap if lit_cap is not None else max(
        bc.literals.shape[1] for bc in bcs)
    return TiledPackedLinear(
        codes=jnp.stack([bc.codes for bc in bcs]),
        literals=jnp.stack([pad_literals(bc.literals, cap) for bc in bcs]),
        nlit=jnp.stack([bc.nlit for bc in bcs]),
        scale=ql.scale, zero=ql.zero,
        shape=tuple(w.shape), seq_len=DEFAULT_SEQ_LEN,
        tile_n=tn, tile_k=tk)


def planned_tiled_specs(shape: tuple, tiles: int, *, stacked: tuple = (),
                        block_weights: int = DEFAULT_BLOCK_WEIGHTS,
                        seq_len: int = DEFAULT_SEQ_LEN,
                        lit_cap_frac: float = 0.25,
                        tile_n: int = 0,
                        tile_k: int = 0) -> TiledPackedLinear:
    """ShapeDtypeStruct stand-in for a TiledPackedLinear.

    ``tile_n/tile_k`` mirror the fused tile-major layout of
    :func:`pack_linear_tiled` (block size shrunk to divide the tile
    volume); 0 keeps the linear per-tile layout.
    """
    out, in_full = shape
    in_t = in_full // tiles
    n = out * in_t
    bw = min(block_weights, (n // seq_len) * seq_len) or seq_len
    if tile_n:
        bw = bcdc.fused_block_weights(tile_n, tile_k, bw, seq_len)
        nb = n // bw
    else:
        nb = -(-n // bw)
    slots = bw // seq_len
    cap = max(1, int(slots * lit_cap_frac))
    sds = jax.ShapeDtypeStruct
    return TiledPackedLinear(
        codes=sds(stacked + (tiles, nb, slots), jnp.uint16),
        literals=sds(stacked + (tiles, nb, cap, seq_len), jnp.uint8),
        nlit=sds(stacked + (tiles, nb), jnp.int32),
        scale=sds(stacked + (out, 1), jnp.float32),
        zero=sds(stacked + (out, 1), jnp.float32),
        shape=tuple(shape), seq_len=seq_len, tile_n=tile_n, tile_k=tile_k)


# ---------------------------------------------------------------------------
# Host-side packing of real weights.
# ---------------------------------------------------------------------------

def quantize_linear(w: jax.Array, qcfg: QuantConfig | None = None) -> QuantLinear:
    """Quantize a (out, in) weight to the QuantLinear container."""
    qcfg = qcfg or QuantConfig(bits=8, granularity="per_channel")
    qt = quantize(jnp.asarray(w), qcfg)
    values = qt.values.reshape(w.shape)  # per_channel rows == w rows
    return QuantLinear(values=values.astype(jnp.uint8),
                       scale=qt.scale, zero=qt.zero)


def pack_expert_stack(ws, table: dict | None = None,
                      block_weights: int = DEFAULT_BLOCK_WEIGHTS,
                      tile="auto"):
    """Quantize + blocked-compress a list of same-shape expert weights into
    one stacked PackedLinear (leading expert axis on every plane, one
    shared dictionary, uniform literal cap; tile-major by default) — the
    host-side mirror of what ``engine.build_serve_params`` emits for
    ``experts/w_*`` leaves.  Returns ``(packed, lut)`` with ``lut`` as a
    device array.  ``tile=None`` keeps the linear layout (grouped-kernel
    ineligible; two-step fallback), for tests of the fallback path.
    """
    from .codec import find_frequent_sequences

    n, k = ws[0].shape
    qls = [quantize_linear(jnp.asarray(w)) for w in ws]
    if table is None:
        table = find_frequent_sequences([np.asarray(q.values) for q in qls])
    lut = bcdc.build_lut(table)
    if tile == "auto":
        picked = bcdc.choose_fused_tiles((n, k), block_weights)
        tile = picked[:2] if picked else None
    if tile is not None:
        tn, tk = tile
        bcs = [bcdc.encode_blocked_tiled(np.asarray(q.values), table,
                                         lut=lut, tile_n=tn, tile_k=tk,
                                         block_weights=block_weights)
               for q in qls]
    else:
        tn, tk = 0, 0
        bcs = [bcdc.encode_blocked(np.asarray(q.values), table, lut=lut,
                                   block_weights=block_weights)
               for q in qls]
    cap = max(bc.literals.shape[1] for bc in bcs)
    packed = PackedLinear(
        codes=jnp.stack([bc.codes for bc in bcs]),
        literals=jnp.stack([pad_literals(bc.literals, cap) for bc in bcs]),
        nlit=jnp.stack([bc.nlit for bc in bcs]),
        scale=jnp.stack([q.scale for q in qls]),
        zero=jnp.stack([q.zero for q in qls]),
        shape=(n, k), tile_n=tn, tile_k=tk)
    return packed, jnp.asarray(lut)


def pack_linear(w: jax.Array, table: dict, lut: np.ndarray,
                qcfg: QuantConfig | None = None,
                block_weights: int = DEFAULT_BLOCK_WEIGHTS,
                lit_cap: int | None = None,
                tile: tuple | None = None) -> PackedLinear:
    """Quantize + blocked-compress a dense weight (host side).

    ``lit_cap`` forces a uniform literal capacity (needed when stacking
    layers); pass None to use the tensor's own max.  ``tile=(tile_n,
    tile_k)`` encodes in the fused-megakernel tile-major layout (pass
    ``"auto"`` to let :func:`blocked_codec.choose_fused_tiles` pick); None
    keeps the linear layout.
    """
    ql = quantize_linear(w, qcfg)
    if tile == "auto":
        picked = bcdc.choose_fused_tiles(w.shape, block_weights)
        tile = picked[:2] if picked else None
    if tile is not None:
        tn, tk = tile
        bc = bcdc.encode_blocked_tiled(np.asarray(ql.values), table, lut=lut,
                                       tile_n=tn, tile_k=tk,
                                       block_weights=block_weights)
    else:
        bc = bcdc.encode_blocked(np.asarray(ql.values), table,
                                 lut=lut, block_weights=block_weights)
    literals = bc.literals
    if lit_cap is not None:
        cur = literals.shape[1]
        if cur < lit_cap:
            pad = jnp.zeros((literals.shape[0], lit_cap - cur,
                             literals.shape[2]), jnp.uint8)
            literals = jnp.concatenate([literals, pad], axis=1)
        elif cur > lit_cap:
            raise ValueError(f"lit_cap {lit_cap} < needed {cur}")
    tn, tk = tile if tile is not None else (0, 0)
    return PackedLinear(codes=bc.codes, literals=literals, nlit=bc.nlit,
                        scale=ql.scale, zero=ql.zero, shape=tuple(w.shape),
                        seq_len=bc.seq_len, tile_n=tn, tile_k=tk)


# ---------------------------------------------------------------------------
# Dry-run shape planning (no data, deterministic shapes).
# ---------------------------------------------------------------------------

def planned_packed_specs(shape: tuple, *, stacked: tuple = (),
                         block_weights: int = DEFAULT_BLOCK_WEIGHTS,
                         seq_len: int = DEFAULT_SEQ_LEN,
                         lit_cap_frac: float = 0.25,
                         tile_n: int = 0,
                         tile_k: int = 0) -> PackedLinear:
    """ShapeDtypeStruct stand-in for a PackedLinear of a given dense shape.

    ``lit_cap_frac`` is the planned escape rate (fraction of slots carrying
    literals); 0.25 is the measured rate on 8-bit quantized transformer
    weights with a 64k dictionary (see benchmarks/compression.py).

    ``tile_n/tile_k`` mirror the fused tile-major layout of
    :func:`pack_linear` / ``engine.build_serve_params`` (block size shrunk
    to divide the tile volume, no round-up padding), so dry-run lowering
    dispatches through the fused megakernel paths exactly like real
    serving; 0 keeps the legacy linear layout (two-step path).
    """
    n = int(np.prod(shape))
    if tile_n:
        bw = bcdc.fused_block_weights(tile_n, tile_k, block_weights,
                                      seq_len)
        nb = n // bw
    else:
        bw = block_weights
        nb = -(-n // bw)
    slots = bw // seq_len
    cap = max(1, int(slots * lit_cap_frac))
    sds = jax.ShapeDtypeStruct
    out = shape[0]
    return PackedLinear(
        codes=sds(stacked + (nb, slots), jnp.uint16),
        literals=sds(stacked + (nb, cap, seq_len), jnp.uint8),
        nlit=sds(stacked + (nb,), jnp.int32),
        scale=sds(stacked + (out, 1), jnp.float32),
        zero=sds(stacked + (out, 1), jnp.float32),
        shape=tuple(shape), seq_len=seq_len, tile_n=tile_n, tile_k=tile_k)


def planned_quant_specs(shape: tuple, *, stacked: tuple = ()) -> QuantLinear:
    sds = jax.ShapeDtypeStruct
    return QuantLinear(
        values=sds(stacked + tuple(shape), jnp.uint8),
        scale=sds(stacked + (shape[0], 1), jnp.float32),
        zero=sds(stacked + (shape[0], 1), jnp.float32))


def lut_spec(n_codes: int = 65536, seq_len: int = DEFAULT_SEQ_LEN):
    return jax.ShapeDtypeStruct((n_codes, seq_len), jnp.uint8)
