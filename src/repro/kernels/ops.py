"""jit'd public wrappers around the Pallas kernels.

Dispatch policy:
  * TPU backend       → Pallas kernel (compiled).
  * CPU/GPU backend   → pure-jnp oracle (``ref.py``) — same semantics; this
    preserves the paper's run-anywhere property.  Tests force
    ``impl='pallas_interpret'`` to validate the kernel bodies on CPU.

All wrappers pad to tile multiples and slice back, so callers never care
about block alignment.
"""
from __future__ import annotations

import collections
import enum
import functools
import os

import jax
import jax.numpy as jnp

from . import ref
from . import dequant_matmul as _dqmm
from . import dict_decode as _dd
from . import flash_attention as _fa
from . import fused_decode_matmul as _fdm


class Impl(str, enum.Enum):
    """The single source of truth for kernel-dispatch impl values.

    Backend selectors: ``AUTO`` (backend default — kernel on TPU, jnp
    oracle elsewhere), ``REF`` (force the oracle), ``PALLAS`` (force the
    compiled kernel), ``PALLAS_INTERPRET`` (kernel bodies in interpret
    mode — CI's CPU kernel job).  Degradation rungs, for the
    compressed-matmul wrappers only: ``UNFUSED`` (legacy two-step
    decode→matmul path) and ``MATERIALIZE`` (pure-jnp decode + dense
    einsum, no Pallas anywhere — serve/resilience.py's last functional
    rung).

    A ``str`` subclass, so every existing ``impl='unfused'`` call site —
    and jit static-argnum hashing — keeps working; dispatch code compares
    against these members instead of scattered string literals.
    """
    AUTO = "auto"
    REF = "ref"
    PALLAS = "pallas"
    PALLAS_INTERPRET = "pallas_interpret"
    UNFUSED = "unfused"
    MATERIALIZE = "materialize"

    __str__ = str.__str__          # f"{Impl.UNFUSED}" -> "unfused"


VALID_IMPLS = frozenset(i.value for i in Impl)

# The resilience ladder's rung names, from the same source of truth the
# dispatch lever uses.  'fused' is not an impl — it serves with the
# session default ('auto' → megakernel dispatch); the fallback rungs pin
# the corresponding Impl lever (serve/resilience.py::_RUNG_IMPL).
FUSED_RUNG = "fused"
DEFAULT_LADDER = (FUSED_RUNG, Impl.UNFUSED.value, Impl.MATERIALIZE.value)

# What 'auto' resolves to before the backend check.  CI's interpret-mode
# kernel job sets REPRO_TEST_IMPL=pallas_interpret (via tests/conftest.py)
# so every auto-dispatched call exercises the Pallas kernel bodies on the
# CPU runner instead of the jnp oracles.  Lenient at import (a bad env
# var falls back to 'auto' instead of breaking every import);
# ``set_default_impl`` is the strict entry point.
_DEFAULT_IMPL = os.environ.get("REPRO_TEST_IMPL", "auto")
if _DEFAULT_IMPL not in VALID_IMPLS:
    _DEFAULT_IMPL = "auto"


def set_default_impl(impl) -> None:
    """Override what ``impl='auto'`` resolves to (tests/CI, the resilience
    ladder's fallback lever).  Validates against :class:`Impl`."""
    global _DEFAULT_IMPL
    _DEFAULT_IMPL = Impl(impl).value


def _resolve_unfused(impl: Impl) -> Impl:
    """'auto' resolves to 'unfused'/'materialize' when the session default
    says so — the lever that forces a degradation rung (or the benchmark
    baseline) through call sites (``generate``) that don't thread an
    ``impl`` argument."""
    if impl == "auto" and _DEFAULT_IMPL in ("unfused", "materialize"):
        return _DEFAULT_IMPL
    return impl


# Trace-time dispatch probe: which decode→dequant→matmul path each call
# took.  Bodies run once per jit trace, so tests can clear this, run a
# sharded matmul, and assert e.g. 'fused_shard_map' was taken (the CI
# acceptance check that sharded paths never silently fall back to the
# dense-materializing two-step path).
DISPATCH_COUNTS = collections.Counter()

# The shard-mapped fused PackedLinear path replicates x over the weight
# axes inside its shard_map (in_specs P(drow, None)), so it trades an
# m·K activation gather for the two-step path's 2·N·K dense-weight HBM
# round trip.  Decode/small-batch shapes win (m ≲ N); 32k-prefill shapes
# lose badly (m ≫ N: +19 GiB collectives, +6 GiB HBM per step measured on
# deepseek-v2-lite prefill_32k×512dev).  Gate: fused shard_map only when
# m ≤ max(N, this floor); the floor keeps decode-scale row counts (and
# the 8-device CI shapes) on the fused path for small-N layers.  The
# grouped expert path is exempt — its xe is expert-sharded, never
# replicated.
FUSED_SHARD_MAP_MAX_M = 512


# Trace-time probe of how each kernel-backed op ran: 'pallas' (compiled
# Mosaic kernel), 'interpret' (kernel body in the Pallas interpreter) or
# 'ref' (jnp oracle).  A chip run must show 'pallas' only.
KERNEL_COUNTS = collections.Counter()


def _use_pallas(impl: Impl) -> tuple[bool, bool]:
    """-> (use_kernel, interpret).  On a TPU the kernel is always compiled:
    interpret mode (e.g. from REPRO_TEST_IMPL) never runs on the chip."""
    if impl == "auto":
        impl = _DEFAULT_IMPL
    on_tpu = jax.default_backend() == "tpu"
    if impl == "ref" or (impl not in ("pallas", "pallas_interpret")
                         and not on_tpu):
        KERNEL_COUNTS["ref"] += 1
        return False, False
    interpret = impl == "pallas_interpret" and not on_tpu
    KERNEL_COUNTS["interpret" if interpret else "pallas"] += 1
    return True, interpret


def _pad_to(x: jax.Array, axis: int, mult: int, value=0):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x, size
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value), size


def dequant_matmul(x, wq, scale, zero, *, out_dtype=jnp.float32,
                   impl: Impl = "auto", bm=None, bn=None, bk=None):
    """y = x @ dequant(wq).T with per-channel affine (scale, zero).

    x: (..., K) float; wq: (N, K) uint8; scale/zero: (N, 1).
    Leading dims of x are flattened to M.  Under a multi-device mesh the
    kernel runs per device in a shard_map: weight rows (output features)
    on ``model`` where they divide, x rows on the data axes, so y comes
    back column-sharded — the layout of the fused compressed path.
    """
    axis_sizes, mesh, ndev = _mesh_state()
    if ndev > 1 and not _is_concrete_mesh(mesh):
        impl = Impl.REF
    use_kernel, interpret = _use_pallas(impl)
    lead = x.shape[:-1]
    kdim = x.shape[-1]
    x2 = x.reshape(-1, kdim)
    n = wq.shape[0]
    if not use_kernel:
        y = ref.dequant_matmul(x2, wq, scale, zero, out_dtype)
        return y.reshape(*lead, n)
    local = functools.partial(_dequant_matmul_kernel, out_dtype=out_dtype,
                              interpret=interpret, bm=bm, bn=bn, bk=bk)
    if ndev <= 1:
        return local(x2, wq, scale, zero).reshape(*lead, n)
    from jax.sharding import PartitionSpec as P
    msize = axis_sizes.get("model", 1)
    wspec = "model" if msize > 1 and n % msize == 0 else None
    dsize = axis_sizes.get("data", 1)
    drow = "data" if dsize > 1 and x2.shape[0] % dsize == 0 else None
    y = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(drow, None), P(wspec, None), P(wspec, None),
                  P(wspec, None)),
        out_specs=P(drow, wspec), check_vma=False)(x2, wq, scale, zero)
    return y.reshape(*lead, n)


def _dequant_matmul_kernel(x2, wq, scale, zero, *, out_dtype, interpret,
                           bm=None, bn=None, bk=None):
    """The Pallas kernel on one device, padded to its tile multiples."""
    kdim = x2.shape[-1]
    kw = {}
    if bm: kw["bm"] = bm
    if bn: kw["bn"] = bn
    if bk: kw["bk"] = bk
    bm_ = kw.get("bm", _dqmm.DEFAULT_BM)
    bn_ = kw.get("bn", _dqmm.DEFAULT_BN)
    bk_ = kw.get("bk", _dqmm.DEFAULT_BK)
    x2, m0 = _pad_to(x2, 0, min(bm_, max(x2.shape[0], 1)))
    x2, _ = _pad_to(x2, 1, min(bk_, kdim))
    wqp, n0 = _pad_to(wq, 0, min(bn_, wq.shape[0]))
    wqp, _ = _pad_to(wqp, 1, min(bk_, kdim))
    sp, _ = _pad_to(scale, 0, min(bn_, scale.shape[0]))
    zp, _ = _pad_to(zero, 0, min(bn_, zero.shape[0]))
    y = _dqmm.dequant_matmul(x2, wqp, sp, zp, out_dtype=out_dtype,
                             interpret=interpret, **kw)
    return y[:m0, :n0]


def dict_decode(codes, literals, nlit, lut, *, impl: Impl = "auto",
                chunk: int | None = None):
    """(nb, slots) uint16 → (nb, slots·S) uint8."""
    use_kernel, interpret = _use_pallas(impl)
    if not use_kernel:
        return ref.dict_decode(codes, literals, nlit, lut)
    ch = chunk or _dd.DEFAULT_CHUNK
    nb = codes.shape[0]
    ch = min(ch, nb)
    # Pad the block axis to a chunk multiple and slice back, instead of
    # shrinking the chunk to a divisor of nb (which silently degraded to
    # chunk=1 — one grid step per block — for prime block counts).  Padded
    # rows decode to LUT row 0 garbage and are dropped by the slice.
    codes, nb0 = _pad_to(codes, 0, ch)
    literals, _ = _pad_to(literals, 0, ch)
    out = _dd.dict_decode(codes, literals, nlit, lut, chunk=ch,
                          interpret=interpret)
    return out[:nb0]


def flash_attention(q, k, v, *, causal=True, sm_scale=None, q_offset=0,
                    impl: Impl = "auto", bq=None, bk=None, kv_chunk=None):
    """(B, Hq, Tq, D) × (B, Hkv, Tk, D) → (B, Hq, Tq, D).

    Mosaic kernels are not partitioned automatically, so under a
    multi-device mesh the kernel runs per device inside a shard_map: batch
    on the data axes and heads on ``model`` where they divide, replicated
    otherwise.  An abstract mesh (no devices to map) takes the jnp path.
    """
    axis_sizes, mesh, ndev = _mesh_state()
    if ndev > 1 and not _is_concrete_mesh(mesh):
        impl = Impl.REF
    use_kernel, interpret = _use_pallas(impl)
    if not use_kernel:
        kw = {"kv_chunk": kv_chunk} if kv_chunk else {}
        return ref.flash_attention(q, k, v, causal=causal,
                                   sm_scale=sm_scale, q_offset=q_offset, **kw)
    kw = {}
    if bq: kw["bq"] = bq
    if bk: kw["bk"] = bk
    fn = functools.partial(_fa.flash_attention, causal=causal,
                           sm_scale=sm_scale, q_offset=q_offset,
                           interpret=interpret, **kw)
    if ndev <= 1:
        return fn(q, k, v)
    from jax.sharding import PartitionSpec as P
    batch = tuple(a for a in ("pod", "data") if axis_sizes.get(a, 1) > 1)
    bsize = 1
    for a in batch:
        bsize *= axis_sizes[a]
    bspec = ((batch if len(batch) > 1 else batch[0])
             if batch and q.shape[0] % bsize == 0 else None)
    msize = axis_sizes.get("model", 1)
    hspec = ("model" if msize > 1 and q.shape[1] % msize == 0
             and k.shape[1] % msize == 0 else None)
    spec = P(bspec, hspec, None, None)
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


def _mesh_state():
    """(axis_sizes, mesh, total_devices) of the trace-time mesh — the
    shared preamble of both fused-dispatch decisions below."""
    from repro.sharding.partition import current_mesh
    axis_sizes, mesh = current_mesh()
    ndev = 1
    for v in axis_sizes.values():
        ndev *= v
    return axis_sizes, mesh, ndev


def _is_concrete_mesh(mesh) -> bool:
    from jax.sharding import Mesh
    return isinstance(mesh, Mesh)


def decode_dequant_matmul(x, packed, lut, *, out_dtype=jnp.bfloat16,
                          impl: Impl = "auto"):
    """Compressed-weight matmul: the paper's serving hot path.

    ``packed`` is a repro.core.compressed.PackedLinear (single layer).

    Dispatch (tile-major planes, ``packed.tile_n > 0``): fused is the
    invariant — the dense weight never materializes in HBM.
      * no mesh / 1 device  → fused megakernel directly
        (``fused_decode_matmul`` on TPU, its strip-scan oracle
        ``ref.fused_decode_matmul`` elsewhere).
      * active concrete mesh → shard_map wrapper: the tile-major block
        axis splits over the weight-sharding axes (pod, model) in whole
        out-tile bands — requires ``(N / tile_n) % (pod·model) == 0``,
        which ``blocked_codec.choose_fused_tiles(shards=...)`` arranges —
        and each device runs the fused grid over its resident compressed
        slab; x replicates over (pod, model) (rows stay data-sharded when
        divisible) and the output comes back column-sharded on
        (pod, model).  Plane gathers (FSDP'd storage) move compressed
        bytes, never the dense weight — same D1 degather economics as the
        two-step path.
    Fallbacks to the legacy two-step path (decode to HBM, then
    ``dequant_matmul``): linear-layout planes (tile_n == 0), stacked
    planes outside a scan, out-tile counts that don't divide the weight
    axes, abstract meshes, prefill-scale row counts under a mesh
    (m > max(N, ``FUSED_SHARD_MAP_MAX_M``) — the shard_map's x
    replication would outweigh the dense round-trip; see the constant),
    and ``impl='unfused'`` (the benchmark baseline).  ``impl='materialize'``
    (probe 'materialize') bypasses every Pallas kernel: pure-jnp decode +
    dequantize to the dense weight, plain einsum — the resilience ladder's
    last functional rung when both kernel paths are faulting.
    """
    impl = _resolve_unfused(impl)
    if impl == "materialize":
        DISPATCH_COUNTS["materialize"] += 1
        w = packed.materialize(lut, dtype=x.dtype)
        return jnp.einsum("...k,nk->...n", x, w).astype(out_dtype)
    unfused = impl == "unfused"
    inner_impl = "auto" if unfused else impl
    tile_n = getattr(packed, "tile_n", 0)
    if not unfused and tile_n and packed.codes.ndim == 2:
        axis_sizes, mesh, ndev = _mesh_state()
        if ndev <= 1:
            DISPATCH_COUNTS["fused"] += 1
            return _fused_decode_matmul(x, packed, lut, out_dtype=out_dtype,
                                        impl=impl)
        waxes = tuple(a for a in ("pod", "model")
                      if axis_sizes.get(a, 1) > 1)
        wsize = 1
        for a in waxes:
            wsize *= axis_sizes[a]
        m_rows = x.size // x.shape[-1] if x.shape[-1] else 0
        if (_is_concrete_mesh(mesh)
                and (packed.shape[0] // tile_n) % wsize == 0
                and m_rows <= max(packed.shape[0], FUSED_SHARD_MAP_MAX_M)):
            DISPATCH_COUNTS["fused_shard_map"] += 1
            return _fused_decode_matmul_sharded(
                x, packed, lut, out_dtype=out_dtype, impl=impl,
                mesh=mesh, axis_sizes=axis_sizes, waxes=waxes)
    DISPATCH_COUNTS["unfused"] += 1
    return _decode_dequant_matmul_unfused(x, packed, lut,
                                          out_dtype=out_dtype,
                                          impl=inner_impl)


def _fused_tile_matmul(x2, codes, literals, nlit, lut, scale, zero, *,
                       shape, tile_n, tile_k, out_dtype, impl: Impl):
    """Fused matmul over tile-major planes, shard-local workhorse.

    ``codes`` may carry a leading column-group axis (G, nb, slots) — the
    shard-local stack of a TiledPackedLinear — in which case group g
    covers x columns [g·K/G, (g+1)·K/G) of ``shape = (N, K)``.  Runs the
    Pallas megakernel (grouped grid) or the strip-scan oracle, summing
    per-group partial affines in f32 (exact: the affine epilogue is
    linear in the accumulators).
    """
    use_kernel, interpret = _use_pallas(impl)
    n, ktot = shape
    m = x2.shape[0]
    if use_kernel:
        bm = min(_fdm.DEFAULT_BM, max(m, 1))
        x2p, m0 = _pad_to(x2, 0, bm)
        y = _fdm.fused_decode_matmul(
            x2p, codes, literals, lut, scale, zero, shape=tuple(shape),
            tile_n=tile_n, tile_k=tile_k, bm=bm, out_dtype=out_dtype,
            interpret=interpret)
        return y[:m0]
    if codes.ndim == 2:
        return ref.fused_decode_matmul(
            x2, codes, literals, nlit, lut, scale, zero,
            shape=tuple(shape), tile_n=tile_n, tile_k=tile_k,
            out_dtype=out_dtype)
    groups = codes.shape[0]
    kg = ktot // groups
    acc = jnp.zeros((m, n), jnp.float32)
    for g in range(groups):   # small static count: unrolled like K-strips
        acc = acc + ref.fused_decode_matmul(
            x2[:, g * kg:(g + 1) * kg], codes[g], literals[g], nlit[g],
            lut, scale, zero, shape=(n, kg), tile_n=tile_n, tile_k=tile_k,
            out_dtype=jnp.float32)
    return acc.astype(out_dtype)


def _fused_decode_matmul(x, packed, lut, *, out_dtype, impl: Impl):
    """Megakernel path — decoded weight tiles live only in VMEM/registers."""
    n, kdim = packed.shape
    lead = x.shape[:-1]
    x2 = x.reshape(-1, kdim)
    y = _fused_tile_matmul(x2, packed.codes, packed.literals, packed.nlit,
                           lut, packed.scale, packed.zero,
                           shape=tuple(packed.shape), tile_n=packed.tile_n,
                           tile_k=packed.tile_k, out_dtype=out_dtype,
                           impl=impl)
    return y.reshape(*lead, n)


def _fused_decode_matmul_sharded(x, packed, lut, *, out_dtype, impl: Impl,
                                 mesh, axis_sizes, waxes):
    """shard_map-wrapped fused megakernel for a mesh-sharded PackedLinear.

    The tile-major block axis (and scale/zero rows) split over ``waxes``
    (the pod/model weight axes) in whole out-tile bands; each device runs
    the fused grid over its shard-local (N/wsize, K) compressed slab.  The
    output is column-parallel — y's feature dim lands sharded on
    ``waxes``, no psum needed — and x's rows stay on the data axis when
    they divide.  For a row_parallel container the math is identical
    (same dense y); only the output layout differs, and the caller's next
    constraint reshards activation bytes, never weight bytes.
    """
    from jax.sharding import PartitionSpec as P

    n, kdim = packed.shape
    lead = x.shape[:-1]
    x2 = x.reshape(-1, kdim)
    m = x2.shape[0]
    wsize = 1
    for a in waxes:
        wsize *= axis_sizes[a]
    n_loc = n // wsize
    wspec = waxes if len(waxes) > 1 else (waxes[0] if waxes else None)
    dsize = axis_sizes.get("data", 1)
    drow = "data" if (dsize > 1 and m % dsize == 0) else None
    tile_n, tile_k = packed.tile_n, packed.tile_k

    def local_fn(xl, codes, lits, nlit, lutl, scale, zero):
        return _fused_tile_matmul(xl, codes, lits, nlit, lutl, scale, zero,
                                  shape=(n_loc, kdim), tile_n=tile_n,
                                  tile_k=tile_k, out_dtype=out_dtype,
                                  impl=impl)

    y = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(drow, None), P(wspec, None), P(wspec, None, None),
                  P(wspec), P(None, None), P(wspec, None), P(wspec, None)),
        out_specs=P(drow, wspec),
        check_vma=False,
    )(x2, packed.codes, packed.literals, packed.nlit, lut,
      packed.scale, packed.zero)
    return y.reshape(*lead, n)


def _decode_dequant_matmul_unfused(x, packed, lut, *, out_dtype,
                                   impl: Impl):
    """Legacy two-step path: decode the full weight, then dequant-matmul.

    Pays 2·N·K bytes of dense-weight HBM traffic per call (write decoded,
    read for the matmul); kept for sharded serving and as the
    ``impl='unfused'`` baseline the benchmarks compare against.
    """
    from repro.sharding.partition import constrain
    packed = packed.degather()   # gather compressed bytes, not f32 (§Perf D1)
    n, kdim = packed.shape
    wq_flat = dict_decode(packed.codes, packed.literals, packed.nlit, lut,
                          impl=impl)
    if getattr(packed, "tile_n", 0):
        from repro.core.blocked_codec import untile_flat
        wq = untile_flat(wq_flat.reshape(-1)[: n * kdim], (n, kdim),
                         packed.tile_n, packed.tile_k)
    else:
        wq = wq_flat.reshape(-1)[: n * kdim].reshape(n, kdim)
    if packed.row_parallel:
        # wo/w_down: contraction dim must carry the model sharding — decode
        # leaves rows:model; reshard the u8 weight (not the f32
        # activations, which SPMD otherwise gathers at 4-13 GiB/layer;
        # §Perf P2), then the dot partial-sums into the standard
        # row-parallel output all-reduce.
        wq = constrain(wq, None, "model")
    return dequant_matmul(x, wq, packed.scale, packed.zero,
                          out_dtype=out_dtype, impl=impl)


def tiled_decode_dequant_matmul(x, packed, lut, *, out_dtype=jnp.bfloat16,
                                impl: Impl = "auto"):
    """2D-TP path (§Perf D2): every device owns a permanently-resident
    (out/model × in/data) compressed tile; x reshards its feature dim onto
    data (MB-scale all-to-all) and the dot's partial sums reduce over data.
    No weight collectives at all.

    ``packed`` is a repro.core.compressed.TiledPackedLinear.

    Dispatch: when the per-tile planes carry the fused tile-major layout
    (``packed.tile_n > 0``) the fused megakernel is the invariant here
    too — no per-device dense tile is ever materialized:
      * no mesh / 1 device → one grouped-grid fused call over the whole
        column-tile stack.
      * active concrete mesh → shard_map: tile axis splits on data, the
        per-tile block axis on model (whole out-tile bands — requires
        ``tiles % data == 0`` and ``(out / tile_n) % model == 0``, which
        ``encode_tiled_planes(tile='auto', shards=(model, 1))``
        arranges); each device runs the fused grid over its resident
        (out/model × in/data) compressed slab and the row-parallel psum
        over data runs in the epilogue.  Weights cross no links; only
        activations move.
    Fallback (linear per-tile layout, stacked planes outside a scan,
    non-divisible tile counts, abstract meshes, ``impl='unfused'``):
    decode + dequantize the dense weight per device, then einsum — the
    legacy two-step 2D-TP path below.
    """
    from repro.sharding.partition import constrain
    impl = _resolve_unfused(impl)
    # 'materialize' shares the dense-einsum fallback below (it already
    # decodes with the pure-jnp codec) but gets its own probe key.
    unfused = impl in ("unfused", "materialize")
    inner_impl = "auto" if unfused else impl
    tile_n = getattr(packed, "tile_n", 0)
    n, kdim = packed.shape
    if not unfused and tile_n and packed.codes.ndim == 3:
        axis_sizes, mesh, ndev = _mesh_state()
        if ndev <= 1:
            DISPATCH_COUNTS["tiled_fused"] += 1
            lead = x.shape[:-1]
            x2 = x.reshape(-1, kdim)
            y = _fused_tile_matmul(
                x2, packed.codes, packed.literals, packed.nlit, lut,
                packed.scale, packed.zero, shape=(n, kdim),
                tile_n=tile_n, tile_k=packed.tile_k,
                out_dtype=out_dtype, impl=impl)
            return y.reshape(*lead, n)
        dsize = axis_sizes.get("data", 1)
        msize = axis_sizes.get("model", 1)
        if (_is_concrete_mesh(mesh) and packed.tiles % dsize == 0
                and (n // tile_n) % msize == 0):
            DISPATCH_COUNTS["tiled_fused_shard_map"] += 1
            return _tiled_fused_sharded(x, packed, lut, out_dtype=out_dtype,
                                        impl=impl, mesh=mesh,
                                        axis_sizes=axis_sizes)
    DISPATCH_COUNTS["tiled_materialize" if impl == "materialize"
                    else "tiled_unfused"] += 1
    w = packed.materialize(lut, dtype=x.dtype)        # (n, kdim), in-sharded
    w = constrain(w, "model", ("pod", "data"))
    xs = constrain(x, *([None] * (x.ndim - 1)), ("pod", "data"))
    y = jnp.einsum("...k,nk->...n", xs, w)
    return constrain(y.astype(out_dtype),
                     *([None] * (x.ndim - 1)), "model")


def _tiled_fused_sharded(x, packed, lut, *, out_dtype, impl: Impl,
                         mesh, axis_sizes):
    """shard_map-wrapped fused megakernel for the TiledPackedLinear 2D-TP
    layout: tile (column-group) axis on data, block axis on model, pods
    replicate weights and carry x rows.  Each device decodes nothing to
    HBM — its grouped fused grid streams the resident compressed tiles —
    and the contraction's partial sums psum over data (the row-parallel
    epilogue), leaving y column-sharded on model.
    """
    from jax.sharding import PartitionSpec as P

    n, kdim = packed.shape
    lead = x.shape[:-1]
    x2 = x.reshape(-1, kdim)
    m = x2.shape[0]
    daxis = "data" if "data" in axis_sizes else None
    maxis = "model" if "model" in axis_sizes else None
    msize = axis_sizes.get("model", 1)
    psize = axis_sizes.get("pod", 1)
    prow = "pod" if ("pod" in axis_sizes and psize > 1
                     and m % psize == 0) else None
    n_loc = n // msize
    in_loc = kdim // axis_sizes.get("data", 1)
    tile_n, tile_k = packed.tile_n, packed.tile_k

    def local_fn(xl, codes, lits, nlit, lutl, scale, zero):
        y = _fused_tile_matmul(xl, codes, lits, nlit, lutl, scale, zero,
                               shape=(n_loc, in_loc), tile_n=tile_n,
                               tile_k=tile_k, out_dtype=jnp.float32,
                               impl=impl)
        if daxis is not None:
            y = jax.lax.psum(y, daxis)    # row-parallel epilogue reduce
        return y.astype(out_dtype)

    y = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(prow, daxis), P(daxis, maxis, None),
                  P(daxis, maxis, None, None), P(daxis, maxis),
                  P(None, None), P(maxis, None), P(maxis, None)),
        out_specs=P(prow, maxis),
        check_vma=False,
    )(x2, packed.codes, packed.literals, packed.nlit, lut,
      packed.scale, packed.zero)
    return y.reshape(*lead, n)


def grouped_fused_local(xe, packed, lut, *, out_dtype=jnp.bfloat16,
                        impl: Impl = "auto"):
    """Shard-local grouped expert fused matmul — no mesh dispatch.

    ``packed`` is a stacked PackedLinear (leading expert axis on every
    plane, tile-major layout); ``xe`` the matching (E, cap, K) token
    blocks.  Runs the grouped Pallas megakernel (TPU/interpret) or its
    vmapped strip-scan oracle directly, so it is safe inside shard_map
    bodies that already own only their expert shard (the local-routing
    MoE); callers outside shard_map should use
    :func:`grouped_decode_dequant_matmul`, which adds mesh dispatch and
    the probe counters.
    """
    tile_n, tile_k = packed.tile_n, packed.tile_k
    assert tile_n and packed.codes.ndim == 3, (tile_n, packed.codes.shape)
    use_kernel, interpret = _use_pallas(impl)
    if use_kernel:
        m = xe.shape[1]
        bm = min(_fdm.DEFAULT_BM, max(m, 1))
        xp, m0 = _pad_to(xe, 1, bm)
        y = _fdm.grouped_fused_decode_matmul(
            xp, packed.codes, packed.literals, lut, packed.scale,
            packed.zero, shape=tuple(packed.shape), tile_n=tile_n,
            tile_k=tile_k, bm=bm, out_dtype=out_dtype, interpret=interpret)
        return y[:, :m0]
    return ref.grouped_fused_decode_matmul(
        xe, packed.codes, packed.literals, packed.nlit, lut,
        packed.scale, packed.zero, shape=tuple(packed.shape),
        tile_n=tile_n, tile_k=tile_k, out_dtype=out_dtype)


def grouped_decode_dequant_matmul(xe, packed, lut, *,
                                  out_dtype=jnp.bfloat16,
                                  impl: Impl = "auto"):
    """Per-expert compressed matmul y[e] = x[e] @ W[e].T — the MoE hot path.

    ``packed`` is a repro.core.compressed.PackedLinear whose planes carry a
    leading expert axis (codes (E, nb, slots), scale (E, N, 1), …); ``xe``
    the capacity-gathered token blocks (E, cap, K) of the same expert
    order.  This is the layer that keeps QMoE-class expert stacks —
    where ~all the model's bytes live — compressed-resident in HBM.

    Dispatch (tile-major planes, ``packed.tile_n > 0``):
      * no mesh / 1 device  → grouped megakernel directly (expert grid
        axis; ``fused_decode_matmul.grouped_fused_decode_matmul`` on TPU,
        the vmapped strip-scan oracle elsewhere).
      * active concrete mesh with experts dividing the model axis →
        shard_map wrapper: experts stay on the model axis (expert
        parallelism) — each device runs the grouped fused grid over its
        resident E/model compressed planes and the output stays
        expert-sharded for the caller's combine scatter.  Plane gathers
        move compressed bytes, never dense experts (§Perf D1 economics).
    Fallback (probe 'grouped_unfused'): linear-layout planes, expert
    counts that don't divide the model axis, abstract meshes, and
    ``impl='unfused'`` — materialize the dense expert stack, then einsum
    (the benchmark baseline, and the only path that pays E·N·K dense
    bytes).
    """
    impl = _resolve_unfused(impl)
    # 'materialize' is the same dense-stack einsum as 'unfused' here (the
    # fallback already decodes pure-jnp), probed separately.
    unfused = impl in ("unfused", "materialize")
    tile_n = getattr(packed, "tile_n", 0)
    e = xe.shape[0]
    if (not unfused and tile_n and lut is not None
            and packed.codes.ndim == 3):
        axis_sizes, mesh, ndev = _mesh_state()
        if ndev <= 1:
            DISPATCH_COUNTS["grouped_fused"] += 1
            return grouped_fused_local(xe, packed, lut, out_dtype=out_dtype,
                                       impl=impl)
        msize = axis_sizes.get("model", 1)
        if _is_concrete_mesh(mesh) and msize > 1 and e % msize == 0:
            DISPATCH_COUNTS["grouped_fused_shard_map"] += 1
            return _grouped_fused_sharded(xe, packed, lut,
                                          out_dtype=out_dtype, impl=impl,
                                          mesh=mesh)
    DISPATCH_COUNTS["grouped_materialize" if impl == "materialize"
                    else "grouped_unfused"] += 1
    assert lut is not None, \
        "grouped_decode_dequant_matmul: compressed stacks need the decode LUT"
    w = packed.materialize(lut, xe.dtype)             # (E, N, K) dense
    return jnp.einsum("emk,enk->emn", xe, w).astype(out_dtype)


def _grouped_fused_sharded(xe, packed, lut, *, out_dtype, impl: Impl, mesh):
    """shard_map-wrapped grouped megakernel: expert-parallel fused MoE.

    Experts split on the model axis for every plane and for the gathered
    token blocks; each device launches the grouped fused grid over its
    E/model resident compressed planes.  No reduction — the output stays
    expert-sharded on model, exactly the layout the MoE combine scatter
    constrains to (see ``layers.apply_moe``).
    """
    import dataclasses

    from jax.sharding import PartitionSpec as P

    def local_fn(xl, codes, lits, nlit, lutl, scale, zero):
        loc = dataclasses.replace(packed, codes=codes, literals=lits,
                                  nlit=nlit, scale=scale, zero=zero)
        return grouped_fused_local(xl, loc, lutl, out_dtype=out_dtype,
                                   impl=impl)

    espec = P("model", None, None)
    y = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(espec, espec, P("model", None, None, None),
                  P("model", None), P(None, None), espec, espec),
        out_specs=espec,
        check_vma=False,
    )(xe, packed.codes, packed.literals, packed.nlit, lut,
      packed.scale, packed.zero)
    return y
