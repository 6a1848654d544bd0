"""Fused decode→dequant→matmul megakernel validation.

Pallas kernel (interpret mode) and strip-scan oracle vs the legacy
two-step path, across odd shapes, degenerate dictionaries, and the
row-parallel container; plus the tile-aligned layout invariants and the
ops.dict_decode chunk-padding fix.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import codec, blocked_codec
from repro.core.blocked_codec import build_lut, choose_fused_tiles
from repro.core.compressed import pack_linear, quantize_linear
from repro.kernels import ops, ref
import importlib

fdm_kernel = importlib.import_module("repro.kernels.fused_decode_matmul")
dd_kernel = importlib.import_module("repro.kernels.dict_decode")


def _packed_pair(rng, n, k, structured=True, table=None):
    """(packed_tiled, packed_linear, lut) for one synthetic weight."""
    if structured:
        w = jnp.asarray(rng.laplace(0.0, 0.02, size=(n, k)).astype(np.float32))
    else:
        w = jnp.asarray(rng.normal(size=(n, k)).astype(np.float32))
    ql = quantize_linear(w)
    if table is None:
        table = codec.find_frequent_sequences([np.asarray(ql.values)])
    lut = build_lut(table)
    pt = pack_linear(w, table, lut, tile="auto")
    plin = pack_linear(w, table, lut)
    return pt, plin, jnp.asarray(lut)


# ---------------------------------------------------------------------------
# tile-aligned layout
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,k", [(64, 128), (70, 96), (128, 512), (24, 1000)])
def test_tiled_layout_decodes_bitexact(n, k, rng):
    """Tile-major planes must decode to the same bytes as linear planes."""
    pt, plin, lut = _packed_pair(rng, n, k)
    assert pt.tile_n > 0 and n % pt.tile_n == 0 and k % pt.tile_k == 0
    np.testing.assert_array_equal(np.asarray(pt.materialize_int8(lut)),
                                  np.asarray(plin.materialize_int8(lut)))


def test_choose_fused_tiles_divisors_and_gates():
    tn, tk, bw = choose_fused_tiles((1024, 4096))
    assert (tn, tk) == (128, 512) and bw == 4096
    tn, tk, bw = choose_fused_tiles((70, 96))
    assert 70 % tn == 0 and 96 % tk == 0 and (tn * tk) % bw == 0
    # too small/odd to hold one gram per tile -> no fused layout
    assert choose_fused_tiles((35, 35)) is None


# ---------------------------------------------------------------------------
# fused kernel vs oracle vs two-step, swept over odd shapes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,n,k", [
    (8, 64, 128),       # tile-multiple
    (13, 70, 96),       # nothing is a tile multiple
    (1, 128, 512),      # decode-style M=1
    (130, 24, 1000),    # M > bm with remainder, odd N/K
])
def test_fused_matches_oracle_interpret(m, n, k, rng):
    pt, _, lut = _packed_pair(rng, n, k)
    x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
    y_ref = ops.decode_dequant_matmul(x, pt, lut, impl="ref",
                                      out_dtype=jnp.float32)
    y_pal = ops.decode_dequant_matmul(x, pt, lut, impl="pallas_interpret",
                                      out_dtype=jnp.float32)
    err = float(jnp.abs(y_pal - y_ref).max() /
                (jnp.abs(y_ref).max() + 1e-9))
    assert err < 2e-2, err  # bf16 MXU x-cast vs f32 oracle


def test_fused_exact_parity_integer_activations(rng):
    """With integer-valued x the bf16 x-cast and every accumulation are
    exact, so the kernel must agree BITWISE with the oracle — the
    acceptance-criterion exactness check for the uint8/affine math.  The
    legacy two-step path materializes w = (q−z)·s (one extra rounding per
    element) so it agrees to f32 roundoff, not bitwise."""
    n, k, m = 64, 256, 16
    pt, _, lut = _packed_pair(rng, n, k)
    x = jnp.asarray(rng.integers(-8, 9, size=(m, k)).astype(np.float32))
    y_oracle = ops.decode_dequant_matmul(x, pt, lut, impl="ref",
                                         out_dtype=jnp.float32)
    y_kernel = ops.decode_dequant_matmul(x, pt, lut, impl="pallas_interpret",
                                         out_dtype=jnp.float32)
    y_twostep = ops.decode_dequant_matmul(x, pt, lut, impl="unfused",
                                          out_dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(y_kernel), np.asarray(y_oracle))
    np.testing.assert_allclose(np.asarray(y_twostep), np.asarray(y_oracle),
                               rtol=1e-4, atol=1e-4)


def test_fused_empty_dictionary(rng):
    """Empty table → every slot escapes; fused decode must still be exact."""
    n, k, m = 32, 128, 8
    pt, _, lut = _packed_pair(rng, n, k, structured=False, table={})
    assert int(np.asarray(pt.nlit).min()) == pt.codes.shape[1]  # all escape
    x = jnp.asarray(rng.integers(-4, 5, size=(m, k)).astype(np.float32))
    y_ref = ops.decode_dequant_matmul(x, pt, lut, impl="ref",
                                      out_dtype=jnp.float32)
    y_pal = ops.decode_dequant_matmul(x, pt, lut, impl="pallas_interpret",
                                      out_dtype=jnp.float32)
    y_two = ops.decode_dequant_matmul(x, pt, lut, impl="unfused",
                                      out_dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(y_pal), np.asarray(y_ref))
    np.testing.assert_allclose(np.asarray(y_two), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)


def test_fused_all_escape_blocks_with_nonempty_table(rng):
    """A populated table that never matches this tensor: rank-gather path
    does all the work while the LUT sits unused."""
    n, k, m = 32, 128, 4
    table = {(250, 251, 252, 253): 0}   # gram absent from random bytes
    pt, _, lut = _packed_pair(rng, n, k, structured=False, table=table)
    x = jnp.asarray(rng.integers(-4, 5, size=(m, k)).astype(np.float32))
    y_ref = ops.decode_dequant_matmul(x, pt, lut, impl="ref",
                                      out_dtype=jnp.float32)
    y_pal = ops.decode_dequant_matmul(x, pt, lut, impl="pallas_interpret",
                                      out_dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(y_pal), np.asarray(y_ref))


def test_fused_row_parallel_packed(rng):
    """row_parallel containers take the fused path on a single device and
    stay numerically identical to the plain container."""
    import dataclasses
    n, k, m = 64, 128, 8
    pt, _, lut = _packed_pair(rng, n, k)
    pt_rp = dataclasses.replace(pt, row_parallel=True)
    x = jnp.asarray(rng.integers(-8, 9, size=(m, k)).astype(np.float32))
    y = ops.decode_dequant_matmul(x, pt, lut, impl="pallas_interpret",
                                  out_dtype=jnp.float32)
    y_rp = ops.decode_dequant_matmul(x, pt_rp, lut, impl="pallas_interpret",
                                     out_dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_rp))


def test_fused_batched_leading_dims(rng):
    pt, _, lut = _packed_pair(rng, 32, 64)
    x = jnp.asarray(rng.normal(size=(2, 3, 64)).astype(np.float32))
    y = ops.decode_dequant_matmul(x, pt, lut, impl="ref",
                                  out_dtype=jnp.float32)
    assert y.shape == (2, 3, 32)


def test_fused_kernel_grouped_planes_interpret(rng):
    """Column-group axis (the shard-local TiledPackedLinear case): stacked
    per-group tile-major planes through the 4-D grid must agree BITWISE
    with the G=1 kernel over the same dense weight (integer x ⇒ exact)."""
    from repro.core.compressed import pack_linear_tiled
    n, k, m, groups = 64, 256, 16, 4
    w = jnp.asarray(rng.laplace(0.0, 0.02, size=(n, k)).astype(np.float32))
    ql = quantize_linear(w)
    table = codec.find_frequent_sequences([np.asarray(ql.values)])
    lut = build_lut(table)
    pt = pack_linear(w, table, lut, tile="auto")
    tiled = pack_linear_tiled(w, table, lut, tiles=groups, tile="auto")
    assert tiled.codes.ndim == 3 and tiled.tile_n > 0
    x = jnp.asarray(rng.integers(-8, 9, size=(m, k)).astype(np.float32))
    y_grouped = fdm_kernel.fused_decode_matmul(
        x, tiled.codes, tiled.literals, jnp.asarray(lut), tiled.scale,
        tiled.zero, shape=(n, k), tile_n=tiled.tile_n, tile_k=tiled.tile_k,
        interpret=True)
    y_flat = fdm_kernel.fused_decode_matmul(
        x, pt.codes, pt.literals, jnp.asarray(lut), pt.scale, pt.zero,
        shape=(n, k), tile_n=pt.tile_n, tile_k=pt.tile_k, interpret=True)
    np.testing.assert_array_equal(np.asarray(y_grouped), np.asarray(y_flat))


def test_fused_kernel_rejects_nontiled_shapes(rng):
    """Kernel-level API asserts tile alignment (ops handles the padding)."""
    pt, _, lut = _packed_pair(rng, 64, 128)
    x = jnp.ones((4, 96), jnp.float32)
    with pytest.raises(AssertionError):
        fdm_kernel.fused_decode_matmul(
            x, pt.codes, pt.literals, lut, pt.scale, pt.zero,
            shape=(64, 128), tile_n=pt.tile_n, tile_k=pt.tile_k,
            interpret=True)


# ---------------------------------------------------------------------------
# ops.dict_decode chunk padding (prime block counts)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nblocks", [7, 13, 1])
def test_dict_decode_prime_block_counts(nblocks, rng):
    """Prime nb used to shrink the kernel chunk to 1 (one grid step per
    block); now nb pads to a chunk multiple and slices back."""
    n = nblocks * 256
    w = rng.integers(0, 12, size=n).astype(np.uint8)
    table = codec.find_frequent_sequences([w], max_codes=500)
    bc = blocked_codec.encode_blocked(w, table, block_weights=256)
    assert bc.codes.shape[0] == nblocks
    out_ref = ref.dict_decode(bc.codes, bc.literals, bc.nlit, bc.lut)
    out_pal = ops.dict_decode(bc.codes, bc.literals, bc.nlit, bc.lut,
                              impl="pallas_interpret")
    np.testing.assert_array_equal(np.asarray(out_ref), np.asarray(out_pal))


# ---------------------------------------------------------------------------
# full 64k dictionary: every LUT row of the per-vreg sweep
# ---------------------------------------------------------------------------

def _full_lut_planes(rng, nb, slots, n_codes=codec.ESCAPE):
    """Codes over an ``n_codes``-entry dictionary (R = 512 LUT rows when
    full), mixed in every code vreg: LUT row boundaries (127/128,
    1023/1024), the last row's codes, ESCAPE and uniform codes; literal
    words for every escape."""
    lut = rng.integers(0, 256, size=(n_codes, 4)).astype(np.uint8)
    kind = rng.integers(0, 4, size=(nb, slots))
    codes = np.where(
        kind == 0, rng.choice([127, 128, 1023, 1024], size=(nb, slots)),
        np.where(kind == 1, rng.integers(n_codes - 127, n_codes,
                                         size=(nb, slots)),
                 np.where(kind == 2, codec.ESCAPE,
                          rng.integers(0, n_codes, size=(nb, slots)))))
    codes = codes.astype(np.uint16)
    literals = rng.integers(0, 256, size=(nb, slots, 4)).astype(np.uint8)
    nlit = (codes == codec.ESCAPE).sum(axis=1).astype(np.int32)
    return (jnp.asarray(codes), jnp.asarray(literals), jnp.asarray(nlit),
            jnp.asarray(lut))


@pytest.mark.parametrize("nb,slots,n_codes", [
    (32, 1024, codec.ESCAPE),  # 16-row chunks: 2 sublane groups x 8 lanes
    (16, 96, codec.ESCAPE),    # slots % 128 != 0: one chunk, 2 groups
    (6, 96, codec.ESCAPE),     # one chunk, rows no whole sublane group
    (16, 256, 6000),           # R = 48: the sweep steps 16 rows at a time
])
def test_dict_decode_full_lut_bitexact(nb, slots, n_codes, rng):
    codes, literals, nlit, lut = _full_lut_planes(rng, nb, slots, n_codes)
    out_ref = ref.dict_decode(codes, literals, nlit, lut)
    out_pal = dd_kernel.dict_decode(codes, literals, nlit, lut,
                                    interpret=True)
    np.testing.assert_array_equal(np.asarray(out_pal), np.asarray(out_ref))
    c = np.asarray(codes)
    hits = np.asarray(out_pal).reshape(nb, slots, 4)[c != codec.ESCAPE]
    np.testing.assert_array_equal(hits, np.asarray(lut)[c[c != codec.ESCAPE]])


@pytest.mark.parametrize("n,k,tile_n,tile_k,slots", [
    (128, 512, 128, 512, 1024),  # internlm2's tile: bpt 16 = 2 x 8 vregs
    (32, 96, 16, 96, 96),        # one chunk of 96 slots, bpt 4
])
def test_fused_full_lut_bitexact(n, k, tile_n, tile_k, slots, rng):
    """Integer activations and power-of-two scales keep every sum exact,
    so the kernel must match the oracle bit for bit."""
    m = 8
    nb = n * k // (slots * 4)
    codes, literals, nlit, lut = _full_lut_planes(rng, nb, slots)
    x = jnp.asarray(rng.integers(-8, 9, size=(m, k)).astype(np.float32))
    scale = jnp.asarray(2.0 ** -rng.integers(4, 9, size=(n, 1)),
                        jnp.float32)
    zero = jnp.asarray(rng.integers(0, 256, size=(n, 1)), jnp.float32)
    kw = dict(shape=(n, k), tile_n=tile_n, tile_k=tile_k)
    y_ref = ref.fused_decode_matmul(x, codes, literals, nlit, lut, scale,
                                    zero, **kw)
    y_pal = fdm_kernel.fused_decode_matmul(x, codes, literals, lut, scale,
                                           zero, bm=m, interpret=True, **kw)
    np.testing.assert_array_equal(np.asarray(y_pal), np.asarray(y_ref))
