"""Compile every kernel of the serving path for a described TPU v5e.

Nothing runs: each test lowers and compiles one Pallas kernel at a real
width (internlm2-1.8b projections, its lm_head, deepseek-v2-lite's
64-expert stacks) for one chip of a ``v5e:2x2`` topology description, so
Mosaic refusals (unsupported gathers, casts, layouts, VMEM overflow) fail
here instead of on the chip.  The topology is described inside a
module-scoped fixture — never at import — because only one process may
load the TPU compiler library at a time.
"""
import importlib
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core.blocked_codec import choose_fused_tiles

dqmm = importlib.import_module("repro.kernels.dequant_matmul")
dd = importlib.import_module("repro.kernels.dict_decode")
fa = importlib.import_module("repro.kernels.flash_attention")
fdm = importlib.import_module("repro.kernels.fused_decode_matmul")

BLOCK_WEIGHTS = 4096
SEQ = 4
N_CODES = 65536


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip are written to but never read back
    # from the persistent cache: keep it out of the way.
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _planes(lead, n, k, sharding):
    """(tile_n, tile_k, planes) of an (n, k) weight in the tile-major
    layout serving picks; every slot may escape (the widest literal
    plane)."""
    tile_n, tile_k, bw = choose_fused_tiles((n, k), BLOCK_WEIGHTS)
    nb, slots = n * k // bw, bw // SEQ
    return tile_n, tile_k, (
            _sds(lead + (nb, slots), jnp.uint16, sharding),
            _sds(lead + (nb, slots, SEQ), jnp.uint8, sharding),
            _sds((N_CODES, SEQ), jnp.uint8, sharding),
            _sds(lead + (n, 1), jnp.float32, sharding),
            _sds(lead + (n, 1), jnp.float32, sharding))


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("n,k,m", [
    (2048, 2048, 8),      # internlm2-1.8b wq/wo, decode batch
    (8192, 2048, 8),      # internlm2-1.8b w1/w3
    (2048, 8192, 128),    # internlm2-1.8b w2, one prefill row block
    (576, 2048, 8),       # deepseek-v2-lite wkv_a: one whole-dim row tile
])
def test_fused_decode_matmul_compiles(one_chip, n, k, m):
    tile_n, tile_k, (codes, lits, lut, scale, zero) = _planes(
        (), n, k, one_chip)
    x = _sds((m, k), jnp.float32, one_chip)
    compiled = _compile(
        lambda *a: fdm.fused_decode_matmul(
            *a, shape=(n, k), tile_n=tile_n, tile_k=tile_k, bm=m,
            out_dtype=jnp.bfloat16),
        x, codes, lits, lut, scale, zero)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n,k", [
    (1408, 2048),   # deepseek-v2-lite expert w_gate/w_up
    (2048, 1408),   # deepseek-v2-lite expert w_down: (512, 128) tiles
])
def test_grouped_fused_decode_matmul_compiles(one_chip, n, k):
    e, m = 64, 8
    tile_n, tile_k, (codes, lits, lut, scale, zero) = _planes(
        (e,), n, k, one_chip)
    x = _sds((e, m, k), jnp.float32, one_chip)
    compiled = _compile(
        lambda *a: fdm.grouped_fused_decode_matmul(
            *a, shape=(n, k), tile_n=tile_n, tile_k=tile_k, bm=m,
            out_dtype=jnp.bfloat16),
        x, codes, lits, lut, scale, zero)
    assert "tpu_custom_call" in compiled.as_text()


def test_dequant_matmul_compiles_lm_head(one_chip):
    """internlm2-1.8b's quant-only lm_head (vocab 92544 × d_model 2048)."""
    n, k, m = 92544, 2048, 8
    compiled = _compile(
        lambda x, w, s, z: dqmm.dequant_matmul(x, w, s, z),
        _sds((m, k), jnp.float32, one_chip),
        _sds((n, k), jnp.uint8, one_chip),
        _sds((n, 1), jnp.float32, one_chip),
        _sds((n, 1), jnp.float32, one_chip))
    assert "tpu_custom_call" in compiled.as_text()


def test_dict_decode_compiles(one_chip):
    _, _, (codes, lits, lut, _, _) = _planes((), 2048, 2048, one_chip)
    nb = codes.shape[0]
    compiled = _compile(
        lambda c, l, nl, t: dd.dict_decode(c, l, nl, t),
        codes, lits, _sds((nb,), jnp.int32, one_chip), lut)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("tq,q_offset", [(256, 0), (1, 255)])
def test_flash_attention_compiles(one_chip, tq, q_offset):
    """internlm2-1.8b heads (16 q / 8 kv, head_dim 128): a prefill and a
    length-1 decode query over a 256-token cache."""
    b, hq, hkv, tk, d = 2, 16, 8, 256, 128
    compiled = _compile(
        lambda q, k, v: fa.flash_attention(q, k, v, causal=True,
                                           q_offset=q_offset),
        _sds((b, hq, tq, d), jnp.bfloat16, one_chip),
        _sds((b, hkv, tk, d), jnp.bfloat16, one_chip),
        _sds((b, hkv, tk, d), jnp.bfloat16, one_chip))
    assert "tpu_custom_call" in compiled.as_text()
