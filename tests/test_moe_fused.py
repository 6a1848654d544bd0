"""Grouped expert megakernel — fused decode→dequant→matmul for MoE stacks.

Acceptance contract of the grouped path: compressed expert stacks route
through ``ops.grouped_decode_dequant_matmul`` (probes 'grouped_fused' /
'grouped_fused_shard_map'), dense expert weights never materialize
(``layers.MATERIALIZE_COUNTS['packed_stacked']`` stays zero), and the
numerics match the materialize-dense baseline — across prime expert
counts, capacity-overflow drop slots, shared-expert configs, and 1×1 /
2×4 / 8×1 meshes, in both oracle ('ref') and kernel-body
('pallas_interpret') modes.  Multi-device meshes run in a subprocess
(XLA locks the device count at first init), mirroring
tests/test_sharded_fused.py.
"""
import dataclasses
import os
import subprocess
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core.compressed import PackedLinear, pack_expert_stack
from repro.core.policy import CompressionPolicy
from repro.kernels import ops
from repro.launch.mesh import make_mesh
from repro.models import layers as L


def _expert_stack(rng, e, n, k, tile=True):
    """Stacked compressed expert weight (shared dictionary, uniform literal
    cap) + lut + the dense f32 stack, as build_serve_params emits it."""
    ws = [rng.laplace(0.0, 0.02, size=(n, k)).astype(np.float32)
          for _ in range(e)]
    packed, lut = pack_expert_stack(ws, tile="auto" if tile else None)
    dense = packed.materialize(lut, jnp.float32)
    return packed, lut, dense


# ---------------------------------------------------------------------------
# op level: kernel vs oracle vs materialized dense, prime expert counts
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("e,n,k,m", [
    (3, 64, 128, 8),     # prime E, tile-multiple dims
    (5, 48, 64, 13),     # prime E, odd cap
    (7, 24, 96, 130),    # prime E, cap > DEFAULT_BM with remainder
])
def test_grouped_kernel_bitexact_vs_oracle(e, n, k, m, rng):
    """Integer x ⇒ every accumulation is exact: the grouped Pallas kernel
    must agree BITWISE with the vmapped strip-scan oracle, and to f32
    roundoff with the materialized-dense einsum (which pays one extra
    rounding per element building w = (q−z)·s)."""
    packed, lut, dense = _expert_stack(rng, e, n, k)
    xe = jnp.asarray(rng.integers(-8, 9, size=(e, m, k)).astype(np.float32))
    y_ref = ops.grouped_decode_dequant_matmul(xe, packed, lut, impl="ref",
                                              out_dtype=jnp.float32)
    y_pal = ops.grouped_decode_dequant_matmul(
        xe, packed, lut, impl="pallas_interpret", out_dtype=jnp.float32)
    y_dense = jnp.einsum("emk,enk->emn", xe, dense)
    np.testing.assert_array_equal(np.asarray(y_pal), np.asarray(y_ref))
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_dense),
                               rtol=1e-4, atol=1e-4)


def test_grouped_dispatch_probes_and_fallbacks(rng):
    """Single device: tile-major stacks take 'grouped_fused';
    impl='unfused' and linear-layout stacks fall back to
    'grouped_unfused' (materialize + einsum) with matching numerics."""
    packed, lut, _ = _expert_stack(rng, 4, 32, 128)
    plin, lutl, _ = _expert_stack(rng, 4, 32, 128, tile=False)
    xe = jnp.asarray(rng.normal(size=(4, 8, 128)).astype(np.float32))
    ops.DISPATCH_COUNTS.clear()
    y_f = ops.grouped_decode_dequant_matmul(xe, packed, lut, impl="ref",
                                            out_dtype=jnp.float32)
    y_u = ops.grouped_decode_dequant_matmul(xe, packed, lut, impl="unfused",
                                            out_dtype=jnp.float32)
    assert plin.tile_n == 0
    y_l = ops.grouped_decode_dequant_matmul(xe, plin, lutl, impl="ref",
                                            out_dtype=jnp.float32)
    c = ops.DISPATCH_COUNTS
    assert c["grouped_fused"] == 1 and c["grouped_unfused"] == 2, dict(c)
    err = float(jnp.abs(y_f - y_u).max() / (jnp.abs(y_u).max() + 1e-9))
    # unfused's inner decode/matmul follow the session default impl, which
    # is the bf16 kernel body under REPRO_TEST_IMPL=pallas_interpret
    tol = 1e-4 if ops._DEFAULT_IMPL in ("auto", "ref") else 2e-2
    assert err < tol, err
    assert y_l.shape == y_f.shape


def test_grouped_unfused_default_impl_lever(rng):
    """ops.set_default_impl('unfused') forces the materialize baseline
    through impl='auto' call sites (the benchmark lever)."""
    packed, lut, _ = _expert_stack(rng, 2, 32, 128)
    xe = jnp.asarray(rng.normal(size=(2, 8, 128)).astype(np.float32))
    prev = ops._DEFAULT_IMPL
    try:
        ops.set_default_impl("unfused")
        ops.DISPATCH_COUNTS.clear()
        ops.grouped_decode_dequant_matmul(xe, packed, lut)
        assert ops.DISPATCH_COUNTS["grouped_unfused"] == 1, \
            dict(ops.DISPATCH_COUNTS)
        assert ops.DISPATCH_COUNTS["grouped_fused"] == 0
    finally:
        ops.set_default_impl(prev)


# ---------------------------------------------------------------------------
# layer level: routing/capacity semantics identical across paths
# ---------------------------------------------------------------------------

def _moe_params(rng, cfg):
    """init_moe + build_serve_params → compressed expert stacks."""
    from repro.serve.engine import build_serve_params
    params = L.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    st = build_serve_params(params, CompressionPolicy(mode="compressed",
                                                      min_weight_size=1024))
    wg = st.params["experts"]["w_gate"]
    assert isinstance(wg, PackedLinear) and wg.tile_n > 0 \
        and wg.codes.ndim == 3
    return st


@pytest.mark.parametrize("impl", ["ref", "pallas_interpret"])
@pytest.mark.parametrize("capacity_factor", [1.25, 0.25])
def test_moe_layer_grouped_matches_materialize(impl, capacity_factor, rng):
    """apply_moe through the grouped kernel == the materialize-dense
    baseline, with and without capacity-overflow drop slots, shared
    experts included.  Identical routing (router is dense either way) —
    only the expert FFN path differs."""
    cfg = dataclasses.replace(get_config("deepseek-v2-lite-16b").smoke,
                              capacity_factor=capacity_factor)
    st = _moe_params(rng, cfg)
    x = jnp.asarray(rng.normal(size=(2, 8, cfg.d_model)).astype(np.float32))
    ops.DISPATCH_COUNTS.clear()
    L.MATERIALIZE_COUNTS.clear()
    y_f, aux_f = L.apply_moe(st.params, x, cfg, lut=st.lut, impl=impl)
    assert ops.DISPATCH_COUNTS["grouped_fused"] == 3, \
        dict(ops.DISPATCH_COUNTS)
    assert L.MATERIALIZE_COUNTS.get("packed_stacked", 0) == 0, \
        dict(L.MATERIALIZE_COUNTS)
    y_u, aux_u = L.apply_moe(st.params, x, cfg, lut=st.lut, impl="unfused")
    assert ops.DISPATCH_COUNTS["grouped_unfused"] == 3, \
        dict(ops.DISPATCH_COUNTS)
    err = float(jnp.abs(y_f - y_u).max() / (jnp.abs(y_u).max() + 1e-9))
    # strict f32 tolerance only when BOTH paths run f32: the kernel casts
    # x to bf16, and under REPRO_TEST_IMPL=pallas_interpret the unfused
    # baseline's inner dequant_matmul runs the (bf16) kernel body too
    strict = impl == "ref" and ops._DEFAULT_IMPL in ("auto", "ref")
    tol = 1e-4 if strict else 2e-2
    assert err < tol, err
    np.testing.assert_allclose(float(aux_f), float(aux_u), rtol=1e-5)


def test_moe_expert_scan_mode_still_materializes_per_expert(rng):
    """The paper's expert-granular scan mode (single-device edge config)
    keeps its decode-one-expert-at-a-time semantics and matches the
    grouped path."""
    cfg = dataclasses.replace(get_config("deepseek-v2-lite-16b").smoke,
                              moe_expert_scan=True)
    st = _moe_params(rng, cfg)
    x = jnp.asarray(rng.normal(size=(1, 8, cfg.d_model)).astype(np.float32))
    y_s, _ = L.apply_moe(st.params, x, cfg, lut=st.lut, impl="ref")
    cfg2 = dataclasses.replace(cfg, moe_expert_scan=False)
    y_g, _ = L.apply_moe(st.params, x, cfg2, lut=st.lut, impl="ref")
    err = float(jnp.abs(y_s - y_g).max() / (jnp.abs(y_g).max() + 1e-9))
    assert err < 1e-4, err


# ---------------------------------------------------------------------------
# model level: a compressed MoE config serves through the grouped kernel
# ---------------------------------------------------------------------------

def test_moe_generate_zero_expert_materialization(rng):
    """deepseek-v2-lite smoke (MLA + 8 routed + 2 shared experts) under
    ``generate``: every expert matmul dispatches grouped-fused, zero
    materialize calls on expert planes — the PR's acceptance probe."""
    from repro.models import lm as LM
    from repro.serve.engine import build_serve_params, generate

    cfg = get_config("deepseek-v2-lite-16b").smoke
    params = LM.init_lm(jax.random.PRNGKey(0), cfg, jnp.float32)
    st = build_serve_params(params, CompressionPolicy(mode="compressed",
                                                      min_weight_size=1024))
    toks = jnp.ones((2, 8), jnp.int32)
    ops.DISPATCH_COUNTS.clear()
    L.MATERIALIZE_COUNTS.clear()
    out = generate(st.params, cfg, toks, lut=st.lut, max_new=6)
    assert out.shape == (2, 14)
    c = ops.DISPATCH_COUNTS
    assert c["grouped_fused"] > 0, dict(c)
    assert c["grouped_unfused"] == 0, dict(c)
    assert L.MATERIALIZE_COUNTS.get("packed_stacked", 0) == 0, \
        dict(L.MATERIALIZE_COUNTS)
    # numerics: full forward fused vs forced-unfused
    logits_f, _, _ = LM.forward(st.params, cfg, toks, lut=st.lut)
    logits_u, _, _ = LM.forward(st.params, cfg, toks, lut=st.lut,
                                impl="unfused")
    err = float(jnp.abs(logits_f - logits_u).max() /
                (jnp.abs(logits_u).max() + 1e-9))
    assert err < 2e-2, err


# ---------------------------------------------------------------------------
# meshes: 1×1 / 2×4 / 8×1 expert-parallel parity (subprocess: XLA locks the
# device count at first init)
# ---------------------------------------------------------------------------

_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import numpy as np, jax, jax.numpy as jnp
from repro.configs import get_config
from repro.core.policy import CompressionPolicy
from repro.kernels import ops
from repro.models import layers as L
from repro.models import lm as LM
from repro.serve.engine import build_serve_params
from repro.launch.mesh import make_mesh
from repro.sharding import partition as PT

cfg = get_config("deepseek-v2-lite-16b").smoke
params = LM.init_lm(jax.random.PRNGKey(0), cfg, jnp.float32)
st = build_serve_params(params, CompressionPolicy(mode="compressed",
                                                  min_weight_size=1024),
                        model_shards=4)
toks = jnp.ones((2, 8), jnp.int32)

def prefill_logits(cfg_v, mesh, impl):
    caches = LM.init_caches(cfg_v, 2, 14, dtype=jnp.float32)
    specs = PT.make_param_specs(st.params, mesh,
                                PT.ShardingConfig(mode="serve"))
    sp = jax.device_put(st.params, PT.to_named(specs, mesh))
    lut = jax.device_put(st.lut, jax.NamedSharding(
        mesh, jax.sharding.PartitionSpec()))
    @jax.jit
    def f(sp, lut, toks, caches):
        with PT.active_mesh(mesh):
            logits, _, _ = LM.forward(sp, cfg_v, toks, caches=caches,
                                      pos=0, lut=lut, impl=impl)
        return logits[:, -1]
    with mesh:
        return f(sp, lut, toks, caches)

def relerr(a, b):
    return float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-9))

# expert-parallel dispatch: 8 experts over the model axis when it divides;
# graceful materialize fallback on the data-only mesh
for shape, want in (((1, 1), "grouped_fused"),
                    ((2, 4), "grouped_fused_shard_map"),
                    ((8, 1), "grouped_unfused")):
    mesh = make_mesh(shape, ("data", "model"))
    ops.DISPATCH_COUNTS.clear()
    L.MATERIALIZE_COUNTS.clear()
    lf = prefill_logits(cfg, mesh, "auto")
    c = dict(ops.DISPATCH_COUNTS)
    assert c.get(want, 0) > 0, (shape, c)
    if want != "grouped_unfused":
        assert c.get("grouped_unfused", 0) == 0, (shape, c)
        assert L.MATERIALIZE_COUNTS.get("packed_stacked", 0) == 0, \
            (shape, dict(L.MATERIALIZE_COUNTS))
    lu = prefill_logits(cfg, mesh, "unfused")
    e = relerr(lf, lu)
    assert e < 2e-2, (shape, e)

# local-routing MoE (shard_map dispatch) on the 2x4 mesh: compressed
# planes enter the shard_map expert-sharded, grouped kernel runs per shard
cfg_l = dataclasses.replace(cfg, moe_local_dispatch=True,
                            name=cfg.name + "-local")
mesh = make_mesh((2, 4), ("data", "model"))
ops.DISPATCH_COUNTS.clear()
L.MATERIALIZE_COUNTS.clear()
lf = prefill_logits(cfg_l, mesh, "auto")
c = dict(ops.DISPATCH_COUNTS)
assert c.get("grouped_fused_shard_map", 0) > 0, c
assert L.MATERIALIZE_COUNTS.get("packed_stacked", 0) == 0, \
    dict(L.MATERIALIZE_COUNTS)
lu = prefill_logits(cfg_l, mesh, "unfused")
assert relerr(lf, lu) < 2e-2, relerr(lf, lu)

print("MOE_MESH_OK")
"""


@pytest.mark.slow
def test_moe_mesh_parity_subprocess():
    """1×1 / 2×4 / 8×1 meshes: grouped dispatch probes + fused-vs-unfused
    parity for the global and local-routing MoE paths.  REPRO_TEST_IMPL
    passes through, so the kernel-interpret CI job runs the grouped
    kernel *body* under the shard-local (E/msize) shapes too."""
    env = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"}
    if os.environ.get("REPRO_TEST_IMPL"):
        env["REPRO_TEST_IMPL"] = os.environ["REPRO_TEST_IMPL"]
    r = subprocess.run([sys.executable, "-c", _MESH_SCRIPT],
                       capture_output=True, text=True, timeout=1800,
                       env=env)
    assert "MOE_MESH_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-2000:]


@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs 8 devices (tier1-multidevice CI job)")
def test_moe_generate_grouped_shard_map_8dev(rng):
    """Multi-device CI acceptance: one MoE-config generate through the
    grouped shard-mapped expert path, dispatch-probe asserted."""
    from repro.models import lm as LM
    from repro.serve.engine import build_serve_params, generate
    from repro.sharding import partition as PT

    cfg = dataclasses.replace(get_config("deepseek-v2-lite-16b").smoke,
                              moe_local_dispatch=True,
                              name="deepseek-v2-lite-smoke-local8")
    params = LM.init_lm(jax.random.PRNGKey(0), cfg, jnp.float32)
    st = build_serve_params(params, CompressionPolicy(mode="compressed",
                                                      min_weight_size=1024),
                            model_shards=4)
    mesh = make_mesh((2, 4), ("data", "model"))
    specs = PT.make_param_specs(st.params, mesh,
                                PT.ShardingConfig(mode="serve"))
    sp = jax.device_put(st.params, PT.to_named(specs, mesh))
    lut = jax.device_put(st.lut, jax.NamedSharding(
        mesh, jax.sharding.PartitionSpec()))
    toks = jnp.ones((2, 8), jnp.int32)
    ops.DISPATCH_COUNTS.clear()
    L.MATERIALIZE_COUNTS.clear()
    out = generate(sp, cfg, toks, lut=lut, max_new=6, mesh=mesh)
    assert out.shape == (2, 14)
    c = ops.DISPATCH_COUNTS
    assert c["grouped_fused_shard_map"] > 0, dict(c)
    assert c.get("grouped_unfused", 0) == 0, dict(c)
    assert L.MATERIALIZE_COUNTS.get("packed_stacked", 0) == 0, \
        dict(L.MATERIALIZE_COUNTS)
