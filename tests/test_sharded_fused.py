"""Shard-mapped fused decode→dequant→matmul parity + dispatch probes.

The acceptance contract of the sharded fused paths: under 1×1, 2×4 and
8×1 (data, model) meshes, ``ops.decode_dequant_matmul`` and
``ops.tiled_decode_dequant_matmul`` must (a) dispatch to the fused /
shard-mapped-fused path — asserted via the trace-time
``ops.DISPATCH_COUNTS`` probe, so a silent fall-back to the
dense-materializing two-step path fails the test — and (b) match the
unfused two-step baseline numerically.  Shapes include a prime M (131)
that forces the kernel-facing M-tile padding.  Multi-device meshes run in
a subprocess (XLA locks the device count at first init), mirroring
tests/test_sharding.py.
"""
import subprocess
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import codec
from repro.core.blocked_codec import build_lut, choose_fused_tiles
from repro.core.compressed import (pack_linear, pack_linear_tiled,
                                   quantize_linear)
from repro.kernels import ops
from repro.launch.mesh import make_mesh


def _packed(rng, n, k, msize=1, tiles=0):
    w = jnp.asarray(rng.laplace(0.0, 0.02, size=(n, k)).astype(np.float32))
    ql = quantize_linear(w)
    table = codec.find_frequent_sequences([np.asarray(ql.values)])
    lut = build_lut(table)
    if tiles:
        p = pack_linear_tiled(w, table, lut, tiles=tiles, tile="auto",
                              shards=(msize, 1))
    else:
        picked = choose_fused_tiles((n, k), shards=(msize, 1))
        p = pack_linear(w, table, lut, tile=picked[:2] if picked else None)
    return p, jnp.asarray(lut)


def test_dispatch_probe_single_device(rng):
    """No mesh → 'fused' / 'tiled_fused'; impl='unfused' → the two-step
    probes.  (Counters tick at trace time, once per jit trace.)"""
    p, lut = _packed(rng, 32, 128)
    pt, lutt = _packed(rng, 32, 128, tiles=4)
    x = jnp.asarray(rng.normal(size=(8, 128)).astype(np.float32))
    ops.DISPATCH_COUNTS.clear()
    ops.decode_dequant_matmul(x, p, lut, impl="ref")
    ops.decode_dequant_matmul(x, p, lut, impl="unfused")
    ops.tiled_decode_dequant_matmul(x, pt, lutt, impl="ref")
    ops.tiled_decode_dequant_matmul(x, pt, lutt, impl="unfused")
    c = ops.DISPATCH_COUNTS
    assert c["fused"] == 1 and c["unfused"] == 1, dict(c)
    assert c["tiled_fused"] == 1 and c["tiled_unfused"] == 1, dict(c)


def test_tiled_fused_single_device_matches_two_step(rng):
    """Grouped fused call over the whole column-tile stack ≈ the dense
    materialize+einsum path (f32 oracle on CPU)."""
    pt, lut = _packed(rng, 64, 256, tiles=4)
    assert pt.tile_n > 0
    x = jnp.asarray(rng.normal(size=(131, 256)).astype(np.float32))  # prime M
    y_f = ops.tiled_decode_dequant_matmul(x, pt, lut, impl="ref",
                                          out_dtype=jnp.float32)
    y_u = ops.tiled_decode_dequant_matmul(x, pt, lut, impl="unfused",
                                          out_dtype=jnp.float32)
    err = float(jnp.abs(y_f - y_u).max() / (jnp.abs(y_u).max() + 1e-9))
    assert err < 1e-4, err


def test_shard_aware_tile_choice_divides_per_shard_dims():
    tn, tk, _ = choose_fused_tiles((1024, 4096), shards=(8, 1))
    assert (1024 // 8) % tn == 0 and 4096 % tk == 0
    # shard count that doesn't divide the dim is ignored, not fatal
    assert choose_fused_tiles((70, 96), shards=(8, 1)) == \
        choose_fused_tiles((70, 96))


_SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import numpy as np, jax, jax.numpy as jnp
from repro.core import codec
from repro.core.blocked_codec import build_lut, choose_fused_tiles
from repro.core.compressed import pack_linear, pack_linear_tiled, quantize_linear
from repro.kernels import ops
from repro.launch.mesh import make_mesh
from repro.sharding import partition as PT

rng = np.random.default_rng(0)

def build(n, k, msize):
    w = jnp.asarray(rng.laplace(0.0, 0.02, size=(n, k)).astype(np.float32))
    ql = quantize_linear(w)
    table = codec.find_frequent_sequences([np.asarray(ql.values)])
    lut = build_lut(table)
    picked = choose_fused_tiles((n, k), shards=(msize, 1))
    return w, pack_linear(w, table, lut, tile=picked[:2]), table, lut

def relerr(a, b):
    return float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-9))

for mesh_shape in ((1, 1), (2, 4), (8, 1)):
    dsz, msz = mesh_shape
    mesh = make_mesh(mesh_shape, ("data", "model"))
    single = dsz * msz == 1
    # m=131: prime, > DEFAULT_BM once padded -> exercises the M-tile padding
    for (m, n, k) in ((16, 64, 128), (131, 64, 256)):
        w, packed, table, lut_np = build(n, k, msz)
        lut = jnp.asarray(lut_np)
        x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
        with mesh, PT.active_mesh(mesh):
            ops.DISPATCH_COUNTS.clear()
            y_f = jax.jit(lambda x, p: ops.decode_dequant_matmul(
                x, p, lut, out_dtype=jnp.float32))(x, packed)
            y_u = jax.jit(lambda x, p: ops.decode_dequant_matmul(
                x, p, lut, impl="unfused", out_dtype=jnp.float32))(x, packed)
        want = "fused" if single else "fused_shard_map"
        assert ops.DISPATCH_COUNTS[want] >= 1, (mesh_shape, dict(ops.DISPATCH_COUNTS))
        assert relerr(y_f, y_u) < 1e-4, (mesh_shape, (m, n, k), relerr(y_f, y_u))

        # row_parallel container: same fused path, same numbers
        rp = dataclasses.replace(packed, row_parallel=True)
        with mesh, PT.active_mesh(mesh):
            y_rp = jax.jit(lambda x, p: ops.decode_dequant_matmul(
                x, p, lut, out_dtype=jnp.float32))(x, rp)
        np.testing.assert_allclose(np.asarray(y_rp), np.asarray(y_f),
                                   rtol=1e-6, atol=1e-6)

        # TiledPackedLinear 2D-TP: tiles on data, block axis on model,
        # row-parallel psum over data in the epilogue
        tiled = pack_linear_tiled(w, table, lut_np, tiles=8, tile="auto",
                                  shards=(msz, 1))
        assert tiled.tile_n > 0
        with mesh, PT.active_mesh(mesh):
            ops.DISPATCH_COUNTS.clear()
            y_tf = jax.jit(lambda x, p: ops.tiled_decode_dequant_matmul(
                x, p, lut, out_dtype=jnp.float32))(x, tiled)
            y_tu = jax.jit(lambda x, p: ops.tiled_decode_dequant_matmul(
                x, p, lut, impl="unfused", out_dtype=jnp.float32))(x, tiled)
        want = "tiled_fused" if single else "tiled_fused_shard_map"
        assert ops.DISPATCH_COUNTS[want] >= 1, (mesh_shape, dict(ops.DISPATCH_COUNTS))
        assert relerr(y_tf, y_tu) < 1e-4, (mesh_shape, (m, n, k), relerr(y_tf, y_tu))

# out-tile count that does NOT divide the weight axes -> graceful two-step
# fallback (probe proves it), numerics still exact
mesh = make_mesh((2, 4), ("data", "model"))
w, packed, table, lut_np = build(64, 128, 1)   # tile_n=64 -> nnt=1, 1 % 4 != 0
lut = jnp.asarray(lut_np)
assert (64 // packed.tile_n) % 4 != 0
x = jnp.asarray(rng.normal(size=(8, 128)).astype(np.float32))
with mesh, PT.active_mesh(mesh):
    ops.DISPATCH_COUNTS.clear()
    y_f = jax.jit(lambda x, p: ops.decode_dequant_matmul(
        x, p, lut, out_dtype=jnp.float32))(x, packed)
    y_u = jax.jit(lambda x, p: ops.decode_dequant_matmul(
        x, p, lut, impl="unfused", out_dtype=jnp.float32))(x, packed)
assert ops.DISPATCH_COUNTS["fused_shard_map"] == 0, dict(ops.DISPATCH_COUNTS)
assert ops.DISPATCH_COUNTS["unfused"] >= 1, dict(ops.DISPATCH_COUNTS)
assert relerr(y_f, y_u) < 1e-5

print("SHARDED_FUSED_OK")
"""


@pytest.mark.slow
def test_sharded_fused_parity_subprocess():
    """1×1, 2×4, 8×1 meshes: fused/shard-mapped dispatch + parity vs the
    unfused baseline, for PackedLinear and TiledPackedLinear."""
    r = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT],
                       capture_output=True, text=True, timeout=900,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"})
    assert "SHARDED_FUSED_OK" in r.stdout, \
        r.stdout[-2000:] + r.stderr[-2000:]


@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs 8 devices (tier1-multidevice CI job)")
def test_sharded_fused_inprocess_8dev(rng):
    """Direct (non-subprocess) version for the multi-device CI job: the
    2×4 mesh must take both shard-mapped fused paths and match unfused."""
    from repro.sharding import partition as PT
    mesh = make_mesh((2, 4), ("data", "model"))
    p, lut = _packed(rng, 64, 256, msize=4)
    pt, lutt = _packed(rng, 64, 256, msize=4, tiles=8)
    x = jnp.asarray(rng.normal(size=(16, 256)).astype(np.float32))
    with mesh, PT.active_mesh(mesh):
        ops.DISPATCH_COUNTS.clear()
        y_f = jax.jit(lambda x, p: ops.decode_dequant_matmul(
            x, p, lut, out_dtype=jnp.float32))(x, p)
        y_u = jax.jit(lambda x, p: ops.decode_dequant_matmul(
            x, p, lut, impl="unfused", out_dtype=jnp.float32))(x, p)
        y_tf = jax.jit(lambda x, p: ops.tiled_decode_dequant_matmul(
            x, p, lutt, out_dtype=jnp.float32))(x, pt)
        y_tu = jax.jit(lambda x, p: ops.tiled_decode_dequant_matmul(
            x, p, lutt, impl="unfused", out_dtype=jnp.float32))(x, pt)
    c = ops.DISPATCH_COUNTS
    assert c["fused_shard_map"] >= 1 and c["tiled_fused_shard_map"] >= 1, \
        dict(c)
    for got, ref_ in ((y_f, y_u), (y_tf, y_tu)):
        err = float(jnp.abs(got - ref_).max() / (jnp.abs(ref_).max() + 1e-9))
        assert err < 1e-4, err
