"""Paper §5 latency discussion — decompression overhead on CPU.

The paper's own latency numbers are CPU-measured (Xeon 6130): dense vs
quantized vs compressed per-example latency, where compressed pays the
layer-by-layer decode cost.  This container is also CPU, so these are real
wall-clock measurements of the same pipeline (smoke-scale model).

Also measures the microbenches the serving engine cares about:
  * kernel_latency — dict_decode + dequant_matmul vs a dense matmul.
  * fused_latency  — the fused decode→dequant→matmul path vs the legacy
    two-step (``impl='unfused'``) path at 1024² and 4096², with an
    estimated bytes-moved model alongside wall clock: the fused kernel
    replaces the 2·N·K dense-weight HBM round-trip with the compressed
    payload streamed per M-tile, which is the whole point of the
    megakernel (see kernels/fused_decode_matmul.py).
"""
from __future__ import annotations

import json

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import codec
from repro.core.blocked_codec import build_lut, choose_fused_tiles
from repro.core.compressed import (pack_expert_stack, pack_linear,
                                   quantize_linear)
from repro.core.policy import CompressionPolicy
from repro.kernels import ops
from repro.launch.mesh import make_mesh
from repro.kernels.fused_decode_matmul import DEFAULT_BM
from repro.serve.context import ServeContext
from repro.serve.engine import build_serve_params, generate

from .common import emit, time_call, trained_tiny_model, \
    synthetic_trained_weights


def serving_latency():
    cfg, params, _ = trained_tiny_model(steps=60)
    toks = jnp.ones((4, 16), jnp.int32)

    modes = {"dense": (params, None)}
    for mode in ("quant", "compressed"):
        st = build_serve_params(params, CompressionPolicy(
            mode=mode, min_weight_size=1024))
        modes[mode] = (st.params, st.lut)

    for mode, (p, lut) in modes.items():
        ctx = ServeContext(cfg=cfg, lut=lut)
        t = time_call(lambda p=p, ctx=ctx: generate(p, cfg, toks, ctx=ctx,
                                                    max_new=8),
                      warmup=1, iters=3)
        emit(f"latency.generate8.{mode}_s", f"{t:.4f}",
             "batch=4 prompt=16 (paper: compressed ~1.5-5x dense on CPU)")


def kernel_latency():
    rng = np.random.default_rng(0)
    n, k, m = 1024, 1024, 256
    w = jnp.asarray(rng.normal(size=(n, k)).astype(np.float32) * 0.02)
    x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
    ql = quantize_linear(w)
    table = codec.find_frequent_sequences([np.asarray(ql.values)])
    lut = jnp.asarray(build_lut(table))
    packed = pack_linear(w, table, np.asarray(lut), tile="auto")

    dense = jax.jit(lambda x: x @ w.T)
    quant = jax.jit(lambda x: ops.dequant_matmul(x, ql.values, ql.scale,
                                                 ql.zero, impl="ref"))
    comp = jax.jit(lambda x: ops.decode_dequant_matmul(x, packed, lut,
                                                       impl="ref"))
    td = time_call(dense, x)
    tq = time_call(quant, x)
    tc = time_call(comp, x)
    emit("latency.matmul_1024x1024.dense_us", f"{td*1e6:.1f}", "")
    emit("latency.matmul_1024x1024.quant_us", f"{tq*1e6:.1f}",
         f"{tq/td:.2f}x dense")
    emit("latency.matmul_1024x1024.compressed_us", f"{tc*1e6:.1f}",
         f"{tc/td:.2f}x dense (decode amortized per call)")


def _fused_bytes_model(m, n, k, payload, bm=DEFAULT_BM, tile_n=128,
                       dtype_bytes=4):
    """Estimated HBM bytes moved per call (TPU kernel traffic model).

    unfused: compressed payload in, dense uint8 weight written to HBM by
    dict_decode and read back by dequant_matmul (the 2·N·K round-trip),
    plus activations/outputs.
    fused:   compressed payload re-streamed once per M-tile of the grid,
    output written once; the decoded weight never leaves VMEM.
    Both matmul grids re-stream x once per N-tile (same 128-wide tiles),
    so that term is common and the delta is purely the weight traffic:
    2·N·K dense round-trip vs (M/bm)·payload.  Returns
    (unfused_total, fused_total, unfused_weight, fused_weight) so callers
    can report the weight-traffic ratio undiluted by the shared x/y terms.
    """
    x_b = -(-n // tile_n) * m * k * dtype_bytes    # per-N-tile x re-stream
    y_b = m * n * dtype_bytes
    w_unfused = payload + 2 * n * k
    w_fused = -(-m // bm) * payload
    return w_unfused + x_b + y_b, w_fused + x_b + y_b, w_unfused, w_fused


def fused_latency(rows: list | None = None):
    """Single-device fused vs unfused.  Appends machine-readable rows to
    ``rows`` (the BENCH_latency.json payload) alongside the CSV emits."""
    rng = np.random.default_rng(0)
    m = 256
    for size in (1024, 4096):
        n = k = size
        w = jnp.asarray(synthetic_trained_weights(rng, (n, k)))
        ql = quantize_linear(w)
        table = codec.find_frequent_sequences([np.asarray(ql.values)])
        lut = jnp.asarray(build_lut(table))
        packed = pack_linear(w, table, np.asarray(lut), tile="auto")
        x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
        # packed is an argument (not a closure constant) so XLA doesn't
        # constant-fold the decode into the compile.
        fused = jax.jit(lambda x, p: ops.decode_dequant_matmul(
            x, p, lut, out_dtype=jnp.float32))
        unfused = jax.jit(lambda x, p: ops.decode_dequant_matmul(
            x, p, lut, impl="unfused", out_dtype=jnp.float32))
        tf = time_call(fused, x, packed, iters=10)
        tu = time_call(unfused, x, packed, iters=10)
        ub, fb, uw, fw = _fused_bytes_model(m, n, k, packed.payload_nbytes,
                                            tile_n=packed.tile_n or 128)
        tag = f"latency.fused_matmul_{size}x{size}"
        emit(f"{tag}.unfused_ms", f"{tu*1e3:.2f}",
             f"two-step decode→matmul, ~{ub/2**20:.1f} MiB moved "
             f"({uw/2**20:.1f} MiB weight)")
        emit(f"{tag}.fused_ms", f"{tf*1e3:.2f}",
             f"{tu/tf:.2f}x unfused, ~{fb/2**20:.1f} MiB moved "
             f"({fw/2**20:.1f} MiB weight, {uw/fw:.1f}x fewer weight bytes)")
        if rows is not None:
            common = dict(bench="fused_matmul", m=m, n=n, k=k, devices=1,
                          mesh=None)
            rows.append(dict(common, path="unfused", wall_ms=tu * 1e3,
                             est_bytes_moved=ub, est_weight_bytes=uw))
            rows.append(dict(common, path="fused", wall_ms=tf * 1e3,
                             est_bytes_moved=fb, est_weight_bytes=fw,
                             speedup_vs_unfused=tu / tf))


def sharded_fused_latency(rows: list | None = None):
    """Shard-mapped fused vs unfused on a (data, model) mesh over the host
    devices.  Needs >1 device (CI exports
    XLA_FLAGS=--xla_force_host_platform_device_count=8); on a single
    device it emits a skip marker so the JSON schema stays stable."""
    from repro.sharding import partition as PT

    ndev = jax.device_count()
    if ndev < 2:
        emit("latency.sharded_fused.skipped", "1", "single device")
        if rows is not None:
            rows.append(dict(bench="fused_matmul", devices=ndev, mesh=None,
                             path="fused_shard_map", skipped="single device"))
        return
    msize = min(4, ndev)
    dsize = ndev // msize
    mesh = make_mesh((dsize, msize), ("data", "model"))
    rng = np.random.default_rng(0)
    m, size = 256, 1024
    n = k = size
    w = jnp.asarray(synthetic_trained_weights(rng, (n, k)))
    ql = quantize_linear(w)
    table = codec.find_frequent_sequences([np.asarray(ql.values)])
    lut = jnp.asarray(build_lut(table))
    picked = choose_fused_tiles((n, k), shards=(msize, 1))
    packed = pack_linear(w, table, np.asarray(lut), tile=picked[:2])
    if (n // packed.tile_n) % msize != 0:
        # odd device counts (3, 5, ...) where the out-tile bands cannot
        # split over the model axis: record the skip, don't crash the
        # JSON artifact
        emit("latency.sharded_fused.skipped", "1",
             f"out-tiles !% model={msize}")
        if rows is not None:
            rows.append(dict(bench="fused_matmul", devices=ndev,
                             mesh=[dsize, msize], path="fused_shard_map",
                             skipped=f"out-tiles !% model={msize}"))
        return
    x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
    with mesh, PT.active_mesh(mesh):
        fused = jax.jit(lambda x, p: ops.decode_dequant_matmul(
            x, p, lut, out_dtype=jnp.float32))
        unfused = jax.jit(lambda x, p: ops.decode_dequant_matmul(
            x, p, lut, impl="unfused", out_dtype=jnp.float32))
        ops.DISPATCH_COUNTS.clear()
        tf = time_call(fused, x, packed, iters=10)
        tu = time_call(unfused, x, packed, iters=10)
        assert ops.DISPATCH_COUNTS["fused_shard_map"] >= 1, \
            dict(ops.DISPATCH_COUNTS)
    tag = f"latency.sharded_fused_matmul_{size}x{size}.mesh{dsize}x{msize}"
    emit(f"{tag}.unfused_ms", f"{tu*1e3:.2f}", "two-step under mesh")
    emit(f"{tag}.fused_ms", f"{tf*1e3:.2f}",
         f"{tu/tf:.2f}x unfused, shard-mapped megakernel")
    if rows is not None:
        common = dict(bench="fused_matmul", m=m, n=n, k=k, devices=ndev,
                      mesh=[dsize, msize])
        rows.append(dict(common, path="unfused", wall_ms=tu * 1e3))
        rows.append(dict(common, path="fused_shard_map", wall_ms=tf * 1e3,
                         speedup_vs_unfused=tu / tf))


def _moe_expert_stack(rng, e, n, k):
    """Synthetic stacked compressed expert weight (one shared dictionary,
    tile-major planes, uniform literal cap) — what build_serve_params
    emits for ``experts/w_*`` leaves."""
    ws = [synthetic_trained_weights(rng, (n, k)) for _ in range(e)]
    return pack_expert_stack(ws)


def moe_fused_latency(rows: list | None = None):
    """Grouped expert megakernel vs the materialize-dense baseline.

    One stacked expert matmul (E, cap, d) × compressed (E, n, d) planes —
    the MoE serving hot loop.  The unfused baseline decodes the whole
    dense expert stack to HBM (E·n·d uint8 written + read back) before the
    einsum; the grouped kernel streams the compressed blocks per
    (expert, tile) instead.  tokens/s counts the E·cap gathered token
    slots each call processes.
    """
    rng = np.random.default_rng(0)
    # cap = one M-tile (decode-style capacity): the grouped grid streams
    # the compressed payload exactly once, the baseline still pays the
    # full dense round-trip
    e, n, k, cap = 4, 2048, 2048, 128
    packed, lut = _moe_expert_stack(rng, e, n, k)
    xe = jnp.asarray(rng.normal(size=(e, cap, k)).astype(np.float32))
    grouped = jax.jit(lambda x, p: ops.grouped_decode_dequant_matmul(
        x, p, lut, out_dtype=jnp.float32))
    unfused = jax.jit(lambda x, p: ops.grouped_decode_dequant_matmul(
        x, p, lut, impl="unfused", out_dtype=jnp.float32))
    ops.DISPATCH_COUNTS.clear()
    tg = time_call(grouped, xe, packed, iters=10)
    tu = time_call(unfused, xe, packed, iters=10)
    assert ops.DISPATCH_COUNTS["grouped_fused"] >= 1, \
        dict(ops.DISPATCH_COUNTS)
    tokens = e * cap
    # weight-byte traffic: the baseline's 2·E·n·k dense round-trip vs the
    # compressed payload re-streamed once per M-tile of the grid
    uw = packed.payload_nbytes + 2 * e * n * k
    fw = -(-cap // DEFAULT_BM) * packed.payload_nbytes
    tag = f"latency.moe_grouped_{e}x{n}x{k}"
    emit(f"{tag}.unfused_ms", f"{tu*1e3:.2f}",
         f"materialize-dense experts, ~{uw/2**20:.1f} MiB weight traffic")
    emit(f"{tag}.grouped_ms", f"{tg*1e3:.2f}",
         f"{tu/tg:.2f}x unfused, ~{fw/2**20:.1f} MiB weight "
         f"({uw/fw:.1f}x fewer weight bytes)")
    if rows is not None:
        common = dict(bench="moe_grouped_matmul", experts=e, n=n, k=k,
                      cap=cap, devices=1, mesh=None)
        rows.append(dict(common, path="unfused", wall_ms=tu * 1e3,
                         tokens_per_s=tokens / tu, est_weight_bytes=uw))
        rows.append(dict(common, path="grouped_fused", wall_ms=tg * 1e3,
                         tokens_per_s=tokens / tg, est_weight_bytes=fw,
                         speedup_vs_unfused=tu / tg))


def moe_generate_latency(rows: list | None = None):
    """End-to-end MoE serving: deepseek-v2-lite smoke ``generate`` with the
    grouped expert megakernel vs the forced materialize-dense baseline
    (``ops.set_default_impl('unfused')``; a renamed cfg busts the jit
    caches so both paths really trace).  Informational at smoke scale —
    48×64 experts are overhead-dominated on CPU; the perf claim lives in
    :func:`moe_fused_latency`'s representative-size rows."""
    import dataclasses

    from repro.configs import get_config
    from repro.models import lm as LM

    cfg = get_config("deepseek-v2-lite-16b").smoke
    params = LM.init_lm(jax.random.PRNGKey(0), cfg, jnp.float32)
    st = build_serve_params(params, CompressionPolicy(
        mode="compressed", min_weight_size=1024))
    toks = jnp.ones((4, 8), jnp.int32)
    max_new = 8
    prev = ops._DEFAULT_IMPL
    for path, cfg_v in (
            ("grouped_fused", cfg),
            ("unfused", dataclasses.replace(cfg,
                                            name=cfg.name + "-unfused"))):
        try:
            if path == "unfused":
                ops.set_default_impl("unfused")
            ops.DISPATCH_COUNTS.clear()
            t = time_call(lambda c=cfg_v: generate(
                st.params, c, toks, lut=st.lut, max_new=max_new),
                warmup=1, iters=3)
            disp = dict(ops.DISPATCH_COUNTS)
        finally:
            ops.set_default_impl(prev)
        tps = toks.shape[0] * max_new / t
        emit(f"latency.moe_generate.{path}_s", f"{t:.4f}",
             f"deepseek-v2-lite smoke, {tps:.1f} tok/s")
        if rows is not None:
            rows.append(dict(bench="moe_generate",
                             arch="deepseek-v2-lite-smoke", path=path,
                             wall_s=t, tokens_per_s=tps, dispatch=disp))


def moe_json(path: str = "BENCH_moe.json"):
    """Machine-readable MoE artifact: grouped fused vs materialize-dense,
    op-level (tokens/s + weight bytes moved) and generate-level."""
    rows: list = []
    moe_fused_latency(rows)
    moe_generate_latency(rows)
    payload = {"schema": 1, "bench": "moe",
               "backend": jax.default_backend(),
               "host_devices": jax.device_count(), "rows": rows}
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
    emit("moe.json_rows", str(len(rows)), path)
    return payload


def latency_json(path: str = "BENCH_latency.json"):
    """Machine-readable latency artifact: fused vs unfused, single-device
    vs shard-mapped — the seed of the perf trajectory CI tracks."""
    rows: list = []
    fused_latency(rows)
    sharded_fused_latency(rows)
    payload = {"schema": 1, "bench": "latency",
               "backend": jax.default_backend(),
               "host_devices": jax.device_count(), "rows": rows}
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
    emit("latency.json_rows", str(len(rows)), path)
    return payload


def main():
    serving_latency()
    kernel_latency()
    fused_latency()
    sharded_fused_latency()
    moe_fused_latency()
    moe_generate_latency()


if __name__ == "__main__":
    main()
