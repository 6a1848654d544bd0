#!/usr/bin/env python3
"""Prove the compressed serving path runs on one TPU chip.

    python3 chip_smoke.py               # one chip: kernel checks, A, B
    python3 chip_smoke.py --four-chips  # the expert-parallel mesh check

One process holds the chip for every phase:

  K. each Pallas kernel of the path at one real width — fused and grouped
     decode→dequant→matmul, dict_decode, dequant_matmul, flash_attention —
     against its XLA oracle (``repro.kernels.ref``) run on the same chip at
     float32 ``highest`` matmul precision;
  A. internlm2-1.8b, the whole published config, compressed, served through
     ``launch.serve.main`` (ResilientEngine → scheduler Engine → ops);
  B. deepseek-v2-lite-16b at published widths (64 experts, top-6, MLA) cut
     to its leading dense layer plus one MoE layer, with tiered expert
     residency.

Weights are random, made from a seed, and packed on the host.  Earlier
lines report per-phase pack / compile / serve seconds, the compressed MiB,
``peak_bytes_in_use``, the dispatch and fallback counters and the ladder
rung that served.  The script exits non-zero, without the final line, when
JAX finds no TPU, when a compressed matmul took any path but the fused
kernels (``unfused``, ``materialize``, a jnp oracle or interpret mode),
when any fallback fired, or when a kernel check is off.  Its last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

``--four-chips`` runs only phase B's model, packed once, on a (1, 4)
``data × model`` mesh with the experts on ``model``, and compares its
logits with the one-device run of the same packed parameters; per-device
``memory_stats`` show where the planes landed.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
FLOAT_TOL = 2e-3        # max |kernel - oracle| / max |oracle|, matmuls
ATTN_TOL = 2e-2         # flash attention (f32 softmax, MXU passes in-kernel)
MESH_TOL = 2e-2         # mesh vs one-device logits (bf16 activations)
FUSED_PATHS = {"fused", "grouped_fused"}
MESH_PATHS = FUSED_PATHS | {"fused_shard_map", "grouped_fused_shard_map"}

PHASE_A = ["--arch", "internlm2-1.8b", "--full", "--mode", "compressed",
           "--batch", "4", "--slots", "2", "--prompt-len", "16",
           "--max-new", "8", "--stagger", "1"]
PHASE_B_ARCH = "deepseek-v2-lite-16b"
PHASE_B_LAYERS = 2      # the leading dense layer + one MoE layer
PHASE_B = ["--arch", PHASE_B_ARCH, "--full", "--layers", str(PHASE_B_LAYERS),
           "--mode", "compressed", "--residency", "tiered",
           "--batch", "4", "--slots", "2",
           "--prompt-len", "16", "--max-new", "8", "--stagger", "1"]


class SmokeFailure(RuntimeError):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def _import_repro():
    src = ROOT / "src"
    check((src / "repro").is_dir(),
          f"no repro package at {src}: run chip_smoke.py from a checkout")
    sys.path.insert(0, str(src))


def _device():
    import jax
    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"no TPU: JAX sees {len(devs)} {devs[0].platform} device(s)")
    return devs


def _peak_bytes(dev):
    return (dev.memory_stats() or {}).get("peak_bytes_in_use")


def _relerr(a, b):
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / (np.abs(b).max() + 1e-30))


# ---------------------------------------------------------------------------
# Phase K: kernels vs oracles on the chip.
# ---------------------------------------------------------------------------

def kernel_checks(seed: int = 0) -> dict:
    import functools
    import importlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import codec
    from repro.core.blocked_codec import build_lut
    from repro.core.compressed import (pack_expert_stack, pack_linear,
                                       quantize_linear)
    from repro.kernels import ref
    fdm = importlib.import_module("repro.kernels.fused_decode_matmul")
    dd = importlib.import_module("repro.kernels.dict_decode")
    dqmm = importlib.import_module("repro.kernels.dequant_matmul")
    fa = importlib.import_module("repro.kernels.flash_attention")

    rng = np.random.default_rng(seed)
    cpu = jax.local_devices(backend="cpu")[0]
    dev = jax.devices()[0]

    def bf16_normal(*shape):
        x = rng.normal(size=shape).astype(np.float32)
        return np.asarray(jnp.asarray(x, jnp.bfloat16).astype(jnp.float32))

    def weights(*shape):
        return rng.laplace(0.0, 0.02, size=shape).astype(np.float32)

    def highest(fn, *args, **kw):
        with jax.default_matmul_precision("highest"):
            return jax.jit(functools.partial(fn, **kw))(*args)

    errs = {}
    # internlm2-1.8b wq: (2048, 2048), decode batch of 8
    n = k = 2048
    with jax.default_device(cpu):
        w = weights(n, k)
        ql = quantize_linear(jnp.asarray(w))
        table = codec.find_frequent_sequences([np.asarray(ql.values)])
        lut = build_lut(table)
        pk = pack_linear(jnp.asarray(w), table, lut, tile="auto")
    pk, lutd = jax.device_put((pk, jnp.asarray(lut)), dev)
    x = jax.device_put(bf16_normal(8, k), dev)
    shp = dict(shape=(n, k), tile_n=pk.tile_n, tile_k=pk.tile_k)
    y = fdm.fused_decode_matmul(x, pk.codes, pk.literals, lutd, pk.scale,
                                pk.zero, bm=8, **shp)
    y_ref = highest(ref.fused_decode_matmul, x, pk.codes, pk.literals,
                    pk.nlit, lutd, pk.scale, pk.zero, **shp)
    errs["fused_decode_matmul"] = (_relerr(y, y_ref), FLOAT_TOL)

    words = dd.dict_decode(pk.codes, pk.literals, pk.nlit, lutd)
    exact = ref.dict_decode(pk.codes, pk.literals, pk.nlit, lutd)
    errs["dict_decode"] = (float(np.count_nonzero(
        np.asarray(words) != np.asarray(exact))), 0.0)

    # deepseek-v2-lite expert stack: w_gate (1408, 2048), 8 experts
    e, n, k = 8, 1408, 2048
    with jax.default_device(cpu):
        stack, slut = pack_expert_stack([weights(n, k) for _ in range(e)])
    stack, slut = jax.device_put((stack, slut), dev)
    xe = jax.device_put(bf16_normal(e, 8, k), dev)
    shp = dict(shape=(n, k), tile_n=stack.tile_n, tile_k=stack.tile_k)
    y = fdm.grouped_fused_decode_matmul(xe, stack.codes, stack.literals,
                                        slut, stack.scale, stack.zero,
                                        bm=8, **shp)
    y_ref = highest(ref.grouped_fused_decode_matmul, xe, stack.codes,
                    stack.literals, stack.nlit, slut, stack.scale,
                    stack.zero, **shp)
    errs["grouped_fused_decode_matmul"] = (_relerr(y, y_ref), FLOAT_TOL)

    # internlm2-1.8b lm_head, quant-only: (92544, 2048)
    n, k = 92544, 2048
    with jax.default_device(cpu):
        ql = quantize_linear(jnp.asarray(weights(n, k)))
    ql = jax.device_put(ql, dev)
    x = jax.device_put(bf16_normal(8, k), dev)
    y = dqmm.dequant_matmul(x, ql.values, ql.scale, ql.zero)
    y_ref = highest(ref.dequant_matmul, x, ql.values, ql.scale, ql.zero)
    errs["dequant_matmul"] = (_relerr(y, y_ref), FLOAT_TOL)

    # internlm2-1.8b attention heads: 16 q / 8 kv, head_dim 128, prefill
    q = jax.device_put(jnp.asarray(bf16_normal(1, 16, 256, 128),
                                   jnp.bfloat16), dev)
    kv = [jax.device_put(jnp.asarray(bf16_normal(1, 8, 256, 128),
                                     jnp.bfloat16), dev) for _ in range(2)]
    o = fa.flash_attention(q, *kv, causal=True)
    o_ref = highest(ref.attention_naive, q, *kv, causal=True)
    errs["flash_attention"] = (_relerr(o, o_ref), ATTN_TOL)

    for name, (err, tol) in errs.items():
        print(f"kernel check {name}: error {err!r} (tolerance {tol!r})")
    return errs


# ---------------------------------------------------------------------------
# Phases A and B: serving through the launcher.
# ---------------------------------------------------------------------------

def serve_phase(name: str, argv: list, want_paths: set) -> dict:
    from repro.kernels import ops
    from repro.launch import serve
    ops.KERNEL_COUNTS.clear()
    print(f"--- phase {name}: launch.serve {' '.join(argv)}")
    t0 = time.perf_counter()
    s = serve.main(argv)
    wall = time.perf_counter() - t0
    kernels = dict(ops.KERNEL_COUNTS)
    print(f"phase {name}: pack {s['pack_s']:.2f} s, compile "
          f"{s['compile_s']:.2f} s ({s['compiles']} compiles), serve "
          f"{s['serve_s']:.2f} s, wall {wall:.2f} s; compressed "
          f"{s['compressed_mib']:.2f} MiB; peak_bytes_in_use "
          f"{s['peak_bytes_in_use']}")
    print(f"phase {name}: DISPATCH_COUNTS {s['dispatch']} FALLBACK_COUNTS "
          f"{s['fallbacks']} last_rung {s['last_rung']!r} KERNEL_COUNTS "
          f"{kernels}")
    want_requests = int(argv[argv.index("--batch") + 1])
    check(s["completed"] == want_requests
          and set(s["reasons"]) <= {"max_new", "eos"},
          f"phase {name}: served {s['completed']}/{want_requests}, "
          f"completions {s['reasons']}")
    paths = set(s["dispatch"])
    check(paths and paths <= FUSED_PATHS and want_paths <= paths,
          f"phase {name}: compressed matmuls took {s['dispatch']}, want "
          f"only {sorted(FUSED_PATHS)} including {sorted(want_paths)}")
    check(not s["fallbacks"], f"phase {name}: fallbacks {s['fallbacks']}")
    check(s["last_rung"] == "fused",
          f"phase {name}: last rung {s['last_rung']!r}, want 'fused'")
    check(set(kernels) == {"pallas"},
          f"phase {name}: kernels ran as {kernels}, want compiled only")
    return dict(s, wall_s=wall, kernels=kernels)


# ---------------------------------------------------------------------------
# --four-chips: expert-parallel mesh vs one device.
# ---------------------------------------------------------------------------

def four_chip_check(devs) -> dict:
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.core.policy import CompressionPolicy
    from repro.kernels import ops
    from repro.launch.mesh import make_mesh
    from repro.models import lm as LM
    from repro.serve.engine import build_serve_params
    from repro.sharding import partition as PT

    check(len(devs) >= 4, f"--four-chips needs 4 TPU chips, JAX sees "
                          f"{len(devs)}")
    cfg = dataclasses.replace(get_config(PHASE_B_ARCH).full,
                              n_layers=PHASE_B_LAYERS)
    t0 = time.perf_counter()
    with jax.default_device(jax.local_devices(backend="cpu")[0]):
        params = LM.init_lm(jax.random.PRNGKey(0), cfg, jnp.float32)
        st = build_serve_params(params, CompressionPolicy(
            mode="compressed", min_weight_size=1024), model_shards=4)
        del params
    print(f"four-chips: packed {cfg.name} x{cfg.n_layers} layers in "
          f"{time.perf_counter() - t0:.2f} s")
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(2, 16)), jnp.int32)

    def logits_on(mesh):
        caches = LM.init_caches(cfg, 2, 24, dtype=jnp.bfloat16)
        specs = PT.make_param_specs(st.params, mesh,
                                    PT.ShardingConfig(mode="serve"))
        sp = jax.device_put(st.params, PT.to_named(specs, mesh))
        lut = jax.device_put(st.lut, jax.NamedSharding(
            mesh, jax.sharding.PartitionSpec()))

        @jax.jit
        def f(sp, lut, toks, caches):
            with PT.active_mesh(mesh):
                logits, _, _ = LM.forward(sp, cfg, toks, caches=caches,
                                          pos=0, lut=lut)
            return logits[:, -1].astype(jnp.float32)

        ops.DISPATCH_COUNTS.clear()
        with mesh:
            out = np.asarray(f(sp, lut, toks, caches))
        # expert-plane bytes each device holds, from the placed shards
        experts = {d: 0 for d in mesh.devices.flat}
        for leaf in jax.tree_util.tree_leaves(sp["blocks"]["moe"]["experts"]):
            for shard in leaf.addressable_shards:
                experts[shard.device] += shard.data.nbytes
        return out, dict(ops.DISPATCH_COUNTS), list(experts.values())

    one = make_mesh((1, 1), ("data", "model"), devices=devs[:1])
    four = make_mesh((1, 4), ("data", "model"), devices=devs[:4])
    l1, d1, _ = logits_on(one)
    l4, d4, experts = logits_on(four)
    err = _relerr(l4, l1)
    print(f"four-chips: one-device dispatch {d1}; mesh (1,4) dispatch {d4}")
    print(f"four-chips: expert-plane bytes per device {experts}; "
          f"bytes_in_use per device "
          f"{[(d.memory_stats() or {}).get('bytes_in_use') for d in devs[:4]]}"
          f"; peak per device {[_peak_bytes(d) for d in devs[:4]]}")
    print(f"four-chips: logits error {err!r} (tolerance {MESH_TOL!r})")
    check(set(d1) <= FUSED_PATHS and "grouped_fused" in d1,
          f"one-device run took {d1}")
    check(set(d4) <= MESH_PATHS and "grouped_fused_shard_map" in d4,
          f"mesh run took {d4}, want the expert-parallel grouped kernel")
    check(np.isfinite(l4).all() and err <= MESH_TOL,
          f"mesh logits off by {err!r}")
    check(min(experts) > 0 and max(experts) == min(experts),
          f"expert planes not split over the mesh: {experts}")
    return dict(logit_error=err, expert_bytes=experts)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the expert-parallel (1, 4) mesh check")
    args = ap.parse_args(argv)
    try:
        _import_repro()
        devs = _device()
        from repro.launch import compile_cache
        print(f"device: {devs[0].device_kind} x{len(devs)}; compile cache "
              f"{compile_cache.setup()}")
        if args.four_chips:
            four_chip_check(devs)
        else:
            from repro.launch.compile_cache import CompileClock
            t0 = time.perf_counter()
            with CompileClock() as clock:
                errs = kernel_checks()
            print(f"phase K: {time.perf_counter() - t0:.2f} s, compile "
                  f"{clock.seconds:.2f} s; peak_bytes_in_use "
                  f"{_peak_bytes(devs[0])}")
            bad = {k: v for k, v in errs.items() if not v[0] <= v[1]}
            check(not bad, f"kernel checks off: {bad}")
            a = serve_phase("A", PHASE_A, {"fused"})
            b = serve_phase("B", PHASE_B, {"fused", "grouped_fused"})
            print(f"compile seconds: K {clock.seconds:.2f}, A "
                  f"{a['compile_s']:.2f}, B {b['compile_s']:.2f}, total "
                  f"{clock.seconds + a['compile_s'] + b['compile_s']:.2f}")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
