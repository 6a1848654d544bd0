"""Serving launcher — compress a model and serve a request trace.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b \
        --mode compressed --batch 8 --slots 3 --stagger 2 --max-new 16

Widths are the config's smoke widths unless ``--full`` asks for the
published ones; ``--layers N`` keeps the first N layers (leading dense
layers included).  Random weights from ``--seed`` are quantized and packed
on the host CPU, so device memory holds only the served artifact.
``main(argv)`` runs in-process and returns the printed summary as a dict
(pack / serve / compile seconds, dispatch and fallback counters, the
ladder rung that served, per-request outputs, peak device bytes).  The
persistent compile cache is ``$JAX_COMPILATION_CACHE_DIR`` or
``<checkout>/.jax_cache`` (``launch/compile_cache.py``).

Drives the request-level API: each of ``--batch`` prompts is submitted as
a ``serve.Request`` with staggered arrivals (``--stagger`` engine steps
apart), served by the continuous-batching ``serve.Engine`` over a paged
KV pool of ``--slots`` decode slots — requests join and leave the running
decode loop per tick, and the occupancy/throughput summary printed at the
end shows the overlap.  Overload knobs: ``--max-queue`` bounds the
admission queue (overflow sheds per ``--shed-policy``) and
``--request-ttl`` expires requests that wait or run too long — overload
always surfaces as accounted-for completions ('shed'/'deadline'), and the
queue-peak/shed/preempt/quarantine counters print with the summary.  With
compression on, the engine comes from
``ResilientEngine.scheduler()``: every jitted prefill/decode step walks
the retry/degradation ladder and the health snapshot is printed.

Sharded serving (``--mesh DATA,MODEL``, e.g. with
``XLA_FLAGS=--xla_force_host_platform_device_count=8 ... --mesh 2,4``):
params are placed with the partition rules, the step functions are traced
under the mesh, and every compressed matmul dispatches through the
shard-mapped fused decode→dequant→matmul path — a single traced program
per phase, no dense per-device weight materialization (the dispatch
summary printed at the end proves which paths ran).  ``--tiles N`` stores
eligible weights as 2D-TP column tiles (TiledPackedLinear).

Tiered expert residency (``--residency tiered``, compressed MoE archs,
mesh-less): compressed expert planes back off to host RAM and an HBM
cache of ``--expert-cache-mib`` (0 = auto from ``--hbm-budget-mib`` via
``core.policy.device_budget`` — the paper's 4–8 GB edge budget) serves
the grouped kernel, with routing-aware one-layer-ahead prefetch
(serve/residency.py, docs/residency.md).  Outputs are bitwise-equal to
fully-resident serving; the summary adds hit/miss/prefetch/eviction/
bytes-fetched counters alongside the resilience health snapshot.

Runtime memory pressure (``--pressure-trace step|spike|ramp|oscillate``):
replays a seeded budget trace (``testing.faults.pressure_trace``) against
a ``serve.governor.MemoryGovernor`` attached to the engine — the budget
moves per step and the governor walks the reclaim/regrow ladder (trim
expert cache → shrink KV pool/preempt → tighten admission → refuse new
work as ``finished='pressure'``), with ``--min-budget-mib`` as the
operator refusal floor.  The end-of-run summary prints the applied plan,
plan-change count, and per-rung reclaim latency (docs/serving.md).
"""
from __future__ import annotations

import argparse
import time

import numpy as np
import jax
import jax.numpy as jnp

import dataclasses

from repro.configs import get_config
from repro.core import CompressionPolicy
from repro.kernels import ops
from repro.launch import compile_cache
from repro.launch.mesh import make_mesh
from repro.models import lm as LM
from repro.serve.context import ServeContext
from repro.serve.engine import build_serve_params
from repro.serve.resilience import (FALLBACK_COUNTS, ResiliencePolicy,
                                    ResilientEngine)
from repro.serve.scheduler import Engine, Request
from repro.sharding import partition as PT
from repro.train.data import DataConfig, DataPipeline


def _parse_mesh(spec: str | None):
    """'2,4' -> Mesh((2, 4), ('data', 'model')); None -> no mesh."""
    if not spec:
        return None
    shape = tuple(int(s) for s in spec.split(","))
    assert len(shape) == 2, f"--mesh wants DATA,MODEL, got {spec!r}"
    ndev = jax.device_count()
    need = shape[0] * shape[1]
    assert need <= ndev, (f"--mesh {spec} needs {need} devices, have {ndev} "
                          f"(set XLA_FLAGS=--xla_force_host_platform_"
                          f"device_count={need} for CPU)")
    return make_mesh(shape, ("data", "model"))


def _parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--full", action="store_true",
                    help="serve the config's published widths (default: "
                         "its reduced smoke widths)")
    ap.add_argument("--layers", type=int, default=0,
                    help="depth cut: keep the first N layers, leading "
                         "dense layers included (0 = all)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    ap.add_argument("--mode", default="compressed",
                    choices=["dense", "quant", "compressed"])
    ap.add_argument("--batch", type=int, default=4,
                    help="number of requests in the trace")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=3,
                    help="decode slots in the paged-KV pool (requests "
                         "beyond this queue and join as slots free)")
    ap.add_argument("--page-size", type=int, default=8,
                    help="tokens per KV page")
    ap.add_argument("--stagger", type=int, default=2,
                    help="engine steps between request arrivals "
                         "(0 = all at once)")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="bound the admission queue: overflow sheds a "
                         "request per --shed-policy as a "
                         "Completion(finished='shed') (default: unbounded)")
    ap.add_argument("--shed-policy", default="reject-new",
                    choices=["reject-new", "drop-oldest"],
                    help="who sheds when the bounded queue overflows")
    ap.add_argument("--request-ttl", type=int, default=None,
                    help="engine-wide TTL in engine steps from submit; "
                         "expired requests complete with "
                         "finished='deadline' (default: no TTL)")
    ap.add_argument("--mesh", default=None,
                    help="DATA,MODEL mesh shape for sharded serving")
    ap.add_argument("--tiles", type=int, default=0,
                    help="2D-TP column tiles for compressed weights "
                         "(TiledPackedLinear; 0 = plain PackedLinear)")
    ap.add_argument("--verify", default="off",
                    choices=["off", "fast", "full"],
                    help="integrity gate before serving: re-hash the "
                         "packed artifact against its manifest (fast = "
                         "sampled digests, full = every byte) plus the "
                         "device-side invariant check; corrupt leaves "
                         "refuse to serve (core/integrity.py)")
    ap.add_argument("--residency", default="hbm",
                    choices=["hbm", "tiered"],
                    help="expert residency: 'hbm' keeps every compressed "
                         "expert on device; 'tiered' backs them in host "
                         "RAM with a routing-aware HBM cache "
                         "(serve/residency.py; compressed MoE only, "
                         "mesh-less)")
    ap.add_argument("--expert-cache-mib", type=int, default=0,
                    help="HBM expert-cache size for --residency tiered "
                         "(0 = auto from --hbm-budget-mib via "
                         "core.policy.device_budget)")
    ap.add_argument("--hbm-budget-mib", type=int, default=4096,
                    help="device memory budget used to auto-size the "
                         "expert cache (paper target: 4-8 GB edge)")
    ap.add_argument("--pressure-trace", default="none",
                    choices=["none", "step", "spike", "ramp", "oscillate"],
                    help="replay a seeded runtime memory-pressure trace "
                         "against the serving engine: the budget moves "
                         "per step and serve.governor.MemoryGovernor "
                         "walks the reclaim/regrow ladder "
                         "(testing.faults.pressure_trace; seeded via "
                         "REPRO_FAULT_SEED)")
    ap.add_argument("--pressure-low-mib", type=int, default=0,
                    help="the trace's low watermark (0 = auto: 60%% of "
                         "--hbm-budget-mib)")
    ap.add_argument("--min-budget-mib", type=int, default=0,
                    help="operator floor for the governor: below this it "
                         "refuses new work (finished='pressure') instead "
                         "of reclaiming further (0 = the computed "
                         "min_viable floor only)")
    return ap


def _serving_config(args):
    """The config at the requested widths, cut to ``--layers``."""
    entry = get_config(args.arch)
    cfg = entry.full if args.full else entry.smoke
    n_all = cfg.n_layers
    lead = cfg.first_dense_layers if cfg.family == "moe" else 0
    if args.layers:
        if not lead < args.layers <= n_all:
            raise SystemExit(f"--layers {args.layers}: {cfg.name} has "
                             f"{n_all} layers, {lead} of them leading "
                             f"dense layers that the cut keeps")
        cfg = dataclasses.replace(cfg, n_layers=args.layers)
    print(f"depth: kept {cfg.n_layers} of {n_all} layers "
          f"({lead} leading dense) at {'published' if args.full else 'smoke'}"
          f" widths, d_model {cfg.d_model}")
    return cfg


def _build_on_host(args, cfg, model_shards):
    """Random weights from ``--seed`` and the served artifact, built on
    the host CPU so the dense f32 parameters never occupy device memory.
    Returns (serve state or None, served params, lut)."""
    with jax.default_device(jax.local_devices(backend="cpu")[0]):
        params = LM.init_lm(jax.random.PRNGKey(args.seed), cfg, jnp.float32)
        if args.mode == "dense":
            return None, params, None
        st = build_serve_params(
            params, CompressionPolicy(mode=args.mode, min_weight_size=1024,
                                      tiles=args.tiles),
            model_shards=model_shards)
    return st, st.params, st.lut


def _place(sp, lut, mesh, keep_experts_on_host: bool):
    """Move the served artifact to the device(s): per the partition rules
    on a mesh (lut replicated), else onto the default device — except
    tiered-residency expert stacks, whose backing tier is host RAM."""
    if mesh is not None:
        specs = PT.make_param_specs(sp, mesh, PT.ShardingConfig(mode="serve"))
        sp = jax.device_put(sp, PT.to_named(specs, mesh))
        if lut is not None:
            lut = jax.device_put(
                lut, jax.NamedSharding(mesh, jax.sharding.PartitionSpec()))
        return sp, lut
    dev = jax.devices()[0]
    experts = None
    if keep_experts_on_host:
        experts = sp["blocks"]["moe"]["experts"]
        sp = {**sp, "blocks": {**sp["blocks"], "moe": {
            **sp["blocks"]["moe"], "experts": None}}}
    sp = jax.device_put(sp, dev)
    if experts is not None:
        sp["blocks"]["moe"]["experts"] = experts
    return sp, (None if lut is None else jax.device_put(lut, dev))


def main(argv=None) -> dict:
    """Run the launcher; returns the printed summary as a dict."""
    args = _parser().parse_args(argv)
    cache = compile_cache.setup()
    with compile_cache.CompileClock() as clock:
        summary = _serve(args)
    summary.update(compile_s=clock.seconds, compiles=clock.compiles,
                   cache_dir=cache)
    print(f"compile: {clock.seconds:.2f} s in {clock.compiles} backend "
          f"compiles (cache {cache})")
    return summary


def _serve(args) -> dict:
    mesh = _parse_mesh(args.mesh)
    model_shards = mesh.shape["model"] if mesh is not None else 1
    cfg = _serving_config(args)
    data = DataPipeline(DataConfig(vocab_size=cfg.vocab_size,
                                   batch=args.batch,
                                   seq_len=args.prompt_len,
                                   seed=args.seed))
    t_pack = time.perf_counter()
    st, sp, lut = _build_on_host(args, cfg, model_shards)
    pack_s = time.perf_counter() - t_pack
    mib = None
    if st is not None:
        mib = sum(st.stats.values()) / 2**20
        print(f"{args.mode} weights: {mib:.2f} MiB (packed on host in "
              f"{pack_s:.2f} s)")
    sp, lut = _place(sp, lut, mesh, args.residency == "tiered")
    if mesh is not None:
        print(f"mesh: {dict(mesh.shape)}")

    max_len = args.prompt_len + args.max_new

    def _tree_bytes(t):
        return sum(int(l.nbytes) for l in jax.tree_util.tree_leaves(t)
                   if hasattr(l, "nbytes"))

    def _device_budget(expert_bytes: int) -> "object":
        from repro.core.policy import device_budget
        from repro.serve.kv_cache import PagedKVPool
        resident_bytes = _tree_bytes(sp) - expert_bytes + \
            (int(lut.nbytes) if lut is not None else 0)
        probe_pool = PagedKVPool(cfg, args.slots, max_len,
                                 page_size=args.page_size)
        kv_bytes = _tree_bytes(probe_pool.pages)
        return device_budget(args.hbm_budget_mib * 2**20,
                             expert_bytes=expert_bytes,
                             resident_bytes=resident_bytes,
                             kv_bytes=kv_bytes,
                             act_bytes=64 * 2**20)

    budget = None
    residency = None
    if args.residency == "tiered":
        # Tiered expert residency: compressed expert planes back off to
        # host RAM; an HBM cache sized by the device budget serves the
        # grouped kernel (serve/residency.py).  Compressed MoE, mesh-less.
        from repro.serve.residency import ResidencyManager
        assert args.mode == "compressed", \
            "--residency tiered requires --mode compressed"
        assert mesh is None, "--residency tiered is single-device (no --mesh)"

        budget = _device_budget(_tree_bytes(sp["blocks"]["moe"]["experts"]))
        cache_bytes = (args.expert_cache_mib * 2**20
                       if args.expert_cache_mib > 0
                       else budget.expert_cache_bytes)
        st = dataclasses.replace(st, params=sp, lut=lut)
        residency = ResidencyManager(st, cfg, cache_bytes=cache_bytes)
        # summary(expert_cache_used=...) surfaces the overshoot when the
        # granted budget was too small and the cache clamped to its
        # one-expert-per-layer floor — never silently hidden
        used = (residency.capacity * residency.n_layers
                * residency.bytes_per_expert)
        print(budget.summary(expert_cache_used=used))
        print(f"expert cache: {residency.capacity}/{residency.n_experts} "
              f"experts/layer x {residency.n_layers} layers "
              f"({used / 2**20:.2f} MiB of "
              f"{cache_bytes / 2**20:.2f} MiB granted)")

    governor = None
    if args.pressure_trace != "none":
        from repro.serve.governor import MemoryGovernor
        from repro.testing.faults import pressure_trace
        if budget is None:
            budget = _device_budget(0)
        low = (args.pressure_low_mib * 2**20 if args.pressure_low_mib > 0
               else int(0.6 * args.hbm_budget_mib * 2**20))
        trace = pressure_trace(args.pressure_trace,
                               boot_bytes=budget.budget_bytes,
                               low_bytes=low, n_steps=64)
        state = {"i": 0}

        def poll():
            i = min(state["i"], len(trace) - 1)
            state["i"] += 1
            return trace[i]

        governor = MemoryGovernor(
            budget, poll=poll,
            min_budget_bytes=(args.min_budget_mib * 2**20
                              if args.min_budget_mib > 0 else None))
        print(f"pressure trace: {args.pressure_trace} "
              f"({budget.budget_bytes / 2**20:.0f} -> {low / 2**20:.0f} MiB "
              f"low watermark over {len(trace)} steps)")
    if st is not None:
        # integrity gate (manifest re-hash + device invariants) runs at
        # construction when --verify is on; corrupt leaves raise
        # IntegrityError naming themselves instead of serving garbage.
        rengine = ResilientEngine(
            cfg, dataclasses.replace(st, params=sp, lut=lut),
            policy=ResiliencePolicy(verify=args.verify), mesh=mesh,
            residency=residency)
        if args.verify != "off":
            print(rengine.verify_report.summary())
            print(rengine.invariant_report.summary())
        eng = rengine.scheduler(n_slots=args.slots, max_len=max_len,
                                page_size=args.page_size,
                                max_queue=args.max_queue,
                                shed_policy=args.shed_policy,
                                request_ttl=args.request_ttl,
                                governor=governor)
    else:
        rengine = None
        eng = Engine(ServeContext(cfg=cfg, mesh=mesh, lut=lut), sp,
                     n_slots=args.slots, max_len=max_len,
                     page_size=args.page_size, max_queue=args.max_queue,
                     shed_policy=args.shed_policy,
                     request_ttl=args.request_ttl, governor=governor)

    toks = np.asarray(data.batch_at(0)["tokens"])
    arrivals = [i * args.stagger for i in range(args.batch)]
    ops.DISPATCH_COUNTS.clear()
    FALLBACK_COUNTS.clear()

    t = time.perf_counter()
    submitted = 0
    while submitted < args.batch or eng.health()["occupied"] \
            or eng.health()["queued"]:
        while submitted < args.batch and eng.steps >= arrivals[submitted]:
            eng.submit(Request(tokens=toks[submitted],
                               max_new=args.max_new, rid=submitted))
            submitted += 1
        eng.step()
    jax.block_until_ready(eng.pool.pages)
    dt = time.perf_counter() - t

    h = eng.health()
    n_tok = sum(c.n_generated for c in eng.completions)
    print(f"served {h['completed']} requests / {n_tok} tokens in "
          f"{1e3*dt:.1f} ms ({n_tok/dt:.1f} tok/s) over {h['steps']} steps")
    print(f"occupancy: mean {h['occupancy_mean']:.2f} "
          f"max {h['occupancy_max']} of {args.slots} slots; "
          f"joined mid-decode: {h['joined_mid_decode']}")
    print(f"overload: queue_peak {h['queue_peak']} shed {h['shed']} "
          f"expired {h['expired']} preempted {h['preempted']} "
          f"quarantined {h['quarantined']} resumed {h['resumed']}")
    reasons = {}
    for c in eng.completions:
        reasons[c.finished] = reasons.get(c.finished, 0) + 1
    print("completions by reason:", reasons)
    if args.mode == "compressed":
        print("matmul dispatch:", dict(ops.DISPATCH_COUNTS))
    if rengine is not None:
        print("health:", rengine.health())
    if rengine is not None and rengine.residency is not None:
        r = rengine.residency.snapshot()
        print(f"residency: hits {r['hit']} (+{r['prefetch_hit']} prefetch) "
              f"misses {r['miss']} evictions {r['evict']} "
              f"fetched {r['bytes_fetched']/2**20:.2f} MiB "
              f"hit_rate {r['hit_rate']} prefetch_hit_rate "
              f"{r['prefetch_hit_rate']} stall {r['stall_s']:.3f}s")
    if governor is not None:
        s = governor.snapshot()
        print(f"pressure: plan_changes {s['plan_changes']} "
              f"refusing {s['refusing']} plan {s['plan']} "
              f"rung_latency_s {s['rung_latency_s']}")
    by_rid = {c.rid: c for c in eng.completions}
    print("sample:", by_rid[0].tokens[args.prompt_len:].tolist())
    peak = _peak_bytes(mesh)
    if peak is not None:
        print(f"device memory: peak_bytes_in_use {peak}")
    eng.close()       # stop the residency prefetch worker (no leaked
    # threads — asserted in tests; see Engine.close)
    return dict(
        arch=args.arch, config=cfg.name, n_layers=cfg.n_layers,
        mode=args.mode, completed=h["completed"], tokens=n_tok,
        steps=h["steps"], serve_s=dt, pack_s=pack_s, compressed_mib=mib,
        reasons=reasons, dispatch=dict(ops.DISPATCH_COUNTS),
        fallbacks=dict(FALLBACK_COUNTS),
        last_rung=rengine.last_rung if rengine is not None else None,
        outputs={rid: c.tokens[args.prompt_len:].tolist()
                 for rid, c in by_rid.items()},
        peak_bytes_in_use=peak)


def _peak_bytes(mesh):
    """Peak device bytes per device where the backend reports them."""
    devs = (list(mesh.devices.flat) if mesh is not None
            else jax.devices()[:1])
    stats = [d.memory_stats() for d in devs]
    if not all(stats):
        return None
    peaks = [st.get("peak_bytes_in_use") for st in stats]
    return peaks[0] if len(peaks) == 1 else peaks


if __name__ == "__main__":
    main()
