"""One run of one cell: set up, warm up, measure, check, report.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (``setup_s``, from process start to the window's start): draw the
seed's weights on the device one layer at a time, pack them on the host
with the program's ``build_serve_params`` (with the configuration's cached
dictionary), place them, build ``ResilientEngine.scheduler()``, and warm
up every prompt length the traffic sends plus the decode tick.  Then a
first step admits each client's first request (cut short so that the
clients finish them at spread-out steps, ``traffic.py``), and the window
opens: the harness steps the engine, records the end of every step and
sends each client's next request when its last one ends, until
``--seconds`` have passed.  Then, unmeasured and sending nothing more,
it steps on until a request the window admitted has finished, reads the
peak device memory, frees the program's state and compares a sample of
the finished requests with the reference (``correctness.py``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import pathlib
import shutil
import sys
import time

import numpy as np

from bench import (correctness, ledger as L, peaks as P, spec, tables,
                   validity, weights as W)
from bench.traffic import Traffic


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell needs."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Ctx:
    """What a per-layer metric reader may read."""
    config: dict
    model: object
    summary: dict
    ledger: L.Ledger
    peaks: dict
    trace: object = None                 # trace_reduce.Summary
    fused_weights: list = dataclasses.field(default_factory=list)
    rows_per_call: list = dataclasses.field(default_factory=list)


def _devices(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX sees {len(devs)} {devs[0].platform} "
                     f"device(s)")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def fused_weights(params, materialized=()) -> list:
    """[(N, K, plane bytes)] of every weight and layer the fused kernel
    multiplies: tile-laid compressed matrices outside the expert stacks,
    less those the model reads whole (``MATERIALIZED``)."""
    import jax
    from repro.core.compressed import PackedLinear
    out = []
    flat, _ = jax.tree_util.tree_flatten_with_path(
        params, is_leaf=lambda x: isinstance(x, PackedLinear))
    for path, leaf in flat:
        name = jax.tree_util.keystr(path)
        if not isinstance(leaf, PackedLinear) or "experts" in name \
                or leaf.tile_n == 0 or any(m in name for m in materialized):
            continue
        planes = [leaf.codes, leaf.literals, leaf.nlit, leaf.scale,
                  leaf.zero]
        layers = leaf.codes.shape[0] if leaf.codes.ndim == 3 else 1
        per = sum(int(p.nbytes) for p in planes) // layers
        n, k = leaf.shape
        out.extend([(n, k, per)] * layers)
    return out


def build(c, model, seed: int, device, log=log):
    """The served artifact for ``seed``: drawn, packed, placed."""
    import jax
    from repro.core import CompressionPolicy
    from repro.serve.engine import build_serve_params
    policy = CompressionPolicy(mode="compressed", min_weight_size=1024)
    cpu = jax.local_devices(backend="cpu")[0]

    def pack(s, table):
        t = time.perf_counter()
        glob, layers = W.host_model(s, model, c, device)
        params = model.to_program(glob, layers)
        del glob, layers
        t_draw = time.perf_counter() - t
        with jax.default_device(cpu):
            st = build_serve_params(params, policy, table=table,
                                    manifest=False)
        log(f"weights of seed {s}: drawn in {t_draw:.2f} s, packed in "
            f"{time.perf_counter() - t - t_draw:.2f} s "
            f"({sum(st.stats.values()) / 2**20:.2f} MiB)")
        return st

    path = tables.path_for(c)
    table = tables.load(path)
    if table is None:
        log(f"no dictionary at {path}: building it from seed "
            f"{c['table_seed']}")
        st = pack(c["table_seed"], None)
        tables.save(st.table, path)
        table = st.table
        if seed != c["table_seed"]:
            del st
            st = pack(seed, table)
    else:
        st = pack(seed, table)
    sp = jax.device_put(st.params, device)
    lut = jax.device_put(st.lut, device)
    return dataclasses.replace(st, params=sp, lut=lut)


def _warm(eng, traffic: Traffic, rng) -> None:
    """Compile every shape the window uses: a prefill per prompt length,
    the decode tick, the fragment insert."""
    from repro.serve.scheduler import Request
    t = time.perf_counter()
    lengths = traffic.prompt_lengths()
    for i, n in enumerate(lengths):
        eng.submit(Request(tokens=rng.integers(0, traffic.vocab, n,
                                               dtype=np.int32),
                           max_new=2, rid=10**9 + i))
    bad = [c for c in eng.drain() if c.finished not in L.OK_ENDINGS]
    if bad:
        raise RuntimeError(f"warm-up request ended {bad[0].finished}: "
                           f"{(bad[0].error or '')[:2000]}")
    log(f"warm-up: prompts {lengths} served in "
        f"{time.perf_counter() - t:.2f} s")


def _trace_ctx(directory):
    """Profile into ``directory``; no profiling where it is None."""
    import jax
    if directory is None:
        return contextlib.nullcontext()
    pathlib.Path(directory).mkdir(parents=True, exist_ok=True)
    return jax.profiler.trace(str(directory))


class GcPauses:
    """Collector pauses while open: (generation, seconds) of each."""

    def __init__(self):
        self.pauses, self._t = [], None

    def _cb(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t))

    def __enter__(self):
        gc.callbacks.append(self._cb)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._cb)


FINISH_STEPS = 64


def serve_window(eng, traffic: Traffic, seconds: float, *, trace_dir=None):
    """Drive the engine for ``seconds`` after one admission step, then
    until a request the window admitted has finished (at most
    ``FINISH_STEPS`` steps, not measured); returns (ledger, compiles
    inside the window, {step: seconds inside ``Engine.step``}, collector
    pauses inside the window)."""
    import jax
    from repro.launch.compile_cache import CompileClock
    from repro.serve.scheduler import Request
    led = L.Ledger()
    client_of, in_step = {}, {}
    clock = time.perf_counter

    def send(t, client):
        d = traffic.next(client)
        rid = len(client_of)
        client_of[rid] = client
        eng.submit(Request(tokens=d.prompt, max_new=d.max_new, rid=rid))
        led.sent(rid, t, len(d.prompt), d.max_new)

    def step():
        t = clock()
        with jax.profiler.TraceAnnotation("bench.step"):
            done = eng.step()
        t_end = clock()
        in_step[eng.steps - 1] = t_end - t
        led.step_done(eng.steps - 1, t_end, eng.health()["admitted"], done)
        for c in done:
            if c.finished not in L.OK_ENDINGS:
                log(f"request {c.rid} ended {c.finished}: "
                    f"{(c.error or '')[:400]}")
        return done, t_end

    # one admission step, before the window: every client's first request
    for k in range(traffic.clients):
        send(clock(), k)
    step()
    with _trace_ctx(trace_dir):
        with CompileClock() as cc, GcPauses() as gp, \
                jax.profiler.TraceAnnotation("bench.window"):
            t0 = clock()
            led.open_window(t0, eng.steps)
            while clock() - t0 < seconds:
                done, t = step()
                with jax.profiler.TraceAnnotation("bench.submit"):
                    for c in done:
                        send(t, client_of[c.rid])
    led.close_window()
    # nothing more is sent; the engine runs on until a request the window
    # admitted has finished, so that the comparison holds one of the
    # window's own prefills
    late = [r for r in led.recs.values() if r.admit_step is not None
            and r.admit_step >= led.first_window_step]
    for _ in range(FINISH_STEPS):
        if not late or any(r.finished is not None for r in late):
            break
        step()
    return led, cc.compiles, in_step, gp.pauses


TRACE_DIR = spec.BENCH / ".cache" / "trace"


def _validity(ops, fallbacks, rengine, window_compiles, health) -> list:
    checks = [validity.check_dispatch(ops.DISPATCH_COUNTS),
              validity.check_kernels(ops.KERNEL_COUNTS),
              validity.check_fallbacks(fallbacks),
              validity.check_rung(rengine.last_rung),
              validity.check_window_compiles(window_compiles),
              validity.check_requests(health)]
    for name, value, ok in checks:
        log(f"run check {name}: {value} ({'ok' if ok else 'FAILED'})")
    return checks


def _log_window(led, summ, setup_s, in_step, pauses) -> None:
    ks = led.window_steps()
    ends = {k: led.step_end[k] - led._prev_end(k) for k in ks}
    med = float(np.median(list(ends.values())))
    log(f"step seconds: median {med:.4f}, min {min(ends.values()):.4f}, "
        f"max {max(ends.values()):.4f}")
    slow = sorted(ks, key=lambda k: -ends[k])[:3]
    log("slowest steps: " + "; ".join(
        f"{k}: {ends[k]:.4f} s, {in_step[k]:.4f} s in Engine.step"
        for k in slow))
    full = [s for g, s in pauses if g == 2]
    log(f"collector in the window: {len(pauses)} pauses, "
        f"{sum(s for _, s in pauses):.4f} s in all, longest "
        f"{max((s for _, s in pauses), default=0.0):.4f} s; "
        f"{len(full)} full, {sum(full):.4f} s")
    log(f"window: {summ['steps']} steps, {summ['tokens']} tokens in "
        f"{summ['span_s']:.3f} s; {summ['prefills']} first tokens, "
        f"{summ['itl_samples']} gaps, {summ['completed']} finished, "
        f"setup {setup_s:.2f} s")


def _compare(model, c, seed, picked, limit, device):
    """(correct, widest gap or None) of the sampled requests."""
    t = time.perf_counter()
    gap, n_cmp = None, 0
    if picked:
        seqs, rows, served = correctness.served_rows(picked)
        ref = correctness.reference_logits(model, c, seed, seqs, rows,
                                           device=device)
        gap, n_cmp = float(correctness.gaps(ref, served).max()), len(served)
    log(f"reference: {len(picked)} requests, {n_cmp} served tokens "
        f"compared in {time.perf_counter() - t:.2f} s")
    return bool(n_cmp > 0 and gap <= limit), gap


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, root=spec.BENCH, require_tpu: bool = True,
        bench_json=None) -> dict:
    """Everything but printing.  Returns {"result": the result line's
    object, "checks": run-validity checks, "valid": bool, "ledger",
    "picked": the requests compared}."""
    from repro.kernels import ops
    from repro.models.config import ModelConfig
    from repro.serve.resilience import (FALLBACK_COUNTS, ResiliencePolicy,
                                        ResilientEngine)

    w = spec.workload(workload, root)
    c = spec.config(w["config"], root)
    bench = bench_json if isinstance(bench_json, dict) else spec.benchmark()
    devs = _devices(w["chips"], require_tpu)
    dev = devs[0]
    peaks = P.lookup(dev.device_kind) if require_tpu else {}
    model = spec.model(c["model_type"])
    cfg = ModelConfig(**model.program_config(c))
    traffic = Traffic(spec.traffic(w["traffic"], root), seed,
                      c["vocab_size"])
    serving = w["serving"]
    if serving.get("residency", "hbm") != "hbm":
        raise ValueError("only HBM-resident experts are benchmarked")
    max_len = serving.get("max_len", traffic.max_len())
    if max_len < traffic.max_len():
        raise ValueError(f"max_len {max_len} < the traffic's longest "
                         f"request {traffic.max_len()}")

    for counter in (ops.DISPATCH_COUNTS, ops.KERNEL_COUNTS, FALLBACK_COUNTS):
        counter.clear()
    state = build(c, model, seed, dev)
    fused = fused_weights(state.params, model.MATERIALIZED)
    rengine = ResilientEngine(cfg, state,
                              policy=ResiliencePolicy(verify="off"))
    eng = rengine.scheduler(n_slots=serving["slots"], max_len=max_len,
                            page_size=serving["page_size"])
    _warm(eng, traffic, np.random.default_rng(seed))
    eng.reset_stats()
    # the host's dense copies go now, and what set-up leaves is frozen out
    # of the collector's scans, as a server does once it has started
    gc.collect()
    gc.freeze()
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    led, window_compiles, in_step, pauses = serve_window(
        eng, traffic, seconds, trace_dir=TRACE_DIR if trace else None)
    gc.unfreeze()
    setup_s = led.t0 - t_start
    summ = led.summary()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    checks = _validity(ops, FALLBACK_COUNTS, rengine, window_compiles,
                       eng.health())
    _log_window(led, summ, setup_s, in_step, pauses)

    finished = [r for r in led.recs.values()
                if r.finished in L.OK_ENDINGS
                and r.finish_step >= led.first_window_step]
    late = [r for r in led.recs.values() if r.finished is not None
            and r.finish_step > led.last_window_step]
    log(f"after the window: {max(led.step_end) - led.last_window_step} "
        f"steps, {len(late)} more requests finished")
    picked = correctness.sample(finished, seed, w["correct"]["tokens"],
                                led.first_window_step)
    eng.close()
    rengine.close()
    del eng, rengine, state
    gc.collect()
    limit = float(w["correct"]["max_logit_gap"])
    correct, gap = _compare(model, c, seed, picked, limit, dev)

    result = {"correct": correct, "attempted": summ["attempted"],
              "failed": summ["failed"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    metrics = {}
    if not trace:
        e2e = {"tokens_per_s": (summ["tokens_per_s"], "tokens/s"),
               "itl_p95_ms": (summ["itl_p95_ms"], "ms"),
               "ttft_p95_ms": (summ["ttft_p95_ms"], "ms"),
               "peak_hbm_mib": (peak / 2**20, "MiB"),
               "setup_s": (setup_s, "s")}
        for m in spec.cell_metrics(bench, workload, "end_to_end"):
            value, unit = e2e[m["name"]]
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": unit}
    else:
        from bench import trace_reduce as TR
        summary = TR.reduce(TR.load(TR.find_xspace(TRACE_DIR)))
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        result["breakdown"] = summary.breakdown()
        ctx = Ctx(config=c, model=model, summary=summ, ledger=led,
                  peaks=peaks, trace=summary, fused_weights=fused,
                  rows_per_call=(led.window_prefills()
                                 + list(led.decode_rows().values())))
        for m in spec.cell_metrics(bench, workload, "per_layer"):
            value = spec.metric_reader(m["name"], root).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result.update(metrics=metrics, device=device,
                  checks={"logit_gap": {"value": gap, "limit": limit}})
    return {"result": result, "checks": checks,
            "valid": not validity.failures(checks), "ledger": led,
            "picked": picked}
