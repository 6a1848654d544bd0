"""Continuous-batching engine — slot lifecycle, paging, and parity.

The request-level API's acceptance contract:

  * every request served through ``serve.Engine`` — whenever it arrived,
    whichever slot it landed in, whoever its co-tenants were — yields
    tokens **bitwise-equal** to a one-shot ``engine.generate`` of the same
    prompt at the pool's cache length;
  * requests join a *running* decode loop (mid-decode admission), finish
    independently (EOS or budget), and free their slot + pages for queued
    requests — with no stale KV bleeding across page reuse;
  * one ``generate_step`` trace serves the whole mixed trace (admissions
    and completions are traced-value changes, never retraces);
  * the degradation ladder covers the scheduler's jitted steps via
    ``ResilientEngine.scheduler()``.

The request-level robustness layer rides the same contract:

  * overload is *accounted*, never unbounded: a full bounded queue sheds
    per policy, TTL'd requests expire queued or in-flight — always as
    completions with explicit reasons;
  * a poisoned request is quarantined alone: the bisect isolates exactly
    one culprit from a mixed batch (reusing the existing trace), and the
    survivors — like preempted-then-resumed victims — finish bitwise-equal
    to an uninterrupted run;
  * page pressure (overcommitted ``n_pages``, injected alloc failure)
    preempts strictly-lower-priority work, never deadlocks admission.

Plus the satellite seams: the ``Impl`` enum as the one home for impl
strings, and ``ServeContext`` deprecating the loose ``lut=``/``mesh=``
kwargs.
"""
import dataclasses
import warnings

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core import CompressionPolicy
from repro.kernels import ops
from repro.launch.mesh import make_mesh
from repro.models import lm as LM
from repro.serve import engine as engine_mod
from repro.serve.context import ServeContext
from repro.serve.engine import build_serve_params, generate
from repro.serve.kv_cache import PagedKVPool, PoolError, PoolExhausted
from repro.serve.resilience import (FALLBACK_COUNTS, ResiliencePolicy,
                                    ResilientEngine)
from repro.serve.scheduler import Engine, Request
from repro.testing import FaultInjector


@pytest.fixture(scope="module")
def served():
    """(cfg, ServeState, ctx) for the dense smoke config."""
    cfg = get_config("llama3.2-1b").smoke
    params = LM.init_lm(jax.random.PRNGKey(0), cfg, jnp.float32)
    st = build_serve_params(
        params, CompressionPolicy(mode="compressed", min_weight_size=1024))
    return cfg, st, ServeContext.from_state(cfg, st)


def _prompts(cfg, n, seed=100):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab_size,
                        int(rng.randint(4, 12))).astype(np.int32)
            for _ in range(n)]


def _ref(st, cfg, ctx, prompt, max_new, max_len):
    return np.asarray(generate(st.params, cfg, prompt[None, :], ctx=ctx,
                               max_new=max_new, max_len=max_len))[0]


# -- parity ------------------------------------------------------------

def test_single_request_bitwise_parity(served):
    cfg, st, ctx = served
    eng = Engine(ctx, st.params, n_slots=2, max_len=24)
    [p] = _prompts(cfg, 1)
    eng.submit(Request(tokens=p, max_new=5))
    comps = eng.drain()
    assert len(comps) == 1 and comps[0].finished == "max_new"
    np.testing.assert_array_equal(
        comps[0].tokens, _ref(st, cfg, ctx, p, 5, eng.pool.max_len))


def test_mixed_trace_staggered_arrivals_bitwise_parity(served):
    """The acceptance bar: 8 overlapping requests, staggered arrivals,
    varied prompt/decode lengths, 3 slots — every output bitwise-equal to
    one-shot generate, with occupancy > 1 and mid-decode admissions."""
    cfg, st, ctx = served
    eng = Engine(ctx, st.params, n_slots=3, max_len=20)
    prompts = _prompts(cfg, 8)
    rng = np.random.RandomState(0)
    max_news = rng.randint(3, 9, 8)
    arrivals = np.concatenate([[0], np.cumsum(rng.poisson(1.5, 7))])
    submitted = 0
    while submitted < 8 or eng.health()["occupied"] or eng.health()["queued"]:
        while submitted < 8 and eng.steps >= arrivals[submitted]:
            eng.submit(Request(tokens=prompts[submitted],
                               max_new=int(max_news[submitted]),
                               rid=submitted))
            submitted += 1
        eng.step()
    h = eng.health()
    assert h["completed"] == 8
    assert h["occupancy_max"] > 1
    assert h["joined_mid_decode"] >= 1
    by_rid = {c.rid: c for c in eng.completions}
    for i, p in enumerate(prompts):
        np.testing.assert_array_equal(
            by_rid[i].tokens,
            _ref(st, cfg, ctx, p, int(max_news[i]), eng.pool.max_len),
            err_msg=f"request {i} diverged from one-shot generate")


def test_one_trace_serves_the_whole_trace(served):
    """Admissions/completions are traced-value changes: a full multi-
    admission drain runs on ONE generate_step trace (and one prefill)."""
    cfg, st, ctx = served
    cfgf = dataclasses.replace(cfg, name=cfg.name + "-sched-trace")
    eng = Engine(ctx.with_cfg(cfgf), st.params, n_slots=2, max_len=20)
    engine_mod.TRACE_COUNTS.clear()
    for i, p in enumerate(_prompts(cfg, 4)):
        eng.submit(Request(tokens=p, max_new=4, rid=i))
    eng.drain()
    assert engine_mod.TRACE_COUNTS["generate_step"] == 1, \
        dict(engine_mod.TRACE_COUNTS)
    assert len(eng.completions) == 4


# -- slot lifecycle ----------------------------------------------------

def test_completion_frees_slot_and_queue_refills(served):
    """More requests than slots: early finishers free their slot, queued
    requests join the *running* loop, pages recycle, outputs stay exact."""
    cfg, st, ctx = served
    eng = Engine(ctx, st.params, n_slots=2, max_len=16)
    prompts = _prompts(cfg, 5, seed=7)
    max_news = [2, 6, 3, 5, 4]
    for i, p in enumerate(prompts):
        eng.submit(Request(tokens=p, max_new=max_news[i], rid=i))
    n_pages0 = len(eng.pool.free_pages)
    eng.drain()
    h = eng.health()
    assert h["completed"] == 5
    assert h["joined_mid_decode"] >= 1          # refill joined mid-stream
    assert len(eng.pool.free_pages) == n_pages0  # all pages returned
    by_rid = {c.rid: c for c in eng.completions}
    for i, p in enumerate(prompts):
        np.testing.assert_array_equal(
            by_rid[i].tokens,
            _ref(st, cfg, ctx, p, max_news[i], eng.pool.max_len),
            err_msg=f"request {i}: stale KV after page reuse?")



def test_occupancy_counts_every_step(served):
    """``health()`` occupancy is over every step since ``reset_stats``:
    max_new 3 and 5 on two slots occupy 2, 2, 1, 1 slots."""
    cfg, st, ctx = served
    eng = Engine(ctx, st.params, n_slots=2, max_len=16)
    for i, (p, n) in enumerate(zip(_prompts(cfg, 2, seed=11), (3, 5))):
        eng.submit(Request(tokens=p, max_new=n, rid=i))
    eng.drain()
    h = eng.health()
    assert (h["steps"], h["occupancy_mean"], h["occupancy_max"]) == \
        (4, 1.5, 2)
    eng.reset_stats()
    h = eng.health()
    assert (h["occupancy_mean"], h["occupancy_max"]) == (0.0, 0)

def test_page_reuse_no_stale_kv(served):
    """Serve the same prompt before and after other tenants churned
    through the pool's pages (LIFO reuse): outputs must be identical."""
    cfg, st, ctx = served
    eng = Engine(ctx, st.params, n_slots=2, max_len=16)
    [p0, p1, p2] = _prompts(cfg, 3, seed=11)
    eng.submit(Request(tokens=p0, max_new=5, rid=0))
    first = eng.drain()[0].tokens
    # churn: different prompts write different KV into the same pages
    eng.submit(Request(tokens=p1, max_new=6, rid=1))
    eng.submit(Request(tokens=p2, max_new=4, rid=2))
    eng.drain()
    eng.submit(Request(tokens=p0, max_new=5, rid=3))
    again = eng.drain()[0].tokens
    np.testing.assert_array_equal(first, again)


def test_eos_stops_early_and_frees_slot(served):
    """A request whose eos_id matches a mid-stream token finishes early
    with finished='eos', truncated at (and including) the EOS token."""
    cfg, st, ctx = served
    eng = Engine(ctx, st.params, n_slots=2, max_len=24)
    [p] = _prompts(cfg, 1, seed=3)
    full = Engine(ctx, st.params, n_slots=1, max_len=24)
    full.submit(Request(tokens=p, max_new=6))
    ref = full.drain()[0].tokens
    gen = ref[len(p):]
    eos = int(gen[2])                      # a token generated mid-stream
    eng.submit(Request(tokens=p, max_new=6, eos_id=eos))
    [c] = eng.drain()
    assert c.finished == "eos"
    assert c.n_generated <= 6 and c.tokens[-1] == eos
    np.testing.assert_array_equal(c.tokens, ref[:len(p) + c.n_generated])
    assert eng.health()["occupied"] == 0
    assert len(eng.pool.free_pages) == eng.pool.n_pages


def test_sampling_deterministic_per_request(served):
    """temperature > 0: per-request PRNG (seed folded with absolute
    position) makes outputs reproducible run to run."""
    cfg, st, ctx = served
    outs = []
    for _ in range(2):
        eng = Engine(ctx, st.params, n_slots=2, max_len=20)
        for i, p in enumerate(_prompts(cfg, 2, seed=5)):
            eng.submit(Request(tokens=p, max_new=5, temperature=0.8,
                               seed=42 + i, rid=i))
        eng.drain()
        outs.append({c.rid: c.tokens for c in eng.completions})
    for rid in outs[0]:
        np.testing.assert_array_equal(outs[0][rid], outs[1][rid])


def test_submit_validates(served):
    cfg, st, ctx = served
    eng = Engine(ctx, st.params, n_slots=1, max_len=16)
    with pytest.raises(ValueError, match="max_len"):
        eng.submit(Request(tokens=np.arange(10), max_new=10))
    with pytest.raises(ValueError, match="empty"):
        eng.submit(Request(tokens=np.zeros((0,), np.int32)))


# -- cache paging across model families --------------------------------

def test_moe_dropless_parity():
    """MoE configs page too (stacked + per-layer 'first' caches, MLA
    latent planes).  Expert-capacity drops depend on batch size, so exact
    parity needs the dropless regime (capacity_factor >= E / top_k)."""
    cfg = get_config("deepseek-v2-lite-16b").smoke
    cfg = dataclasses.replace(cfg, name=cfg.name + "-sched-dropless",
                              capacity_factor=float(cfg.n_experts)
                              / cfg.top_k)
    params = LM.init_lm(jax.random.PRNGKey(0), cfg, jnp.float32)
    st = build_serve_params(
        params, CompressionPolicy(mode="compressed", min_weight_size=1024))
    ctx = ServeContext.from_state(cfg, st)
    eng = Engine(ctx, st.params, n_slots=2, max_len=16)
    prompts = _prompts(cfg, 3, seed=9)
    for i, p in enumerate(prompts):
        eng.submit(Request(tokens=p, max_new=3, rid=i))
    eng.drain()
    assert eng.health()["occupancy_max"] > 1
    by_rid = {c.rid: c for c in eng.completions}
    for i, p in enumerate(prompts):
        np.testing.assert_array_equal(
            by_rid[i].tokens, _ref(st, cfg, ctx, p, 3, eng.pool.max_len))


def test_recurrent_families_rejected():
    """ssm state has no time axis to page — the pool must refuse loudly
    at construction, not corrupt state silently."""
    cfg = get_config("mamba2-2.7b").smoke
    with pytest.raises(ValueError):
        PagedKVPool(cfg, 2, 16)


# -- resilience composition --------------------------------------------

def test_resilient_scheduler_ladder_on_ingraph_fault(served):
    """A persistent fused-kernel fault inside the jitted generate_step:
    the guard walks the ladder, re-traces unfused, and the served outputs
    equal the clean run's."""
    cfg, st, _ = served
    prompts = _prompts(cfg, 2, seed=13)

    def run(cfg_run, inject):
        reng = ResilientEngine(cfg_run, st,
                               policy=ResiliencePolicy(max_retries=0))
        eng = reng.scheduler(n_slots=2, max_len=16)
        for i, p in enumerate(prompts):
            eng.submit(Request(tokens=p, max_new=4, rid=i))
        if inject:
            with FaultInjector().decode_fault(nth=1):
                eng.drain()
        else:
            eng.drain()
        return reng, {c.rid: c.tokens for c in eng.completions}

    _, clean = run(dataclasses.replace(cfg, name=cfg.name + "-rs-clean"),
                   False)
    reng, faulty = run(dataclasses.replace(cfg, name=cfg.name + "-rs-fault"),
                       True)
    assert reng.last_rung == "unfused"
    assert FALLBACK_COUNTS["unfused"] >= 1
    for rid in clean:
        np.testing.assert_array_equal(clean[rid], faulty[rid])


# -- admission control (overload is accounted, never unbounded) --------

def test_bounded_queue_sheds_per_policy(served):
    cfg, st, ctx = served
    [p] = _prompts(cfg, 1, seed=23)
    # reject-new: the overflowing submission sheds
    eng = Engine(ctx, st.params, n_slots=1, max_len=16, max_queue=1)
    r0 = eng.submit(Request(tokens=p, max_new=1))
    r1 = eng.submit(Request(tokens=p, max_new=1))
    assert [c.rid for c in eng.completions] == [r1]
    assert eng.completions[0].finished == "shed"
    assert eng.completions[0].n_generated == 0
    assert eng.health()["queued"] == 1 and eng.health()["shed"] == 1
    # drop-oldest: the queue head sheds, the new submission queues
    eng = Engine(ctx, st.params, n_slots=1, max_len=16, max_queue=1,
                 shed_policy="drop-oldest")
    r0 = eng.submit(Request(tokens=p, max_new=1))
    r1 = eng.submit(Request(tokens=p, max_new=1))
    assert [c.rid for c in eng.completions] == [r0]
    assert eng.completions[0].finished == "shed"
    assert [q.req.rid for q in eng._queue] == [r1]
    assert FALLBACK_COUNTS["shed"] == 2
    with pytest.raises(ValueError, match="shed_policy"):
        Engine(ctx, st.params, shed_policy="drop-newest")


def test_request_ttl_expires_queued_and_inflight(served):
    cfg, st, ctx = served
    p = _prompts(cfg, 1, seed=25)[0][:6]
    eng = Engine(ctx, st.params, n_slots=1, max_len=16)
    eng.submit(Request(tokens=p, max_new=4, rid=0))
    eng.submit(Request(tokens=p, max_new=4, rid=1, ttl_steps=1))
    eng.step()                    # r0 takes the only slot; r1 queued
    eng.step()                    # r1's TTL passes while queued
    by_rid = {c.rid: c for c in eng.completions}
    assert by_rid[1].finished == "deadline" and by_rid[1].n_generated == 0
    eng.drain()
    # in-flight expiry: admitted, decodes, then retired mid-stream with
    # its partial output
    eng.submit(Request(tokens=p, max_new=10, rid=2, ttl_steps=3))
    eng.drain()
    c = {c.rid: c for c in eng.completions}[2]
    assert c.finished == "deadline"
    assert 0 < c.n_generated < 10
    np.testing.assert_array_equal(c.tokens[:len(p)], p)
    # engine-wide default TTL applies to requests that don't carry one
    eng = Engine(ctx, st.params, n_slots=1, max_len=16, request_ttl=0)
    eng.submit(Request(tokens=p, max_new=4, rid=3))
    eng.step()
    assert eng.completions[0].finished == "deadline"
    assert FALLBACK_COUNTS["expired"] == 3


def test_rid_collision_rejected(served):
    cfg, st, ctx = served
    [p] = _prompts(cfg, 1, seed=27)
    eng = Engine(ctx, st.params, n_slots=2, max_len=16)
    eng.submit(Request(tokens=p, max_new=1, rid=7))
    with pytest.raises(ValueError, match="rid 7 already in flight"):
        eng.submit(Request(tokens=p, max_new=1, rid=7))
    # auto-assigned rids stay ahead of caller-supplied ones
    assert eng.submit(Request(tokens=p, max_new=1)) == 8
    eng.drain()
    # a finished rid is no longer live and may be reused
    assert eng.submit(Request(tokens=p, max_new=1, rid=7)) == 7
    eng.drain()


# -- preemption + page pressure ----------------------------------------

def test_preempt_under_page_pressure_resumes_bitwise(served):
    """Overcommitted pool (2 pages back 1 of 2 slots): a priority-1
    arrival evicts the in-flight priority-0 request, which later resumes
    and still matches one-shot generate bitwise."""
    cfg, st, ctx = served
    p0 = _prompts(cfg, 1, seed=29)[0][:6]
    p1 = _prompts(cfg, 1, seed=31)[0][:6]
    eng = Engine(ctx, st.params, n_slots=2, max_len=16, page_size=8,
                 n_pages=2)
    eng.submit(Request(tokens=p0, max_new=8, rid=0))
    eng.step()                                  # r0 holds the only pages
    eng.submit(Request(tokens=p1, max_new=3, rid=1, priority=1))
    eng.drain()
    h = eng.health()
    assert h["preempted"] == 1 and h["resumed"] == 1
    assert FALLBACK_COUNTS["preempt"] == 1
    by_rid = {c.rid: c for c in eng.completions}
    assert by_rid[0].resumed == 1 and by_rid[0].finished == "max_new"
    np.testing.assert_array_equal(
        by_rid[0].tokens, _ref(st, cfg, ctx, p0, 8, eng.pool.max_len),
        err_msg="preempted+resumed request diverged from generate")
    np.testing.assert_array_equal(
        by_rid[1].tokens, _ref(st, cfg, ctx, p1, 3, eng.pool.max_len))
    # equal priority must NOT preempt (no livelock-swap): the late
    # arrival waits for pages instead
    eng = Engine(ctx, st.params, n_slots=2, max_len=16, page_size=8,
                 n_pages=2)
    eng.submit(Request(tokens=p0, max_new=4, rid=0))
    eng.step()
    eng.submit(Request(tokens=p1, max_new=2, rid=1))
    eng.step()
    assert eng.health()["preempted"] == 0
    assert eng.health()["queued"] == 1
    eng.drain()
    assert all(c.finished == "max_new" for c in eng.completions)


def test_alloc_failure_injection_both_seams(served):
    cfg, st, ctx = served
    p = _prompts(cfg, 1, seed=33)[0][:6]
    inj = FaultInjector()
    # can_alloc seam: pressure visible before prefill — admission waits
    eng = Engine(ctx, st.params, n_slots=1, max_len=16)
    eng.submit(Request(tokens=p, max_new=2, rid=0))
    with inj.alloc_failure(times=1) as probe:
        eng.step()
        assert eng.health()["queued"] == 1      # blocked, not crashed
    assert probe.executions == 1
    [c] = eng.drain()
    assert c.finished == "max_new"
    # alloc seam: post-prefill PoolExhausted — requeued at the head
    eng = Engine(ctx, st.params, n_slots=1, max_len=16)
    eng.submit(Request(tokens=p, max_new=2, rid=0))
    with inj.alloc_failure(times=1, seam="alloc") as probe:
        eng.step()
        assert eng.health()["queued"] == 1
    assert probe.executions == 1
    [c] = eng.drain()
    assert c.finished == "max_new"


def test_pool_alloc_free_invariants(served):
    cfg, _, _ = served
    pool = PagedKVPool(cfg, 2, 16, page_size=8)
    pool.alloc(0)
    with pytest.raises(PoolError, match="already owns"):
        pool.alloc(0)                           # double alloc
    n_free = len(pool.free_pages)
    pool.free(1)                                # never allocated: no-op
    assert len(pool.free_pages) == n_free
    pool.free(0)
    assert len(pool.free_pages) == pool.n_pages
    # overcommit: 2 pages back only one slot
    pool = PagedKVPool(cfg, 2, 16, page_size=8, n_pages=2)
    pool.alloc(0)
    assert not pool.can_alloc()
    with pytest.raises(PoolExhausted, match="exhausted"):
        pool.alloc(1)
    with pytest.raises(ValueError, match="cannot back even one slot"):
        PagedKVPool(cfg, 2, 16, page_size=8, n_pages=1)


def test_drain_error_carries_health_and_slot_state(served):
    """A non-converging drain must raise with the health snapshot and
    per-slot/queue rid state attached — the operator's first clue."""
    cfg, st, ctx = served
    [p] = _prompts(cfg, 1, seed=35)
    eng = Engine(ctx, st.params, n_slots=1, max_len=16)
    eng.submit(Request(tokens=p, max_new=2, rid=0))
    with FaultInjector().alloc_failure(times=1 << 30):
        with pytest.raises(RuntimeError, match="did not converge") as ei:
            eng.drain(max_steps=3)
    msg = str(ei.value)
    assert "health=" in msg and "queued rids=[0]" in msg


# -- poisoned-request quarantine ---------------------------------------

def test_quarantine_refuses_exactly_one_of_mixed_batch(served):
    """The acceptance bar: a single-slot fault in a 3-request mixed batch
    refuses exactly that request; the survivors resume and finish
    bitwise-equal to an uninterrupted run — all on ONE generate_step
    trace (the bisect's masked replays are traced-value changes)."""
    cfg, st, ctx = served
    cfgf = dataclasses.replace(cfg, name=cfg.name + "-sched-quar")
    eng = Engine(ctx.with_cfg(cfgf), st.params, n_slots=3, max_len=16)
    prompts = [p[:6] for p in _prompts(cfg, 3, seed=37)]
    for i, p in enumerate(prompts):
        eng.submit(Request(tokens=p, max_new=4, rid=i))
    engine_mod.TRACE_COUNTS.clear()
    # arm only until the quarantine fires, so the slot's next occupant
    # (a resumed survivor) decodes clean
    with FaultInjector().slot_fault(slot=1, nth=1):
        while not any(c.finished == "refused" for c in eng.completions):
            eng.step()
    eng.drain()
    assert engine_mod.TRACE_COUNTS["generate_step"] == 1, \
        dict(engine_mod.TRACE_COUNTS)
    by_rid = {c.rid: c for c in eng.completions}
    assert by_rid[1].finished == "refused"       # slot 1's tenant
    assert "poisoned" in by_rid[1].error
    assert FALLBACK_COUNTS["quarantine"] == 1
    for i in (0, 2):
        assert by_rid[i].finished == "max_new" and by_rid[i].resumed == 1
        np.testing.assert_array_equal(
            by_rid[i].tokens, _ref(st, cfg, ctx, prompts[i], 4,
                                   eng.pool.max_len),
            err_msg=f"survivor {i} diverged after quarantine resume")


def test_quarantine_after_exhausted_ladder(served):
    """Under ResilientEngine the fault must first exhaust the whole
    degradation ladder (it follows the request, not the kernel), and the
    resulting ServeRefused drives the same bisect."""
    cfg, st, _ = served
    reng = ResilientEngine(cfg, st, policy=ResiliencePolicy(max_retries=0))
    eng = reng.scheduler(n_slots=3, max_len=16)
    prompts = [p[:6] for p in _prompts(cfg, 3, seed=39)]
    for i, p in enumerate(prompts):
        eng.submit(Request(tokens=p, max_new=3, rid=i))
    with FaultInjector().slot_fault(slot=1, nth=1):
        while not any(c.finished == "refused" for c in eng.completions):
            eng.step()
    eng.drain()
    refused = [c for c in eng.completions if c.finished == "refused"]
    assert len(refused) == 1 and refused[0].rid == 1
    assert "ServeRefused" in refused[0].error
    assert FALLBACK_COUNTS["quarantine"] == 1
    survivors = [c for c in eng.completions if c.rid != 1]
    assert all(c.finished == "max_new" and c.resumed == 1
               for c in survivors)


def test_decode_fault_mid_mixed_batch_walks_ladder(served):
    """satellite: an in-graph decode_fault calibrated (via FaultProbe) to
    fire mid-decode of a 2-request mixed batch — the ladder re-traces
    unfused and the served outputs equal the clean run's bitwise; no
    request is refused, because the fallback rung genuinely recovers."""
    cfg, st, _ = served
    prompts = [p[:6] for p in _prompts(cfg, 2, seed=41)]

    def run(tag, nth):
        reng = ResilientEngine(
            dataclasses.replace(cfg, name=f"{cfg.name}-mid-{tag}"), st,
            policy=ResiliencePolicy(max_retries=0))
        eng = reng.scheduler(n_slots=2, max_len=16)
        for i, p in enumerate(prompts):
            eng.submit(Request(tokens=p, max_new=5, rid=i))
        with FaultInjector().decode_fault(nth=nth) as probe:
            eng.step()                  # both admitted; first mixed tick
            at_tick1 = probe.executions
            eng.drain()
        assert eng.health()["occupancy_max"] == 2
        return reng, at_tick1, {c.rid: c.tokens for c in eng.completions}

    # calibration: count fused executions up to the first mixed decode
    # tick on a clean run, then arm the fault just past that point
    _, at_tick1, clean = run("clean", nth=1 << 30)
    reng, _, faulty = run("fault", nth=at_tick1 + 1)
    assert reng.last_rung == "unfused"
    assert FALLBACK_COUNTS["unfused"] >= 1
    for rid in clean:
        np.testing.assert_array_equal(clean[rid], faulty[rid])


# -- sharded serving ---------------------------------------------------

@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs 8 devices (tier1-multidevice CI job)")
def test_scheduler_sharded_parity_8dev():
    """2×4 (data, model) mesh: the scheduler's generate_step traces under
    the mesh — the compressed matmuls take the shard-mapped fused path
    (dispatch probe) — and serving a request next to a co-tenant is
    bitwise-identical to serving it alone through the same pool.  (A
    mesh-less run is NOT the reference: cross-device reduction order
    changes the bf16 floats, so the invariance is asserted *within* the
    mesh, where both runs share one trace.)"""
    from repro.sharding import partition as PT
    cfg = get_config("llama3.2-1b").smoke
    params = LM.init_lm(jax.random.PRNGKey(0), cfg, jnp.float32)
    st = build_serve_params(
        params, CompressionPolicy(mode="compressed", min_weight_size=1024),
        model_shards=4)                    # tiles divide the model axis
    mesh = make_mesh((2, 4), ("data", "model"))
    specs = PT.make_param_specs(st.params, mesh,
                                PT.ShardingConfig(mode="serve"))
    sp = jax.device_put(st.params, PT.to_named(specs, mesh))
    lut = jax.device_put(
        st.lut, jax.NamedSharding(mesh, jax.sharding.PartitionSpec()))
    prompts = _prompts(cfg, 2, seed=17)

    cfgm = dataclasses.replace(cfg, name=cfg.name + "-sched-mesh")
    ctxm = ServeContext(cfg=cfgm, mesh=mesh, lut=lut)
    with mesh, PT.active_mesh(mesh):
        ops.DISPATCH_COUNTS.clear()
        solo = {}
        for i, p in enumerate(prompts):
            eng = Engine(ctxm, sp, n_slots=2, max_len=16)
            eng.submit(Request(tokens=p, max_new=4, rid=i))
            eng.drain()
            solo[i] = eng.completions[0].tokens
        assert any(k.endswith("fused_shard_map")
                   for k in ops.DISPATCH_COUNTS), dict(ops.DISPATCH_COUNTS)
        eng = Engine(ctxm, sp, n_slots=2, max_len=16)
        for i, p in enumerate(prompts):
            eng.submit(Request(tokens=p, max_new=4, rid=i))
        eng.drain()
    both = {c.rid: c.tokens for c in eng.completions}
    for i in range(2):
        np.testing.assert_array_equal(
            solo[i], both[i],
            err_msg=f"request {i} changed under co-tenancy on the mesh")


# -- satellite seams ---------------------------------------------------

def test_impl_enum_is_the_one_home():
    assert ops.Impl("unfused") is ops.Impl.UNFUSED
    assert ops.Impl.UNFUSED.value == "unfused"
    assert str(ops.Impl.UNFUSED) == "unfused"          # f-string safe
    assert f"x+{ops.Impl.MATERIALIZE}" == "x+materialize"
    assert ops.VALID_IMPLS == frozenset(i.value for i in ops.Impl)
    assert ops.DEFAULT_LADDER == ResiliencePolicy().ladder
    prev = ops._DEFAULT_IMPL
    try:
        ops.set_default_impl(ops.Impl.REF)
        assert ops._DEFAULT_IMPL == "ref"
        with pytest.raises(ValueError):
            ops.set_default_impl("warp-speed")
    finally:
        ops.set_default_impl(prev)
    from repro import kernels
    assert kernels.Impl is ops.Impl


def test_serve_context_deprecates_loose_kwargs(served):
    cfg, st, ctx = served
    toks = jnp.asarray(_prompts(cfg, 1, seed=19)[0][None, :])
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        via_ctx = generate(st.params, cfg, toks, ctx=ctx, max_new=3)
    with pytest.warns(DeprecationWarning, match="ServeContext"):
        via_kw = generate(st.params, cfg, toks, lut=st.lut, max_new=3)
    np.testing.assert_array_equal(np.asarray(via_ctx), np.asarray(via_kw))
