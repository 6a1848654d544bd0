"""Fault-injection suite — integrity checking + the serving degradation
ladder, for one dense config (llama3.2-1b) and one MoE config
(deepseek-v2-lite-16b).

Proves, with seeded faults from ``repro.testing.FaultInjector``:
  * a single bit flip in any compressed plane (codes/literals/LUT) is
    detected by ``verify_serve_state`` with the offending leaf *named*;
  * structurally-invalid planes (out-of-range LUT index) are caught by
    the device-side invariant check;
  * the ``ResilientEngine`` ladder recovers an injected in-graph
    ``JaxRuntimeError`` by falling back fused → unfused (→ materialize),
    ticking ``FALLBACK_COUNTS`` per rung;
  * transient faults recover in place via bounded retry;
  * deadlines expire as ``DeadlineExceeded``; an exhausted ladder refuses
    with per-rung diagnostics;
  * a corrupt newest checkpoint falls back to the previous committed step.
"""
import dataclasses
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core import CompressionPolicy
from repro.core.integrity import (IntegrityError, check_invariants,
                                  verify_serve_state)
from repro.kernels import ops
from repro.serve import engine as engine_mod
from repro.serve import resilience
from repro.serve.engine import build_serve_params, generate
from repro.serve.resilience import (FALLBACK_COUNTS, DeadlineExceeded,
                                    ResilientEngine, ResiliencePolicy,
                                    ServeRefused)
from repro.testing import FaultInjector
from repro.train import checkpoint as ckpt

ARCHS = ["llama3.2-1b", "deepseek-v2-lite-16b"]


@pytest.fixture(scope="module", params=ARCHS)
def served(request):
    """(cfg, ServeState, tokens, reference greedy output) per arch."""
    from repro.models import lm as LM
    cfg = get_config(request.param).smoke
    params = LM.init_lm(jax.random.PRNGKey(0), cfg, jnp.float32)
    st = build_serve_params(
        params, CompressionPolicy(mode="compressed", min_weight_size=1024))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                              cfg.vocab_size)
    ref = np.asarray(generate(st.params, cfg, toks, lut=st.lut, max_new=4))
    return cfg, st, toks, ref


# -- artifact integrity ------------------------------------------------

def test_manifest_built_and_verifies(served):
    cfg, st, _, _ = served
    assert st.manifest is not None and st.manifest["leaves"]
    assert st.manifest["total_bytes"] > 0
    for level in ("fast", "full"):
        rep = verify_serve_state(st, level=level)
        assert rep.ok, rep.corrupt
        assert rep.checked > 0
    assert verify_serve_state(st, level="off").ok


def test_bitflip_in_codes_detected_and_named(served):
    cfg, st, _, _ = served
    inj = FaultInjector()
    bad, name = inj.flip_bit(st, "", plane="codes")
    rep = verify_serve_state(bad, level="full")
    assert not rep.ok
    assert name in rep.quarantined
    # the clean state still verifies (flip_bit copied)
    assert verify_serve_state(st, level="full").ok


def test_bitflip_in_literals_detected(served):
    cfg, st, _, _ = served
    inj = FaultInjector()
    bad, name = inj.flip_bit(st, "", plane="literals")
    rep = verify_serve_state(bad, level="full")
    assert not rep.ok and name in rep.quarantined


def test_lut_bitflip_detected(served):
    cfg, st, _, _ = served
    inj = FaultInjector()
    bad = inj.flip_lut_bit(st)
    rep = verify_serve_state(bad, level="full")
    assert not rep.ok
    assert any(plane == "lut" for _, plane, _ in rep.corrupt)


def test_invariant_check_catches_out_of_range_code(served):
    cfg, st, _, _ = served
    n_rows = st.lut.shape[0]
    if n_rows >= (1 << 16) - 1:
        pytest.skip("LUT fills the uint16 code space")
    flat, treedef = jax.tree_util.tree_flatten_with_path(st.params)
    leaves = [leaf for _, leaf in flat]
    idx = next(i for i, (p, _) in enumerate(flat)
               if jax.tree_util.keystr(p).endswith(".codes"))
    arr = np.asarray(jax.device_get(leaves[idx])).copy()
    arr.reshape(-1)[0] = n_rows            # indexes past the LUT, not ESCAPE
    leaves[idx] = jnp.asarray(arr)
    bad = dataclasses.replace(st, params=treedef.unflatten(leaves))
    rep = check_invariants(bad)
    assert not rep.ok and rep.quarantined
    assert check_invariants(st).ok


def test_engine_integrity_gate_refuses_corrupt_artifact(served):
    cfg, st, _, _ = served
    inj = FaultInjector()
    bad, name = inj.flip_bit(st, "", plane="codes")
    with pytest.raises(IntegrityError) as ei:
        ResilientEngine(cfg, bad, policy=ResiliencePolicy(verify="full"))
    assert name in ei.value.report.quarantined
    assert FALLBACK_COUNTS["integrity_refused"] == 1


# -- degradation ladder ------------------------------------------------

def test_ladder_falls_back_to_unfused_on_ingraph_fault(served):
    """A persistent fault inside the fused decode kernel's jitted program
    surfaces as JaxRuntimeError; the ladder re-traces on the unfused rung
    and returns output identical to the clean fused run."""
    cfg, st, toks, ref = served
    cfgf = dataclasses.replace(cfg, name=cfg.name + "-rl-ladder")
    eng = ResilientEngine(cfgf, st,
                          policy=ResiliencePolicy(max_retries=0,
                                                  verify="fast"))
    inj = FaultInjector()
    ops.DISPATCH_COUNTS.clear()
    with inj.decode_fault(nth=1):
        out = eng.generate(toks, max_new=4)
    np.testing.assert_array_equal(np.asarray(out), ref)
    assert eng.last_rung == "unfused"
    assert FALLBACK_COUNTS["unfused"] == 1
    assert "materialize" not in FALLBACK_COUNTS
    assert any(k.startswith("unfused") or k.startswith("tiled_unfused")
               or k.startswith("grouped_unfused")
               for k in ops.DISPATCH_COUNTS)
    h = eng.health()
    assert h["last_rung"] == "unfused" and h["recent_errors"]


def test_ladder_walks_every_rung_then_succeeds(served):
    """Seam faults on the first two rungs push the request down to
    materialize; FALLBACK_COUNTS records each rung entry."""
    cfg, st, toks, ref = served
    cfgf = dataclasses.replace(cfg, name=cfg.name + "-rl-allrungs")
    eng = ResilientEngine(cfgf, st,
                          policy=ResiliencePolicy(max_retries=0))
    inj = FaultInjector()
    orig = resilience._generate
    resilience._generate = inj.failing(orig, times=2)
    try:
        out = eng.generate(toks, max_new=4)
    finally:
        resilience._generate = orig
    assert np.asarray(out).shape == ref.shape
    assert eng.last_rung == "materialize"
    assert FALLBACK_COUNTS["unfused"] == 1
    assert FALLBACK_COUNTS["materialize"] == 1
    assert len(eng.health()["recent_errors"]) == 2


def test_transient_fault_recovers_by_retry(served):
    """One-shot fault at the request seam: bounded retry recovers on the
    fused rung itself — no fallback, output equals the clean run."""
    cfg, st, toks, ref = served
    eng = ResilientEngine(cfg, st, policy=ResiliencePolicy(max_retries=1))
    inj = FaultInjector()
    orig = resilience._generate
    resilience._generate = inj.failing(orig, times=1)
    try:
        out = eng.generate(toks, max_new=4)
    finally:
        resilience._generate = orig
    np.testing.assert_array_equal(np.asarray(out), ref)
    assert eng.last_rung == "fused"
    assert FALLBACK_COUNTS["retry:fused"] == 1
    assert "unfused" not in FALLBACK_COUNTS


def test_ladder_exhausted_refuses_with_diagnostics(served):
    cfg, st, toks, _ = served
    eng = ResilientEngine(
        cfg, st, policy=ResiliencePolicy(max_retries=1, ladder=("fused",)))
    inj = FaultInjector()
    orig = resilience._generate
    resilience._generate = inj.failing(orig, times=10)
    try:
        with pytest.raises(ServeRefused) as ei:
            eng.generate(toks, max_new=4)
    finally:
        resilience._generate = orig
    assert FALLBACK_COUNTS["refused"] == 1
    assert FALLBACK_COUNTS["retry:fused"] == 1
    assert len(ei.value.errors) == 2          # 1 try + 1 retry, one rung
    assert all(r == "fused" for r, _, _ in ei.value.errors)



def test_health_reports_the_newest_eight_failures(served):
    """The failure history is bounded: a walk of 12 failed attempts
    leaves the newest 8 in ``health()['recent_errors']``."""
    cfg, st, toks, _ = served
    eng = ResilientEngine(
        cfg, st, policy=ResiliencePolicy(max_retries=11, ladder=("fused",)))
    inj = FaultInjector()
    orig = resilience._generate
    resilience._generate = inj.failing(orig, times=12)
    try:
        with pytest.raises(ServeRefused) as ei:
            eng.generate(toks, max_new=4)
    finally:
        resilience._generate = orig
    assert len(ei.value.errors) == 12
    assert eng.health()["recent_errors"] == ei.value.errors[-8:]

def test_deadline_expires_mid_ladder(served):
    cfg, st, toks, _ = served
    eng = ResilientEngine(
        cfg, st, policy=ResiliencePolicy(max_retries=3, deadline_s=0.05))
    inj = FaultInjector()

    def slow_fail(*a, **kw):
        time.sleep(0.06)
        raise jax.errors.JaxRuntimeError("injected slow fault")

    orig = resilience._generate
    resilience._generate = slow_fail
    try:
        with pytest.raises(DeadlineExceeded):
            eng.generate(toks, max_new=4)
    finally:
        resilience._generate = orig
    assert FALLBACK_COUNTS["deadline"] == 1
    assert FALLBACK_COUNTS["refused"] == 0


# -- checkpoint damage -------------------------------------------------

def _tiny_tree():
    return {"w": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": jnp.ones((3,), jnp.float32)}


def test_restore_latest_falls_back_past_truncated_step(tmp_path):
    d = str(tmp_path / "ck")
    tree = _tiny_tree()
    ckpt.save(d, 3, tree)
    ckpt.save(d, 9, jax.tree_util.tree_map(lambda x: x * 2, tree))
    inj = FaultInjector()
    inj.truncate_step(d, 9)                   # unreadable archive
    skipped = []
    state, step = ckpt.restore_latest(
        d, jax.tree_util.tree_map(jnp.zeros_like, tree),
        on_skip=lambda s, e: skipped.append(s))
    assert step == 3 and skipped == [9]
    np.testing.assert_array_equal(np.asarray(state["w"]),
                                  np.asarray(tree["w"]))


def test_restore_latest_falls_back_past_bitrot(tmp_path):
    """Readable archive, flipped payload bits — only the checksum layer
    catches this one."""
    d = str(tmp_path / "ck")
    tree = _tiny_tree()
    ckpt.save(d, 1, tree)
    ckpt.save(d, 2, jax.tree_util.tree_map(lambda x: x + 1, tree))
    inj = FaultInjector()
    inj.corrupt_step(d, 2, nbits=32)
    state, step = ckpt.restore_latest(
        d, jax.tree_util.tree_map(jnp.zeros_like, tree))
    assert step == 1
    np.testing.assert_array_equal(np.asarray(state["b"]),
                                  np.asarray(tree["b"]))


def test_restore_latest_skips_uncommitted_newest(tmp_path):
    d = str(tmp_path / "ck")
    tree = _tiny_tree()
    ckpt.save(d, 5, tree)
    ckpt.save(d, 8, tree)
    FaultInjector().uncommit_step(d, 8)  # torn write
    _, step = ckpt.restore_latest(
        d, jax.tree_util.tree_map(jnp.zeros_like, tree))
    assert step == 5


def test_restore_latest_raises_when_nothing_loadable(tmp_path):
    d = str(tmp_path / "ck")
    tree = _tiny_tree()
    ckpt.save(d, 4, tree)
    FaultInjector().truncate_step(d, 4)
    with pytest.raises(FileNotFoundError):
        ckpt.restore_latest(d, jax.tree_util.tree_map(jnp.zeros_like, tree))


# -- tiered residency under fault --------------------------------------

def test_fetch_fault_miss_storm_refuses_never_hangs(served):
    """A dead host→HBM transfer link under tiered residency turns every
    cache miss into a ladder-walked fault: the miss-storm must surface
    as a refused request (quarantine → finished='refused') within a
    bounded drain — never a hang or an unaccounted drop."""
    cfg, st, _, _ = served
    if cfg.family != "moe":
        pytest.skip("tiered residency backs MoE expert planes only")
    from repro.serve.residency import RESIDENCY_COUNTS, ResidencyManager
    from repro.serve.scheduler import Request
    mgr = ResidencyManager(st, cfg, capacity=1, prefetch=False)
    reng = ResilientEngine(cfg, st, residency=mgr)
    eng = reng.scheduler(n_slots=2, max_len=24, page_size=8)
    toks = np.arange(1, 7, dtype=np.int32) % cfg.vocab_size
    with FaultInjector().fetch_fault(times=1 << 30) as probe:
        eng.submit(Request(tokens=toks, max_new=4, rid=0))
        done = eng.drain(max_steps=500)
    assert done and all(c.finished == "refused" for c in done)
    assert probe.executions > 0
    assert FALLBACK_COUNTS["refused"] >= 1


def test_fetch_fault_transient_recovers_bitwise(served):
    """A transient transfer fault (first fetch only) retries up the
    ladder and the request still completes bitwise-equal to the
    fully-resident reference — fetch faults are recoverable faults,
    not corruption."""
    cfg, st, toks, ref = served
    if cfg.family != "moe":
        pytest.skip("tiered residency backs MoE expert planes only")
    from repro.serve.residency import ResidencyManager
    mgr = ResidencyManager(st, cfg, capacity=cfg.n_experts, prefetch=False)
    reng = ResilientEngine(cfg, st, residency=mgr)
    with FaultInjector().fetch_fault(times=1) as probe:
        out = np.asarray(reng.generate(toks, max_new=4))
    assert probe.executions == 1
    assert np.array_equal(out, ref)
