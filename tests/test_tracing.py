"""Profiler spans of the serving path.

``Engine.step`` and the resilience guard open ``jax.profiler``
``TraceAnnotation`` spans around each part of a step, so that a profile
can put each idle gap of the device under what the serving thread was
doing.  These tests take a profile of a small ``ResilientEngine``
scheduler (two admissions, a few decode ticks) and check the spans'
nesting and arguments, and that taking a profile changes no token.
"""
import contextlib
import dataclasses
import glob
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.profiler import ProfileData

from repro.configs import get_config
from repro.core import CompressionPolicy
from repro.models import lm as LM
from repro.serve.engine import build_serve_params
from repro.serve.resilience import ResiliencePolicy, ResilientEngine
from repro.serve.scheduler import Request
from repro.testing import FaultInjector

PREFIXES = ("serve.", "guard.")


@dataclasses.dataclass
class Span:
    name: str
    start: float
    end: float
    args: dict

    def holds(self, other: "Span") -> bool:
        return self.start <= other.start and other.end <= self.end


def _spans(trace_dir) -> list:
    """The program's spans, on the thread that ran the engine."""
    [path] = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                       recursive=True)
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out = [Span(e.name, e.start_ns, e.start_ns + e.duration_ns,
                        dict(e.stats))
                   for e in line.events if e.name.startswith(PREFIXES)]
            if any(s.name == "serve.step" for s in out):
                return sorted(out, key=lambda s: (s.start, -s.end))
    raise AssertionError(f"no serve.step span in {path}")


def _parent(spans, child) -> Span:
    """The innermost program span that holds ``child``."""
    around = [s for s in spans if s is not child and s.holds(child)]
    assert around, f"{child.name} lies in no program span"
    return min(around, key=lambda s: s.end - s.start)


def _prompts(cfg, n, seed):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab_size, 6 + 2 * i).astype(np.int32)
            for i in range(n)]


def _serve(cfg, st, prompts, *, policy=None,
           inject=contextlib.nullcontext()):
    """Two requests, the second submitted after the first step; returns
    {rid: tokens}."""
    eng = ResilientEngine(cfg, st, policy=policy).scheduler(n_slots=2,
                                                            max_len=24)
    with inject:
        eng.submit(Request(tokens=prompts[0], max_new=4, rid=7))
        eng.step()
        eng.submit(Request(tokens=prompts[1], max_new=3, rid=9))
        eng.drain()
    return {c.rid: c.tokens for c in eng.completions}


@pytest.fixture(scope="module")
def served():
    cfg = get_config("llama3.2-1b").smoke
    params = LM.init_lm(jax.random.PRNGKey(0), cfg, jnp.float32)
    st = build_serve_params(
        params, CompressionPolicy(mode="compressed", min_weight_size=1024))
    return cfg, st, _prompts(cfg, 2, seed=5)


@pytest.fixture(scope="module")
def traced(served, tmp_path_factory):
    """(spans, tokens untraced, tokens traced) of one clean run."""
    cfg, st, prompts = served
    untraced = _serve(cfg, st, prompts)
    d = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(d)):
        tokens = _serve(cfg, st, prompts)
    return _spans(d), untraced, tokens


# (child span, the kind of its guard.call or None, innermost parent)
NESTING = [
    ("serve.admit", None, "serve.step"),
    ("serve.prefill", None, "serve.admit"),
    ("serve.insert", None, "serve.admit"),
    ("guard.call", "prefill", "serve.prefill"),
    ("serve.decode", None, "serve.step"),
    ("serve.decode.inputs", None, "serve.decode"),
    ("guard.call", "decode", "serve.decode"),
    ("serve.decode.retire", None, "serve.decode"),
    ("guard.dispatch", None, "guard.call"),
    ("guard.wait", None, "guard.call"),
    ("guard.effects", None, "guard.call"),
]


@pytest.mark.parametrize("child,kind,parent", NESTING,
                         ids=[f"{c}[{k}]" if k else c
                              for c, k, _ in NESTING])
def test_span_nesting(traced, child, kind, parent):
    spans, _, _ = traced
    found = [s for s in spans if s.name == child
             and (kind is None or s.args.get("kind") == kind)]
    assert found, f"no {child} span"
    for s in found:
        assert _parent(spans, s).name == parent


def test_decode_parts_in_order(traced):
    """Inputs, then the guarded call, then retirement, in every tick."""
    spans, _, _ = traced
    ticks = [s for s in spans if s.name == "serve.decode"]
    assert len(ticks) >= 3
    for tick in ticks:
        parts = [s.name for s in spans
                 if tick.holds(s) and s is not tick
                 and _parent(spans, s) is tick]
        assert parts == ["serve.decode.inputs", "guard.call",
                         "serve.decode.retire"]
        assert tick.args["rows"] in (1, 2)


def test_guard_parts_in_order(traced):
    spans, _, _ = traced
    for call in (s for s in spans if s.name == "guard.call"):
        parts = [s.name for s in spans
                 if call.holds(s) and s is not call]
        assert parts == ["guard.dispatch", "guard.wait", "guard.effects"]


def test_span_arguments(traced):
    spans, _, _ = traced
    steps = [s.args["step"] for s in spans if s.name == "serve.step"]
    assert steps == list(range(len(steps)))
    admits = [s.args for s in spans if s.name == "serve.admit"]
    assert [(a["rid"], a["prompt_len"], a["resume"]) for a in admits] == \
        [(7, 6, 0), (9, 8, 0)]
    calls = [s.args for s in spans if s.name == "guard.call"]
    assert {a["kind"] for a in calls} == {"prefill", "decode"}
    assert sum(a["kind"] == "prefill" for a in calls) == 2
    assert all(a["rung"] == "fused" and a["attempt"] == 0 for a in calls)


def test_tracing_changes_no_token(traced):
    _, untraced, tokens = traced
    assert untraced.keys() == tokens.keys() == {7, 9}
    for rid in untraced:
        np.testing.assert_array_equal(untraced[rid], tokens[rid])


def test_ladder_attempts_are_spans(served, traced, tmp_path):
    """A persistent fused-kernel fault: every guarded call holds a failed
    ``guard.call`` on the fused rung, then one on the unfused rung, and
    the tokens equal a clean run's."""
    cfg, st, prompts = served
    _, clean, _ = traced
    # a fresh name: the injected fault is traced into the program
    cfg = dataclasses.replace(cfg, name=cfg.name + "-trace-ladder")
    with jax.profiler.trace(str(tmp_path)):
        faulty = _serve(cfg, st, prompts,
                        policy=ResiliencePolicy(max_retries=0),
                        inject=FaultInjector().decode_fault(nth=1))
    spans = _spans(tmp_path)
    calls = [s for s in spans if s.name == "guard.call"]
    for kind in ("prefill", "decode"):
        walk = [(s.args["rung"], s.args["attempt"]) for s in calls
                if s.args["kind"] == kind]
        assert walk and walk == [("fused", 0), ("unfused", 0)] * (
            len(walk) // 2)
    for call in calls:
        parts = [s.name for s in spans if call.holds(s) and s is not call]
        if call.args["rung"] == "fused":       # failed: no barrier ran
            assert parts[0] == "guard.dispatch"
            assert "guard.effects" not in parts
        else:
            assert parts == ["guard.dispatch", "guard.wait",
                             "guard.effects"]
    for rid in clean:
        np.testing.assert_array_equal(clean[rid], faulty[rid])
