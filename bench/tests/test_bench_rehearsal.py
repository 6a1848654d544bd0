"""A whole run of a cell at smoke widths on the CPU, kernels in the Pallas
interpreter: the harness's look for a chip is skipped, so the run never
counts as a measurement.  The same run with the timed path broken
underneath must come out not correct, and so must the control (the
reference with 4-bit weights in the program's place).

The smoke cell's limit was set from its own readings (seeds 1-12, 0.4 s
windows, kernels in the interpreter): the program's widest gap read at
most 0.044, the control's at least 0.70 (seeds 1-3), so the limit is 0.12.
"""
import time

import numpy as np
import pytest

from bench import correctness, harness, spec, tables

ROOT = spec.BENCH / "tests" / "data" / "smoke"
CELL = "internlm2.tiny"
SEED = 102
LIMIT = 0.12
BENCH = {"end_to_end": [{"name": n} for n in (
    "tokens_per_s", "itl_p95_ms", "ttft_p95_ms", "peak_hbm_mib", "setup_s")],
    "per_layer": []}


@pytest.fixture(scope="module")
def interpret(tmp_path_factory):
    from repro.kernels import ops
    saved = (ops._DEFAULT_IMPL, tables.CACHE)
    ops.set_default_impl("pallas_interpret")
    tables.CACHE = tmp_path_factory.mktemp("tables")
    yield
    ops._DEFAULT_IMPL, tables.CACHE = saved


def _run(seed, monkeypatch=None):
    return harness.run(CELL, seed, 0.4, False, t_start=time.perf_counter(),
                       root=ROOT, require_tpu=False, bench_json=BENCH)


@pytest.fixture(scope="module")
def sound(interpret):
    return _run(SEED)


def test_rehearsal_is_correct_but_never_a_measurement(sound):
    r = sound["result"]
    assert r["correct"] is True
    assert r["checks"]["logit_gap"]["value"] <= LIMIT
    assert list(r) == ["correct", "attempted", "failed", "metrics",
                       "device", "checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {"tokens_per_s", "itl_p95_ms",
                                 "ttft_p95_ms", "peak_hbm_mib", "setup_s"}
    assert r["device"]["platform"] == "cpu"
    failed = {c[0]: c[1] for c in sound["checks"] if not c[2]}
    assert failed == {"kernels": {"interpret": failed["kernels"]["interpret"]}}
    assert not sound["valid"]


def test_control_is_not_correct(sound):
    c = spec.config("internlm2-smoke", ROOT)
    model = spec.model(c["model_type"])
    seqs, rows, served = correctness.served_rows(sound["picked"])
    ref = correctness.reference_logits(model, c, SEED, seqs, rows)
    ctl = np.asarray(correctness.reference_logits(model, c, SEED, seqs, rows,
                                                  bits=4))
    assert correctness.gaps(ref, ctl.argmax(1)).max() > LIMIT


def _alter(orig, vocab):
    def step(*a):
        pages, nxt = orig(*a)
        return pages, (nxt + 1) % vocab
    return step


def _unchanged(orig, vocab):
    def step(*a):
        _, nxt = orig(*a)
        return a[5], nxt                 # the pages it was given
    return step


def _half(orig, vocab):
    def step(*a):
        a = list(a)
        active = np.array(a[9])
        active[len(active) // 2:] = False    # half the slots write nothing
        a[9] = active
        return orig(*a)
    return step


@pytest.mark.parametrize("fault", [_alter, _unchanged, _half],
                         ids=["token_altered", "state_unchanged",
                              "half_batch_left_out"])
def test_a_broken_step_is_not_correct(interpret, monkeypatch, fault):
    from repro.serve import scheduler
    monkeypatch.setattr(scheduler, "_generate_step",
                        fault(scheduler._generate_step, 211))
    r = _run(SEED)["result"]
    assert r["correct"] is False
    assert r["checks"]["logit_gap"]["value"] > LIMIT
