"""The window's arithmetic: rates and tails over every sample."""
import dataclasses

import numpy as np
import pytest

from bench.ledger import BookkeepingError, Ledger, p95


@dataclasses.dataclass
class C:
    rid: int
    finished_step: int
    n_generated: int
    finished: str = "max_new"


def test_p95_over_all_samples():
    vals = list(range(1, 101))
    assert p95(vals) == pytest.approx(np.percentile(vals, 95))
    assert p95([]) is None


def _run():
    """Two requests: r0 admitted in the pre-window step 0 (max_new 4),
    r1 sent at the end of step 2 and admitted in step 3 (max_new 3)."""
    led = Ledger()
    led.sent(0, 0.0, prompt_len=10, max_new=4)
    led.step_done(0, 1.0, admitted_total=1, completions=[])    # r0: 2 tokens
    led.open_window(1.0, next_step=1)
    led.step_done(1, 1.5, 1, [])                               # r0: 3
    led.step_done(2, 2.5, 1, [C(0, 2, 4)])                     # r0: 4, done
    led.sent(1, 2.5, prompt_len=5, max_new=3)
    led.step_done(3, 3.0, 2, [])                               # r1: 2
    led.step_done(4, 4.0, 2, [C(1, 4, 3)])                     # r1: 3, done
    return led


def test_rates_and_tails():
    s = _run().summary()
    assert s["span_s"] == pytest.approx(3.0)
    assert s["tokens"] == 1 + 1 + 2 + 1
    assert s["tokens_per_s"] == pytest.approx(5 / 3.0)
    # gaps: r0 at steps 1, 2 (0.5, 1.0); r1 at step 4 (1.0)
    assert s["itl_samples"] == 3
    assert s["itl_p95_ms"] == pytest.approx(1e3 * p95([0.5, 1.0, 1.0]))
    # only r1's first token came in the window: sent 2.5, first at 3.0
    assert s["ttft_samples"] == 1
    assert s["ttft_p95_ms"] == pytest.approx(500.0)
    assert s["attempted"] == 1 and s["failed"] == 0 and s["completed"] == 2


def test_window_accounting_for_the_cost_functions():
    led = _run()
    assert led.window_prefills() == [5]
    assert led.decode_rows() == {1: 1, 2: 1, 3: 1, 4: 1}
    # r0 decodes at steps 1, 2 with contexts 10+1+1, 10+1+2;
    # r1 at steps 3, 4 with 5+1, 5+2
    assert sorted(led.context_lengths()) == [6, 7, 12, 13]


def test_failed_requests_count():
    led = Ledger()
    led.sent(0, 0.0, 4, 8)
    led.open_window(0.0, 0)
    led.step_done(0, 1.0, 1, [C(0, 0, 1, finished="refused")])
    s = led.summary()
    assert s["failed"] == 1 and s["attempted"] == 1


def test_a_step_model_violation_raises():
    led = Ledger()
    led.sent(0, 0.0, 4, 8)
    led.open_window(0.0, 0)
    with pytest.raises(BookkeepingError):
        led.step_done(0, 1.0, 1, [C(0, 0, 5)])     # 5 tokens in 1 step


def test_a_request_refused_at_its_prefill_is_failed_not_admitted():
    led = Ledger()
    led.sent(0, 0.0, 4, 8)
    led.sent(1, 0.0, 4, 8)
    led.open_window(0.0, 0)
    # request 0's prefill is refused; request 1 is admitted in the same step
    led.step_done(0, 1.0, 1, [C(0, 0, 0, finished="refused")])
    assert led.recs[0].admit_step is None and led.recs[1].admit_step == 0
    s = led.summary()
    assert s["failed"] == 1 and s["tokens"] == 2


def test_steps_after_the_close_are_not_measured():
    """Steps run after the window only finish requests for the
    comparison: no token, gap or completion of theirs is counted."""
    led = _run()
    before = led.summary()
    led.close_window()
    led.step_done(5, 9.0, 2, [])
    assert led.window_steps() == [1, 2, 3, 4]
    after = led.summary()
    assert after == before
