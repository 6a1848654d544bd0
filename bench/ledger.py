"""What the client side saw, step by step, and the end-to-end metrics.

The harness drives ``Engine.step()`` itself; a step returns once its
tokens are on the host, so the end of a step is when its tokens reach the
clients.  A request admitted in step ``a`` has its first token (from its
prefill) and, since the same step's decode tick already includes its slot,
its second token at the end of step ``a``; then one token per step until
the step that completes it.  The scheduler admits in submission order, so
the engine's running ``admitted`` count says which requests entered in
each step.
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Dict, List, Optional

import numpy as np

OK_ENDINGS = ("eos", "max_new")


def p95(values) -> Optional[float]:
    """95th percentile by linear interpolation, over every sample."""
    return float(np.percentile(np.asarray(values, float), 95)) \
        if len(values) else None


@dataclasses.dataclass
class Rec:
    rid: int
    sent: float                 # host clock when the client sent it
    prompt_len: int
    max_new: int
    admit_step: Optional[int] = None
    finish_step: Optional[int] = None
    finished: Optional[str] = None
    completion: object = None

    def first_tokens(self) -> int:
        return min(self.max_new, 2)


class BookkeepingError(RuntimeError):
    """The engine did something the ledger's model of a step excludes."""


class Ledger:
    def __init__(self):
        self.recs: Dict[int, Rec] = {}
        self.step_end: Dict[int, float] = {}
        self._fifo = collections.deque()
        self._admitted = 0
        self.t0: Optional[float] = None
        self.first_window_step: Optional[int] = None
        self.last_window_step: Optional[int] = None

    # -- recording -------------------------------------------------------
    def sent(self, rid: int, t: float, prompt_len: int, max_new: int):
        self.recs[rid] = Rec(rid, t, prompt_len, max_new)
        self._fifo.append(rid)

    def open_window(self, t0: float, next_step: int):
        self.t0 = t0
        self.first_window_step = next_step

    def close_window(self):
        """Steps recorded from now on finish requests but are not
        measured."""
        self.last_window_step = max(self.step_end)

    def step_done(self, step: int, t_end: float, admitted_total: int,
                  completions) -> None:
        self.step_end[step] = t_end
        for c in completions:            # refused at its prefill: never
            if c.n_generated == 0 and c.rid in self._fifo:   # admitted
                self._fifo.remove(c.rid)
        for _ in range(admitted_total - self._admitted):
            self.recs[self._fifo.popleft()].admit_step = step
        self._admitted = admitted_total
        for c in completions:
            r = self.recs[c.rid]
            r.finish_step, r.finished, r.completion = \
                c.finished_step, c.finished, c
            if r.admit_step is None:
                if c.finished in OK_ENDINGS:
                    raise BookkeepingError(f"request {c.rid} finished "
                                           f"({c.finished}) unadmitted")
                continue
            want = r.first_tokens() + (c.finished_step - r.admit_step)
            if c.finished in OK_ENDINGS and c.n_generated != want:
                raise BookkeepingError(
                    f"request {c.rid}: {c.n_generated} tokens, the step "
                    f"model says {want}")

    # -- window metrics --------------------------------------------------
    def window_steps(self) -> List[int]:
        last = self.last_window_step
        return sorted(k for k in self.step_end
                      if k >= self.first_window_step
                      and (last is None or k <= last))

    def _prev_end(self, k: int) -> float:
        return self.t0 if k == self.first_window_step else self.step_end[k - 1]

    def summary(self) -> dict:
        steps = self.window_steps()
        if not steps:
            raise BookkeepingError("no step ran in the window")
        last = steps[-1]
        span = self.step_end[last] - self.t0
        tokens = collections.Counter()
        gaps, ttft = [], []
        prefill_tokens = 0
        for r in self.recs.values():
            a = r.admit_step
            if a is None:
                continue
            end = r.finish_step if r.finish_step is not None else last
            for k in range(a, min(end, last) + 1):
                if k < self.first_window_step:
                    continue
                tokens[k] += r.first_tokens() if k == a else 1
                if k > a:
                    gaps.append(self.step_end[k] - self._prev_end(k))
            if self.first_window_step <= a <= last:
                ttft.append(self.step_end[a] - r.sent)
                prefill_tokens += r.prompt_len
        in_window = [r for r in self.recs.values() if r.sent >= self.t0]
        done = [r for r in self.recs.values() if r.finished is not None
                and self.first_window_step <= r.finish_step <= last]
        n_tok = sum(tokens.values())
        return {
            "span_s": span, "steps": len(steps), "tokens": n_tok,
            "tokens_per_s": n_tok / span,
            "ttft_p95_ms": None if not ttft else 1e3 * p95(ttft),
            "itl_p95_ms": None if not gaps else 1e3 * p95(gaps),
            "ttft_samples": len(ttft), "itl_samples": len(gaps),
            "prefill_tokens": prefill_tokens, "prefills": len(ttft),
            "attempted": len(in_window),
            "failed": sum(r.finished not in OK_ENDINGS for r in done),
            "completed": len(done),
            "step_tokens": dict(tokens),
        }

    def decode_rows(self) -> Dict[int, int]:
        """Window step -> requests its decode tick advanced."""
        last = self.window_steps()[-1]
        rows = collections.Counter()
        for r in self.recs.values():
            if r.admit_step is None:
                continue
            end = r.finish_step if r.finish_step is not None else last
            for k in range(max(r.admit_step, self.first_window_step),
                           min(end, last) + 1):
                if r.max_new > 1:
                    rows[k] += 1
        return dict(rows)

    def window_prefills(self) -> List[int]:
        """Prompt lengths of the requests admitted in the window."""
        last = self.window_steps()[-1]
        return [r.prompt_len for r in self.recs.values()
                if r.admit_step is not None
                and self.first_window_step <= r.admit_step <= last]

    def context_lengths(self) -> List[int]:
        """For every token a decode tick produced in the window, the
        context it attended over (prompt + tokens before it)."""
        last = self.window_steps()[-1]
        out = []
        for r in self.recs.values():
            a = r.admit_step
            if a is None:
                continue
            end = r.finish_step if r.finish_step is not None else last
            for k in range(max(a, self.first_window_step),
                           min(end, last) + 1):
                # the decode tick of step k reads the prompt and every
                # token generated before this one: 1 at step a, then +1
                out.append(r.prompt_len + 1 + (k - a))
        return out
