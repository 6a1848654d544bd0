"""What the serving thread was doing while the device idled, from the
program's own profiler spans.

The scheduler and the resilience guard open ``serve.*`` and ``guard.*``
spans (``jax.profiler.TraceAnnotation``) on the thread that steps the
engine.  Each instant of the ``bench.window`` span in which the first
chip runs no operation (what ``device.idle_share`` counts) is put under
the innermost program span open on that thread at that instant:

  * ``guard.wait`` (the host in ``block_until_ready``), split at the end
    of the last ``XLA Modules`` program that started inside the enclosing
    ``guard.call``: before it, the chip waited for its program to start
    (launch lag); after it, the program had ended and the host was not yet
    told (notify lag);
  * any other program span: the host preparing inputs, dispatching,
    inserting, sampling or retiring;
  * no program span at all: the harness's own code between steps, counted
    by none of the three.

A trace with no program spans (a program that opens none) reads None.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import os
import statistics
from typing import List, Optional

from bench import harness
from bench import trace_reduce as TR

PREFIXES = ("serve.", "guard.")
CALL, WAIT, ADMIT = "guard.call", "guard.wait", "serve.admit"


@dataclasses.dataclass
class Split:
    window_s: float
    idle_host_s: float          # idle under a program span other than wait
    launch_lag_s: float         # idle in guard.wait before its program ends
    notify_lag_s: float         # idle in guard.wait after its program ends
    admit_s: List[float]        # serve.admit spans inside the window

    def share(self, seconds: float) -> float:
        return 100.0 * seconds / self.window_s

    def admit_ms(self) -> Optional[float]:
        if not self.admit_s:
            return None
        return 1e3 * statistics.median(self.admit_s)


def _innermost(spans) -> list:
    """[(start, end, span)]: the line cut where the innermost open span
    changes, for spans that nest (one thread's)."""
    segs, stack, t = [], [], -math.inf

    def close_to(x):
        nonlocal t
        while stack and stack[-1].end <= x:
            top = stack.pop()
            if top.end > t:
                segs.append((t, top.end, top))
            t = max(t, top.end)
        if stack and x > t:
            segs.append((t, x, stack[-1]))
        t = max(t, x)

    for sp in spans:
        close_to(sp.start)
        stack.append(sp)
    close_to(math.inf)
    return segs


def _program_end(wait, calls, starts_ends) -> float:
    """End of the last program that starts inside the ``guard.call``
    around ``wait``; +inf where none does (the chip never started it)."""
    around = [c for c in calls if c.start <= wait.start and wait.end <= c.end]
    if not around:
        return math.inf
    call = min(around, key=lambda c: c.end - c.start)
    ends = [e for s, e in starts_ends if call.start <= s <= call.end]
    return max(ends) if ends else math.inf


def split(raw: TR.Raw) -> Optional[Split]:
    window, thread = None, None
    for name, evs in raw.host.items():
        for e in evs:
            if e.name == TR.WINDOW:
                window, thread = (e.start, e.end), name
    if window is None or not raw.ops:
        return None
    spans = sorted((e for e in raw.host[thread]
                    if e.name.startswith(PREFIXES)),
                   key=lambda e: (e.start, -e.end))
    if not spans:
        return None
    lo, hi = window
    first = sorted(raw.ops)[0]
    busy = TR.union((max(e.start, lo), min(e.end, hi))
                    for e in raw.ops[first]
                    if min(e.end, hi) > max(e.start, lo))
    idle, t = [], lo
    for s, e in busy + [(hi, hi)]:
        if s > t:
            idle.append((t, s))
        t = max(t, e)
    programs = [(e.start, e.end) for e in raw.modules.get(first, [])]
    calls = [e for e in spans if e.name == CALL]
    lag_end = {id(w): _program_end(w, calls, programs)
               for w in spans if w.name == WAIT}

    host = launch = notify = 0.0
    segs = _innermost(spans)
    j = 0
    for a, b in idle:
        while j < len(segs) and segs[j][1] <= a:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < b:
            s, e, sp = segs[k]
            s, e = max(s, a), min(e, b)
            if sp.name == WAIT:
                end = lag_end[id(sp)]
                launch += max(0.0, min(e, end) - s)
                notify += max(0.0, e - max(s, end))
            else:
                host += e - s
            k += 1
    admit = [(e.end - e.start) / 1e9 for e in spans
             if e.name == ADMIT and lo <= e.start and e.end <= hi]
    return Split(window_s=(hi - lo) / 1e9, idle_host_s=host / 1e9,
                 launch_lag_s=launch / 1e9, notify_lag_s=notify / 1e9,
                 admit_s=admit)


@functools.lru_cache(maxsize=2)
def _of_file(path: str, mtime: float) -> Optional[Split]:
    return split(TR.load(path))


def of_run(ctx) -> Optional[Split]:
    """The split of the traced run's profile, read once per file."""
    if ctx.trace is None:
        return None
    try:
        path = TR.find_xspace(harness.TRACE_DIR)
    except FileNotFoundError:
        return None
    return _of_file(path, os.path.getmtime(path))
