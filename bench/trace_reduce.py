"""From a profiler trace (``.xplane.pb``) to device busy time, time per
program and per operation, and idle gaps named by what the host did.

On a TPU each device plane (``/device:TPU:<n>``) has an ``XLA Modules``
line, one event per run of a jitted program (``jit_prefill(<id>)``), and
an ``XLA Ops`` line whose events nest: a ``while`` loop spans the ops of
its body.  Op events are named by their HLO text (``%name = type op(...)``);
a Pallas kernel's op takes the kernel's name (``fused_decode_matmul.6``).
Host spans come from ``jax.profiler.TraceAnnotation``: the benchmark wraps
its measured window in ``bench.window`` and its calls into the engine in
spans of their own, and the runtime and the Python tracer add theirs.
Host and device events share one clock in the trace.  Everything is
clipped to the ``bench.window`` span.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import gzip
import os
import pathlib
import re
from typing import Dict, List, Tuple

WINDOW = "bench.window"
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"


@dataclasses.dataclass
class Event:
    name: str
    start: float            # ns
    end: float              # ns


def short_name(name: str) -> str:
    """``%fused_decode_matmul.6 = bf16[...] custom-call(...)`` ->
    ``fused_decode_matmul.6``; ``jit_prefill(123)`` -> ``jit_prefill``."""
    name = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\(\d+\)$", "", name)


def _events(line) -> List[Event]:
    return [Event(e.name, float(e.start_ns),
                  float(e.start_ns) + float(e.duration_ns))
            for e in line.events]


def find_xspace(directory) -> str:
    paths = sorted(glob.glob(os.path.join(str(directory), "**",
                                          "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return paths[-1]


@dataclasses.dataclass
class Raw:
    ops: Dict[str, List[Event]]              # device plane -> op events
    modules: Dict[str, List[Event]]          # device plane -> programs
    host: Dict[str, List[Event]]             # host thread line -> spans


def load(path) -> Raw:
    """A trace from an ``.xplane.pb`` file, gzipped or not."""
    from jax.profiler import ProfileData
    data = pathlib.Path(path).read_bytes()
    if str(path).endswith(".gz"):
        data = gzip.decompress(data)
    pd = ProfileData.from_serialized_xspace(data)
    ops, modules, host = {}, {}, {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OP_LINE:
                    ops[plane.name] = _events(line)
                elif line.name == MODULE_LINE:
                    modules[plane.name] = _events(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host[f"{plane.name}/{line.name}"] = _events(line)
    ops = {k: v for k, v in ops.items() if v}
    return Raw(ops, modules, host)


def union(intervals) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(events, lo, hi) -> List[Event]:
    out = []
    for e in events:
        s, t = max(e.start, lo), min(e.end, hi)
        if t > s:
            out.append(Event(e.name, s, t))
    return out


def self_times(events: List[Event]) -> Dict[str, float]:
    """Seconds per short op name, each op less the ops nested in it."""
    out: Dict[str, float] = collections.Counter()
    stack: List[List] = []          # [event, child seconds]

    def close(item):
        ev, child = item
        out[short_name(ev.name)] += (ev.end - ev.start) / 1e9 - child
        if stack:
            stack[-1][1] += (ev.end - ev.start) / 1e9

    for e in sorted(events, key=lambda e: (e.start, -e.end)):
        while stack and stack[-1][0].end <= e.start:
            close(stack.pop())
        stack.append([e, 0.0])
    while stack:
        close(stack.pop())
    return dict(out)


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                      # mean over the chips that ran ops
    chips: int
    op_s: Dict[str, float]             # op -> self seconds, first chip
    program_s: Dict[str, float]        # program -> seconds, first chip
    gaps: List[Tuple[str, float]]      # (host span, seconds), longest first
    op_events: List[Event]             # clipped ops of the first chip

    def time_matching(self, pattern: str) -> float:
        """Seconds of the first chip's ops whose short name matches
        ``pattern`` (a regular expression, matched in full)."""
        rx = re.compile(pattern)
        return sum(e.end - e.start for e in self.op_events
                   if rx.fullmatch(short_name(e.name))) / 1e9

    def breakdown(self, n: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.gaps[:n]]}


def _host_label(host_events: List[Event], t: float) -> str:
    """The innermost host span around ``t`` (the shortest that covers
    it), else the window itself."""
    best = None
    for e in host_events:
        if e.start <= t <= e.end and e.name != WINDOW:
            if best is None or (e.end - e.start) < (best.end - best.start):
                best = e
    return best.name if best is not None else WINDOW


def reduce(raw: Raw) -> Summary:
    window, thread = None, None
    for name, evs in raw.host.items():
        for e in evs:
            if e.name == WINDOW:
                window, thread = (e.start, e.end), name
    if window is None:
        raise ValueError(f"no {WINDOW!r} span in the trace")
    if not raw.ops:
        raise ValueError("no device ran an operation in the trace")
    lo, hi = window
    busy = []
    for plane in sorted(raw.ops):
        merged = union((e.start, e.end) for e in _clip(raw.ops[plane], lo, hi))
        busy.append((plane, merged, sum(e - s for s, e in merged) / 1e9))
    first, merged, _ = busy[0]
    ops = _clip(raw.ops[first], lo, hi)
    program_s: Dict[str, float] = collections.Counter()
    for e in _clip(raw.modules.get(first, []), lo, hi):
        program_s[short_name(e.name)] += (e.end - e.start) / 1e9
    gaps, t = [], lo
    host_events = raw.host[thread]
    for s, e in merged + [(hi, hi)]:
        if s > t:
            gaps.append((_host_label(host_events, (s + t) / 2),
                         (s - t) / 1e9))
        t = max(t, e)
    gaps.sort(key=lambda g: -g[1])
    return Summary(window_s=(hi - lo) / 1e9,
                   busy_s=sum(b for _, _, b in busy) / len(busy),
                   chips=len(busy), op_s=self_times(ops),
                   program_s=dict(program_s), gaps=gaps, op_events=ops)
