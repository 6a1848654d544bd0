"""Continuous-batching scheduler — the request-level serving API.

The one-shot ``engine.generate`` runs a fixed batch through a single
``lax.scan``: no request can join or leave a running decode loop, so real
traffic (staggered arrivals, varied lengths) serializes.  This module is
the serving front door built on the prefill→insert→generate-step split:

  * ``Request``/``Completion`` — the public dataclasses.  A request is a
    prompt plus decode budget (``max_new``), optional ``eos_id``, sampling
    controls, a ``priority`` (preemption rank), and an optional TTL /
    wall-clock deadline enforced from ``submit()`` time; a completion
    carries the full ``generate``-shaped token sequence plus lifecycle
    metadata (submit/finish step, reason).
  * ``Engine.submit(request)`` — queue a request (returns its rid).  The
    queue is *bounded* when ``max_queue`` is set: overloading it sheds a
    request per the ``shed_policy`` ('reject-new' sheds the submission,
    'drop-oldest' sheds the head of the queue) as a
    ``Completion(finished='shed')`` — overload produces accounted-for
    completions, never an unbounded queue.
  * ``Engine.step()`` — one engine tick: expire queued/in-flight requests
    whose TTL or deadline passed (``finished='deadline'``), admit queued
    requests into free decode slots (jitted prefill into a cache
    *fragment*, then ``kv_cache.insert_fragment`` into the slot's pages),
    advance every occupied slot one token with the jitted
    ``_generate_step``, and retire slots that hit EOS or their ``max_new``
    budget — freeing their pages for the next queued request.  Returns the
    requests completed by this tick.
  * ``Engine.drain()`` — step until queue and slots are empty.

``_generate_step`` is jitted once per (cfg, mesh): the paged view, the
per-slot position vector, the active mask, and the page table are all
*traced* values, so admissions and completions never retrace.  Each tick
advances all occupied slots with per-slot position/length masks — vacant
slots compute garbage that is masked out of storage by the
``write_token`` OOB-drop scatter.

Fault isolation (the request-level robustness layer):

  * **Poisoned-request quarantine** — when a batched decode tick still
    fails after the guard (for a bare ``Engine``, a raw
    ``JaxRuntimeError``; under ``ResilientEngine.scheduler()``, a
    ``ServeRefused`` after the whole degradation ladder), the engine
    *bisects* the active slots by replaying masked sub-batches through
    the already-jitted step — active masks are traced values, so the
    probes reuse the existing trace — refuses only the culprit request(s)
    (``finished='refused'``, ``FALLBACK_COUNTS['quarantine']``), and
    requeues the healthy survivors with their accumulated tokens.
    Survivors resume via a fresh prefill of prompt + generated-so-far
    (device state after a fault is suspect; host tokens are the truth),
    and the resumed stream is bitwise-identical to an uninterrupted run
    because sampling keys fold in the *absolute* position.
  * **Preempt under page pressure** — when the page pool cannot back an
    admission (overcommitted ``n_pages``, or injected alloc failure), the
    lowest-priority/youngest in-flight request is evicted back to the
    queue (``FALLBACK_COUNTS['preempt']``), its pages reclaimed for the
    higher-priority candidate; the victim resumes later through the same
    re-prefill path.  Preemption requires *strictly* lower victim
    priority, so equal-priority traffic can never livelock-swap.

Parity invariant (the acceptance bar): a request served through the
engine — including one that was preempted or survived a quarantine —
yields tokens bitwise-equal to ``engine.generate`` of the same prompt
with ``max_len=engine.pool.max_len``.  The ingredients: prefill uses the
*same* jitted closure over the same cache shape; masked cache entries
(-1e30 → exp underflows to exactly 0.0) contribute nothing to the
softmax sums regardless of what stale pages hold; both paths sample
through ``engine.sample_tokens``; and per-request PRNG keys fold in the
absolute position, so a resume at position P samples exactly what the
uninterrupted run sampled at P.  MoE configs additionally need the
dropless regime (``capacity_factor >= n_experts / top_k``) — expert
capacity depends on batch size, so capacity *drops* may differ between
batch shapes.

``ResilientEngine.scheduler()`` wraps every jitted step in the
retry/deadline/degradation ladder via the ``guard`` hook — see
serve/resilience.py and docs/serving.md.

Tracing: each part of a step runs inside a ``jax.profiler.TraceAnnotation``
span — ``serve.step`` ⊃ ``serve.admit`` (⊃ ``serve.prefill``,
``serve.insert``) and ``serve.decode`` (⊃ ``serve.decode.inputs``, the
guarded call, ``serve.decode.retire``, ``serve.quarantine``) — so a
profile puts each device-idle gap under what the serving thread was doing.
Outside a profile a span costs about a microsecond; see docs/serving.md.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from functools import partial
from typing import Any, List, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.models import lm as LM
from repro.serve import engine as _engine
from repro.serve.context import ServeContext
from repro.serve.kv_cache import (PagedKVPool, PoolExhausted, paged_view,
                                  write_token)
from repro.serve.resilience import FALLBACK_COUNTS, ServeRefused

# What the robustness layer treats as "this jitted call faulted": a raw
# device fault (bare Engine) or an exhausted degradation ladder
# (ResilientEngine guard).  DeadlineExceeded et al. still propagate.
_FAULTS = (jax.errors.JaxRuntimeError, ServeRefused)

SHED_POLICIES = ("reject-new", "drop-oldest")


@dataclasses.dataclass
class Request:
    """One generation request.

    tokens: (T,) int prompt.  max_new: decode budget, generated tokens
    including the one the prefill emits.  eos_id: stop token (the emitted
    sequence includes it).  temperature/seed: sampling controls — the
    per-request PRNG is folded with the absolute position each step, so
    tokens are reproducible regardless of slot placement, co-tenants, or
    preempt/resume cycles.  priority: preemption rank (higher wins; a
    queued request may evict a strictly-lower-priority in-flight one
    under page pressure).  ttl_steps / deadline_s: expiry measured from
    ``submit()`` in engine steps / wall-clock seconds — an expired
    request completes with ``finished='deadline'`` instead of waiting
    forever (ttl_steps=None defers to the engine-wide ``request_ttl``).
    """
    tokens: Any
    max_new: int = 16
    eos_id: Optional[int] = None
    temperature: float = 0.0
    seed: int = 0
    rid: Optional[int] = None          # assigned by submit() when None
    priority: int = 0
    ttl_steps: Optional[int] = None
    deadline_s: Optional[float] = None


@dataclasses.dataclass
class Completion:
    """A finished request: ``tokens`` is prompt + generated — for 'eos' /
    'max_new' exactly the shape one-shot ``generate`` returns for the same
    prompt; for overload/fault outcomes, whatever was produced before the
    lifecycle ended."""
    rid: int
    prompt: np.ndarray
    tokens: np.ndarray
    n_generated: int
    finished: str        # 'eos' | 'max_new' | 'shed' | 'deadline' |
                         # 'refused' | 'pressure'
    submitted_step: int
    finished_step: int
    resumed: int = 0     # preempt/quarantine-survivor re-prefills it took
    error: Optional[str] = None        # diagnostics when finished='refused'


@dataclasses.dataclass
class _Pending:
    """A queued request: fresh (``out`` empty) or awaiting resume after a
    preemption / quarantine survival (``out`` holds the tokens generated
    before eviction)."""
    req: Request
    submitted_step: int
    submit_time: float
    out: List[int] = dataclasses.field(default_factory=list)
    resumed: int = 0


@dataclasses.dataclass
class _Slot:
    """Host-side record of an occupied decode slot."""
    req: Request
    out: List[int]                     # generated tokens so far
    pos: int                           # next cache write position
    key: np.ndarray                    # (2,) uint32 per-request PRNG
    submitted_step: int
    submit_time: float
    resumed: int = 0

    @property
    def rid(self) -> int:
        return self.req.rid

    @property
    def prompt(self) -> np.ndarray:
        return self.req.tokens


@partial(jax.jit, static_argnums=(0, 1, 2))
def _generate_step(cfg, mesh, page_size: int, params, lut, pages,
                   page_table, tok, pos, active, temp, keys):
    """Advance every occupied slot one token (single trace per (cfg, mesh)).

    pages: the paged KV pool pytree.  page_table: (B, npr) int32.  tok:
    (B, 1) last tokens.  pos: (B,) per-slot write positions.  active:
    (B,) bool.  temp: (B,) f32.  keys: (B, 2) uint32 per-request PRNG.
    Returns (new pages, (B,) next tokens).
    """
    _engine.TRACE_COUNTS["generate_step"] += 1
    _, decode_step = _engine._raw_serve_fns(cfg)
    with _engine._mesh_ctx(mesh):
        view = paged_view(cfg, pages, page_table)
        logits, new_view = decode_step(params, lut, tok, view, pos)
        subs = jax.vmap(jax.random.fold_in)(keys, pos)
        nxt = _engine.sample_tokens(logits, temp, subs)
        pages = write_token(cfg, page_size, pages, new_view, page_table,
                            pos, active)
    return pages, nxt


class Engine:
    """Continuous-batching serve engine over a paged KV pool.

    ctx: ``ServeContext`` (cfg, mesh, lut).  params: served weights (the
    ``ServeState.params`` pytree).  n_slots × max_len sizes the decode
    pool (max_len rounds up to a page multiple — read it back from
    ``engine.pool.max_len``); ``n_pages`` overcommits the pool when
    smaller than ``n_slots * pages_per_slot`` (free slot ≠ free pages —
    the preemption regime).  ``guard`` hooks every jitted call:
    ``guard(call, kind)`` with ``call(cfg) -> result`` and kind in
    {'prefill', 'decode', 'replay'} — the resilience ladder substitutes
    rung-suffixed configs and retries here (``ResilientEngine.scheduler``).

    Overload knobs: ``max_queue`` bounds the queue (None = unbounded,
    the pre-admission-control behavior); ``shed_policy`` picks who sheds
    on overflow ('reject-new' | 'drop-oldest'); ``request_ttl`` is the
    engine-wide default ``ttl_steps`` for requests that don't carry one.
    Requeues from preemption/quarantine are exempt from ``max_queue`` —
    admitted work is never shed by the bound that admitted it.
    """

    def __init__(self, ctx: ServeContext, params, *, n_slots: int = 4,
                 max_len: int = 64, page_size: int = 8,
                 dtype=jnp.bfloat16, guard=None,
                 max_queue: Optional[int] = None,
                 shed_policy: str = "reject-new",
                 request_ttl: Optional[int] = None,
                 n_pages: Optional[int] = None,
                 governor=None):
        if shed_policy not in SHED_POLICIES:
            raise ValueError(f"shed_policy must be one of {SHED_POLICIES}, "
                             f"got {shed_policy!r}")
        self.ctx = ctx
        self.params = params
        self.pool = PagedKVPool(ctx.cfg, n_slots, max_len,
                                page_size=page_size, dtype=dtype,
                                n_pages=n_pages)
        self.guard = guard or (lambda call, kind: call(self.ctx.cfg))
        self.max_queue = max_queue
        self.shed_policy = shed_policy
        self.request_ttl = request_ttl
        self._queue: collections.deque = collections.deque()
        self._slots: List[Optional[_Slot]] = [None] * n_slots
        self._next_rid = 0
        self.steps = 0
        self.completions: List[Completion] = []
        # Optional serve.governor.MemoryGovernor: runs at the top of every
        # step() (the fence where no jitted call is in flight) and may
        # trim/regrow the residency cache, retire/restore KV pages,
        # preempt in-flight requests, tighten max_queue, or flip the
        # engine into refuse-new-work mode (finished='pressure').
        self.governor = governor
        self.reset_stats()
        if governor is not None:
            governor.attach(self)

    def reset_stats(self) -> None:
        """Zero the lifecycle counters (benchmarks call this after a
        warmup drain so the measured trace starts clean).  Under tiered
        residency, the manager's fetch/hit counters and the module-wide
        ``RESIDENCY_COUNTS`` probe reset too."""
        self.stats = {"admitted": 0, "joined_mid_decode": 0,
                      "occupancy_steps": 0, "occupancy_sum": 0,
                      "occupancy_max": 0, "shed": 0, "expired": 0,
                      "preempted": 0, "quarantined": 0, "resumed": 0,
                      "queue_peak": 0, "pressure_refused": 0,
                      "pressure_preempted": 0}
        mgr = getattr(self.ctx, "residency", None)
        if mgr is not None:
            from repro.serve.residency import RESIDENCY_COUNTS
            RESIDENCY_COUNTS.clear()
            mgr.reset_stats()

    # -- public API ----------------------------------------------------
    def submit(self, request: Request) -> int:
        """Queue a request; returns its rid.  Admission happens on a
        later ``step()`` when a slot (and its pages) free up.  When the
        bounded queue is full, either this submission or the queue head
        sheds per ``shed_policy`` — as a ``Completion(finished='shed')``
        on ``engine.completions``, never a silent drop."""
        toks = np.asarray(request.tokens, np.int32).reshape(-1)
        if toks.size == 0:
            raise ValueError("empty prompt")
        if request.max_new < 1:
            raise ValueError("max_new must be >= 1")
        if toks.size + request.max_new > self.pool.max_len:
            raise ValueError(
                f"prompt ({toks.size}) + max_new ({request.max_new}) "
                f"exceeds pool max_len ({self.pool.max_len})")
        if request.rid is not None:
            rid = request.rid
            live = ({p.req.rid for p in self._queue}
                    | {s.rid for s in self._slots if s is not None})
            if rid in live:
                raise ValueError(
                    f"rid {rid} already in flight (queued or decoding); "
                    "caller-supplied rids must be unique among live "
                    "requests")
            # keep the auto counter ahead of caller-supplied rids so a
            # later submit() without a rid can never collide with one
            self._next_rid = max(self._next_rid, rid + 1)
        else:
            rid = self._next_rid
            self._next_rid += 1
        pending = _Pending(req=dataclasses.replace(request, tokens=toks,
                                                   rid=rid),
                           submitted_step=self.steps,
                           submit_time=time.monotonic())
        if self.governor is not None and self.governor.refusing:
            # rung 4 of the reclaim ladder: the budget fell below
            # min_viable — new work is refused with its own accounted-for
            # reason, never queued behind an engine that cannot grow
            FALLBACK_COUNTS["pressure_refused"] += 1
            self.stats["pressure_refused"] += 1
            self.completions.append(self._completion(
                pending.req.rid, pending.req.tokens, [], "pressure",
                pending.submitted_step))
            return rid
        if self.max_queue is not None and len(self._queue) >= self.max_queue:
            if self.shed_policy == "reject-new":
                self._shed(pending)
                return rid
            self._shed(self._queue.popleft())       # drop-oldest
        self._queue.append(pending)
        self.stats["queue_peak"] = max(self.stats["queue_peak"],
                                       len(self._queue))
        return rid

    def step(self) -> List[Completion]:
        """One engine tick: expire → admit → decode one token → retire.
        Returns the completions this tick produced.  When a governor is
        attached it runs first — the step boundary is the only fence
        where no jitted call is in flight, so capacity trims / page
        retirement (which reshape traced arrays) are safe here."""
        with TraceAnnotation("serve.step", step=self.steps):
            if self.governor is not None:
                self.governor.on_step(self)
            done = self._expire()
            done.extend(self._admit())
            occ = sum(s is not None for s in self._slots)
            self.stats["occupancy_steps"] += 1
            self.stats["occupancy_sum"] += occ
            self.stats["occupancy_max"] = max(self.stats["occupancy_max"],
                                              occ)
            if occ:
                with TraceAnnotation("serve.decode", rows=occ):
                    done.extend(self._decode_tick())
            self.steps += 1
            self.completions.extend(done)
            return done

    def drain(self, max_steps: int = 100_000) -> List[Completion]:
        """Step until the queue and all slots are empty; returns the
        completions produced while draining."""
        out: List[Completion] = []
        budget = max_steps
        while self._queue or any(s is not None for s in self._slots):
            out.extend(self.step())
            budget -= 1
            if budget <= 0:
                slots = [(i, s.rid, s.pos, len(s.out))
                         for i, s in enumerate(self._slots) if s is not None]
                raise RuntimeError(
                    f"drain did not converge after {max_steps} steps; "
                    f"health={self.health()}; "
                    f"slots (slot, rid, pos, n_out)={slots}; "
                    f"queued rids={[p.req.rid for p in self._queue]}")
        return out

    def health(self) -> dict:
        out = {
            "steps": self.steps,
            "queued": len(self._queue),
            "queue_peak": self.stats["queue_peak"],
            "occupied": sum(s is not None for s in self._slots),
            "admitted": self.stats["admitted"],
            "joined_mid_decode": self.stats["joined_mid_decode"],
            "occupancy_mean": (self.stats["occupancy_sum"]
                               / max(self.stats["occupancy_steps"], 1)),
            "occupancy_max": self.stats["occupancy_max"],
            "completed": len(self.completions),
            "free_pages": len(self.pool.free_pages),
            "shed": self.stats["shed"],
            "expired": self.stats["expired"],
            "preempted": self.stats["preempted"],
            "quarantined": self.stats["quarantined"],
            "resumed": self.stats["resumed"],
        }
        mgr = getattr(self.ctx, "residency", None)
        if mgr is not None:
            out["residency"] = mgr.snapshot()
        if self.governor is not None:
            out["pressure"] = self.governor.snapshot()
        return out

    def close(self) -> None:
        """Tear down serving-side workers (idempotent).  Today that is
        the residency prefetch thread — nothing else owns it, so an
        engine that was handed a tiered context must stop it or every
        served model leaks a live ``residency-prefetch`` thread."""
        mgr = getattr(self.ctx, "residency", None)
        if mgr is not None:
            mgr.close()

    def __enter__(self) -> "Engine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- overload internals --------------------------------------------
    def _shed(self, p: _Pending) -> None:
        FALLBACK_COUNTS["shed"] += 1
        self.stats["shed"] += 1
        self.completions.append(self._completion(
            p.req.rid, p.req.tokens, p.out, "shed", p.submitted_step,
            resumed=p.resumed))

    def _is_expired(self, ttl_steps, deadline_s, submitted_step,
                    submit_time) -> bool:
        ttl = ttl_steps if ttl_steps is not None else self.request_ttl
        if ttl is not None and self.steps - submitted_step >= ttl:
            return True
        if deadline_s is not None and \
                time.monotonic() - submit_time > deadline_s:
            return True
        return False

    def _expire(self) -> List[Completion]:
        """Retire queued and in-flight requests whose TTL/deadline (from
        submit time) has passed — Completion(finished='deadline') with
        whatever tokens exist, FALLBACK_COUNTS['expired'] per request."""
        done: List[Completion] = []
        if self._queue:
            keep: collections.deque = collections.deque()
            while self._queue:
                p = self._queue.popleft()
                if self._is_expired(p.req.ttl_steps, p.req.deadline_s,
                                    p.submitted_step, p.submit_time):
                    FALLBACK_COUNTS["expired"] += 1
                    self.stats["expired"] += 1
                    done.append(self._completion(
                        p.req.rid, p.req.tokens, p.out, "deadline",
                        p.submitted_step, resumed=p.resumed))
                else:
                    keep.append(p)
            self._queue = keep
        for i, s in enumerate(self._slots):
            if s is None:
                continue
            if self._is_expired(s.req.ttl_steps, s.req.deadline_s,
                                s.submitted_step, s.submit_time):
                FALLBACK_COUNTS["expired"] += 1
                self.stats["expired"] += 1
                done.append(self._completion(
                    s.rid, s.prompt, s.out, "deadline", s.submitted_step,
                    resumed=s.resumed))
                self.pool.free(i)
                self._slots[i] = None
        return done

    def _preempt_for(self, head: _Pending) -> bool:
        """Evict the lowest-priority (tie: youngest) in-flight request to
        reclaim pages for ``head`` — only if the victim ranks *strictly*
        below it (equal-priority traffic must not livelock-swap)."""
        occ = [(s.req.priority, -s.submitted_step, i)
               for i, s in enumerate(self._slots) if s is not None]
        if not occ:
            return False
        _, _, i = min(occ)
        victim = self._slots[i]
        if victim.req.priority >= head.req.priority:
            return False
        FALLBACK_COUNTS["preempt"] += 1
        self.stats["preempted"] += 1
        # requeue right behind the head that displaced it, carrying its
        # generated tokens; it resumes via re-prefill when pages free up
        self._queue.insert(1, _Pending(
            req=victim.req, submitted_step=victim.submitted_step,
            submit_time=victim.submit_time, out=list(victim.out),
            resumed=victim.resumed + 1))
        self.pool.free(i)
        self._slots[i] = None
        return True

    def preempt_lowest(self) -> bool:
        """Evict the lowest-priority (tie: youngest) in-flight request to
        give its pages back under *memory pressure* (governor rung 2).
        Unlike ``_preempt_for`` there is no displacing head, so no
        priority precondition — the pool itself must shrink and someone
        has to yield.  The victim requeues at the front with its tokens
        and resumes bitwise-equal via the re-prefill path once pages
        exist again."""
        occ = [(s.req.priority, -s.submitted_step, i)
               for i, s in enumerate(self._slots) if s is not None]
        if not occ:
            return False
        _, _, i = min(occ)
        victim = self._slots[i]
        FALLBACK_COUNTS["pressure_preempt"] += 1
        self.stats["preempted"] += 1
        self.stats["pressure_preempted"] += 1
        self._queue.appendleft(_Pending(
            req=victim.req, submitted_step=victim.submitted_step,
            submit_time=victim.submit_time, out=list(victim.out),
            resumed=victim.resumed + 1))
        self.pool.free(i)
        self._slots[i] = None
        return True

    # -- admission -----------------------------------------------------
    def _prefill(self, toks: np.ndarray):
        """Jitted prefill of a 1-D token sequence into a fresh
        ``max_len``-long cache fragment — the same closure and cache shape
        one-shot ``generate`` uses, so the fragment is bitwise what
        generate's cache would hold."""
        toks = jnp.asarray(np.asarray(toks, np.int32)[None, :])
        caches = LM.init_caches(self.ctx.cfg, 1, self.pool.max_len)

        def call(cfg):
            prefill, _ = _engine.make_serve_fns(
                ctx=self.ctx.with_cfg(cfg))
            return prefill(self.params, self.ctx.lut,
                           {"tokens": toks, "embeds": None}, caches)

        logits, frag = self.guard(call, "prefill")
        tok0 = int(np.asarray(_engine.sample_tokens(logits, 0.0))[0])
        return tok0, frag

    def _admit(self) -> List[Completion]:
        """Move queued requests into free slots (prefill → insert).

        Fresh requests prefill their prompt; resumes (preempted /
        quarantine survivors) prefill prompt + out[:-1] so the cache holds
        exactly what the uninterrupted run's cache held, then continue
        from their last emitted token at the same absolute position.  A
        request whose prefill *itself* faults past the guard is refused
        alone (``finished='refused'``) — one poisoned prompt cannot stall
        the queue behind it."""
        done: List[Completion] = []
        while self._queue:
            free = [i for i, s in enumerate(self._slots) if s is None]
            if not free:
                break
            if not self.pool.can_alloc():
                if not self._preempt_for(self._queue[0]):
                    break
                free = [i for i, s in enumerate(self._slots) if s is None]
            p = self._queue.popleft()
            req = p.req
            resume = bool(p.out)
            with TraceAnnotation("serve.admit", rid=req.rid,
                                 prompt_len=len(req.tokens),
                                 resume=p.resumed):
                toks = (np.concatenate([req.tokens,
                                        np.asarray(p.out[:-1], np.int32)])
                        if resume else req.tokens)
                try:
                    with TraceAnnotation("serve.prefill"):
                        tok0, frag = self._prefill(toks)
                except _FAULTS as e:
                    FALLBACK_COUNTS["quarantine"] += 1
                    self.stats["quarantined"] += 1
                    done.append(self._completion(
                        req.rid, req.tokens, p.out, "refused",
                        p.submitted_step, resumed=p.resumed, error=repr(e)))
                    continue
                self.stats["admitted"] += 1
                if resume:
                    self.stats["resumed"] += 1
                if any(s is not None for s in self._slots):
                    self.stats["joined_mid_decode"] += 1
                if not resume:
                    if req.max_new == 1 or (req.eos_id is not None
                                            and tok0 == req.eos_id):
                        done.append(self._completion(
                            req.rid, req.tokens, [tok0],
                            "eos" if (req.eos_id is not None
                                      and tok0 == req.eos_id)
                            else "max_new", p.submitted_step))
                        continue
                    out = [tok0]
                else:
                    out = list(p.out)      # resume: discard the probe token
                slot = free[0]
                with TraceAnnotation("serve.insert"):
                    try:
                        self.pool.alloc(slot)
                    except PoolExhausted:
                        # pressure surfaced at the alloc seam itself
                        # (injected fault, or raced reclaim): requeue at
                        # the head and retry next tick — prefill is pure,
                        # so nothing is lost
                        self._queue.appendleft(p)
                        break
                    self.pool.insert(frag, slot)
                self._slots[slot] = _Slot(
                    req=req, out=out, pos=len(req.tokens) + len(out) - 1,
                    key=np.asarray(jax.random.PRNGKey(req.seed), np.uint32),
                    submitted_step=p.submitted_step,
                    submit_time=p.submit_time, resumed=p.resumed)
        return done

    # -- decode --------------------------------------------------------
    def _decode_tick(self) -> List[Completion]:
        with TraceAnnotation("serve.decode.inputs"):
            b = self.pool.n_slots
            tok = np.zeros((b, 1), np.int32)
            pos = np.zeros((b,), np.int32)
            active = np.zeros((b,), bool)
            temp = np.zeros((b,), np.float32)
            keys = np.zeros((b, 2), np.uint32)
            for i, s in enumerate(self._slots):
                if s is None:
                    continue
                tok[i, 0] = s.out[-1]
                pos[i] = s.pos
                active[i] = True
                temp[i] = s.req.temperature
                keys[i] = s.key
            pt = jnp.asarray(self.pool.page_table)
            tok, pos = jnp.asarray(tok), jnp.asarray(pos)
            temp, keys = jnp.asarray(temp), jnp.asarray(keys)

        def call_with(mask):
            mgr = getattr(self.ctx, "residency", None)

            def call(cfg):
                if mgr is not None:
                    # tiered residency: run the routed twin of the step
                    # under the fetch/replay protocol.  Only active
                    # slots' routing drives fetches; the launch is pure
                    # (pages are returned, not committed), so replays
                    # are safe and parity holds per decode tick.
                    from repro.serve import residency as _res
                    mgr.check_params(self.params)

                    def launch(dp):
                        pages_, nxt_, routing = _res._tiered_generate_step(
                            cfg, self.ctx.mesh, self.pool.page_size, dp,
                            self.ctx.lut, self.pool.pages, pt, tok, pos,
                            jnp.asarray(mask), temp, keys)
                        return (pages_, nxt_), routing

                    return mgr.run(launch, active=mask)
                return _generate_step(
                    cfg, self.ctx.mesh, self.pool.page_size, self.params,
                    self.ctx.lut, self.pool.pages, pt, tok, pos,
                    jnp.asarray(mask), temp, keys)
            return call

        try:
            pages, nxt = self.guard(call_with(active), "decode")
        except _FAULTS as e:
            with TraceAnnotation("serve.quarantine"):
                return self._quarantine(active, call_with, e)

        with TraceAnnotation("serve.decode.retire"):
            self.pool.pages = pages
            nxt = np.asarray(nxt)
            done: List[Completion] = []
            for i, s in enumerate(self._slots):
                if s is None:
                    continue
                t = int(nxt[i])
                s.out.append(t)
                s.pos += 1
                if len(s.out) >= s.req.max_new or (
                        s.req.eos_id is not None and t == s.req.eos_id):
                    reason = ("eos" if s.req.eos_id is not None
                              and t == s.req.eos_id else "max_new")
                    done.append(self._completion(s.rid, s.prompt, s.out,
                                                 reason, s.submitted_step,
                                                 resumed=s.resumed))
                    self.pool.free(i)
                    self._slots[i] = None
            return done

    def _quarantine(self, active, call_with, exc) -> List[Completion]:
        """Bisect the active slots to isolate the poisoned request(s).

        Replays masked sub-batches through the already-jitted step (the
        mask is a traced value — no retrace); a subset that faults is
        split, a subset that succeeds is vindicated wholesale.  Culprits
        are refused (``finished='refused'``), survivors requeued at the
        front with their accumulated tokens for a resume re-prefill.  If
        no individual culprit reproduces the fault (a cross-request
        interaction or a genuinely global fault), the original error
        re-raises — refusing everyone blindly would be worse than loud
        failure."""
        occupied = [i for i in range(len(self._slots)) if active[i]]

        def faults(subset) -> bool:
            mask = np.zeros_like(active)
            mask[list(subset)] = True
            try:
                self.guard(call_with(mask), "replay")  # outputs discarded
                return False
            except _FAULTS:
                return True

        def bisect(group, known_faulty) -> List[int]:
            if not known_faulty and not faults(group):
                return []
            if len(group) == 1:
                return list(group)
            mid = len(group) // 2
            return bisect(group[:mid], False) + bisect(group[mid:], False)

        culprits = set(bisect(occupied, True))
        if not culprits:
            raise exc
        done: List[Completion] = []
        survivors: List[_Pending] = []
        for i in occupied:
            s = self._slots[i]
            if i in culprits:
                FALLBACK_COUNTS["quarantine"] += 1
                self.stats["quarantined"] += 1
                done.append(self._completion(
                    s.rid, s.prompt, s.out, "refused", s.submitted_step,
                    resumed=s.resumed, error=repr(exc)))
            else:
                # the faulted tick never committed pages, but post-fault
                # device state is not worth trusting: resume from host
                # tokens via a fresh prefill
                survivors.append(_Pending(
                    req=s.req, submitted_step=s.submitted_step,
                    submit_time=s.submit_time, out=list(s.out),
                    resumed=s.resumed + 1))
            self.pool.free(i)
            self._slots[i] = None
        self._queue.extendleft(reversed(survivors))
        return done

    def _completion(self, rid, prompt, out, reason, submitted, *,
                    resumed: int = 0, error: Optional[str] = None
                    ) -> Completion:
        return Completion(
            rid=rid, prompt=np.asarray(prompt),
            tokens=np.concatenate([np.asarray(prompt, np.int32),
                                   np.asarray(out, np.int32)]),
            n_generated=len(out), finished=reason,
            submitted_step=submitted, finished_step=self.steps,
            resumed=resumed, error=error)
