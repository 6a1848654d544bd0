"""Resilient serving — bounded retry, deadlines, and a degradation ladder.

The serve stack's production posture (the inference-side mirror of
``train/fault.py``): a request against a compressed model must never die
on the first ``JaxRuntimeError`` or silently serve from a corrupt
artifact.  ``ResilientEngine`` wraps ``engine.prefill``/``engine.generate``
with:

  * **Integrity gate** — per ``ResiliencePolicy.verify`` ('off'|'fast'|
    'full'), the artifact is host-verified against its pack-time manifest
    (``core.integrity.verify_serve_state``) and the cheap jittable
    device-side invariant check (``check_invariants``) runs before the
    first prefill.  Quarantined leaves abort serving with
    ``IntegrityError`` naming them — no decode of unverified planes while
    verification is on.
  * **Bounded retry** — each ladder rung is attempted up to
    ``max_retries + 1`` times on ``jax.errors.JaxRuntimeError`` (transient
    device faults recover in place, exactly like the train loop's step
    retry).
  * **Degradation ladder** — persistent failures descend
    ``fused`` (megakernel) → ``unfused`` (two-step decode→matmul) →
    ``materialize`` (pure-jnp decode + dense einsum, no Pallas anywhere)
    → refuse with ``ServeRefused`` carrying the per-rung diagnostics.
    Each fallback ticks ``FALLBACK_COUNTS`` (alongside the existing
    ``ops.DISPATCH_COUNTS`` / ``engine.TRACE_COUNTS`` probes) so CI and
    the health snapshot can prove which rungs ran.  Rungs re-trace under a
    suffixed config name — the jit caches key on (cfg, mesh), so a broken
    fused trace is never reused by a fallback rung.
  * **Per-request deadline** — ``deadline_s`` (policy or per-call) bounds
    the whole retry/ladder walk; expiry raises ``DeadlineExceeded``
    instead of burning the remaining rungs.

The same machinery covers the continuous-batching path:
``ResilientEngine.scheduler()`` returns a ``serve.scheduler.Engine`` whose
jitted prefill and ``generate_step`` calls each walk the ladder through
the ``_guard`` hook — one faulty decode tick degrades (and re-traces)
without tearing down the whole serving loop or its co-tenant requests.
When even the ladder's last rung fails for a batched tick (a *poisoned
request*, not a broken kernel), the scheduler takes over: it bisects the
active slots with masked replays of the same jitted step, refuses only
the culprit (``ServeRefused`` semantics at request granularity,
``FALLBACK_COUNTS['quarantine']``), and requeues the healthy survivors —
the guard's ``kind`` is 'replay' for those probes.  Overload events the
scheduler accounts for (shed / expired / preempt) tick the same counter,
so ``health()['fallbacks']`` is the one place CI asserts the whole
robustness matrix.

Each rung attempt runs inside a ``guard.call`` profiler span (arguments
``kind``, ``rung``, ``attempt``) holding ``guard.dispatch`` (the call:
trace on a cache miss, enqueue), ``guard.wait`` (``block_until_ready``)
and ``guard.effects`` (the effects barrier), so a profile tells a program
that started late from one whose end reached the host late.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Optional

import jax
from jax.profiler import TraceAnnotation

from repro.core.integrity import (IntegrityError, check_invariants,
                                  verify_serve_state)
from repro.kernels import ops
from repro.serve import engine as _engine

# Degradation probe: rung/event -> count.  'unfused'/'materialize' tick
# when the ladder *falls back* onto that rung; 'retry:<rung>' per bounded
# in-rung retry; 'deadline' on expiry; 'refused' when the ladder is
# exhausted; 'integrity_refused' when the verify gate quarantines the
# artifact.  The request-level scheduler (serve/scheduler.py) ticks its
# own lifecycle events here too so one counter tells the whole
# degradation story: 'quarantine' per poisoned request refused out of a
# batch, 'preempt' per in-flight request evicted under page pressure,
# 'shed' per request shed by the bounded queue, 'expired' per TTL /
# deadline expiry.  The memory-pressure governor (serve/governor.py)
# ticks 'pressure_*' keys: 'pressure_trim' per residency-capacity trim,
# 'pressure_kv_retire' per KV page-retirement batch, 'pressure_preempt'
# per in-flight request evicted to shrink the pool, 'pressure_tighten'
# per admission tightening, 'pressure_refused' per submission refused at
# rung 4, 'pressure_regrow' per regrow-ladder application.  Reset
# between tests by the autouse conftest fixture.
FALLBACK_COUNTS = collections.Counter()

# Ladder rung -> the ops session impl that forces it.  'fused' serves with
# the session default ('auto': megakernel dispatch); the fallbacks pin the
# lever so every compressed matmul in the re-traced program takes the rung.
_RUNG_IMPL = {ops.FUSED_RUNG: None,
              ops.Impl.UNFUSED.value: ops.Impl.UNFUSED.value,
              ops.Impl.MATERIALIZE.value: ops.Impl.MATERIALIZE.value}


class DeadlineExceeded(TimeoutError):
    """Per-request wall-clock budget expired mid retry/ladder walk."""


class ServeRefused(RuntimeError):
    """Every ladder rung failed; carries the per-rung diagnostics."""

    def __init__(self, errors):
        self.errors = list(errors)        # [(rung, attempt, repr(exc))]
        super().__init__(
            "degradation ladder exhausted: "
            + "; ".join(f"{r}#{a}: {e}" for r, a, e in self.errors))


@dataclasses.dataclass(frozen=True)
class ResiliencePolicy:
    max_retries: int = 1                  # per rung, on JaxRuntimeError
    deadline_s: float = 0.0               # 0 = no per-request deadline
    ladder: tuple = ops.DEFAULT_LADDER
    verify: str = "off"                   # off | fast | full (boot gate)


def _generate(params, cfg, tokens, **kw):
    """Seam for fault injection/tests — resolves to ``engine.generate``."""
    return _engine.generate(params, cfg, tokens, **kw)


def _prefill(cfg, mesh, params, lut, batch, caches, residency=None):
    """Seam mirroring :func:`_generate` for the prefill path."""
    from repro.serve.context import ServeContext
    prefill, _ = _engine.make_serve_fns(
        ctx=ServeContext(cfg=cfg, mesh=mesh, lut=lut, residency=residency))
    return prefill(params, lut, batch, caches)


class ResilientEngine:
    """Fault-covered front door over (ServeState, cfg) serving.

    ``state`` is an ``engine.ServeState`` (or any object with ``params``/
    ``lut``/``manifest`` attributes).  The integrity gate runs once at
    construction per ``policy.verify``; ``generate``/``prefill`` then walk
    the retry/deadline/ladder machinery per request.
    """

    def __init__(self, cfg, state, *, policy: ResiliencePolicy | None = None,
                 mesh=None, residency=None):
        self.cfg = cfg
        self.state = state
        self.mesh = mesh
        # Optional serve.residency.ResidencyManager: tiered expert
        # residency (host-RAM backing + HBM cache).  Threaded into every
        # ServeContext this engine builds, so one cache serves generate,
        # the scheduler, and every degradation-ladder rung; fetch faults
        # raise JaxRuntimeError host-side and walk the same ladder.
        self.residency = residency
        if residency is not None and mesh is not None:
            raise ValueError("tiered residency is single-device — "
                             "mesh must be None")
        self.policy = policy or ResiliencePolicy()
        self.verify_report = None
        self.invariant_report = None
        self.requests = 0
        self.last_rung: Optional[str] = None
        # [(rung, attempt, repr(exc))]: the newest, as many as health()
        # reports
        self._history = collections.deque(maxlen=8)
        if self.policy.verify != "off":
            self._integrity_gate()

    # -- integrity -----------------------------------------------------
    def _integrity_gate(self):
        """Host re-hash + device-side invariants before any decode."""
        self.verify_report = verify_serve_state(self.state,
                                                level=self.policy.verify)
        if not self.verify_report.ok:
            FALLBACK_COUNTS["integrity_refused"] += 1
            raise IntegrityError(self.verify_report)
        self.invariant_report = check_invariants(self.state)
        if not self.invariant_report.ok:
            FALLBACK_COUNTS["integrity_refused"] += 1
            raise IntegrityError(self.invariant_report)

    # -- rung plumbing -------------------------------------------------
    def _rung_cfg(self, rung: str):
        """Fallback rungs serve under a suffixed config name: the serve jit
        caches key on (cfg, mesh), so the fallback re-traces with the
        session impl lever pinned instead of reusing the faulty trace."""
        if rung == self.policy.ladder[0]:
            return self.cfg
        return dataclasses.replace(self.cfg,
                                   name=f"{self.cfg.name}+{rung}")

    @staticmethod
    def _effects_barrier():
        """Surface host-callback/ordered-effect faults as JaxRuntimeError.

        A failing host callback inside a jitted program parks its error on
        the ordered-effects *token*, not (reliably) on the value outputs —
        the custom-call thunks feeding Pallas kernels drop input error
        events — and jax only awaits tokens at interpreter exit.  Draining
        here turns that deferred crash into a catchable per-request fault;
        the poisoned token is cleared so fallback rungs start clean."""
        from jax._src import dispatch as _dispatch
        try:
            jax.effects_barrier()
        except jax.errors.JaxRuntimeError:
            _dispatch.runtime_tokens.clear()
            raise

    def _run_rung(self, rung: str, fn, kind: str, attempt: int):
        lever = _RUNG_IMPL.get(rung)
        prev = ops._DEFAULT_IMPL
        with TraceAnnotation("guard.call", kind=kind, rung=rung,
                             attempt=attempt):
            try:
                if lever is not None:
                    ops.set_default_impl(lever)
                with TraceAnnotation("guard.dispatch"):
                    out = fn()
                with TraceAnnotation("guard.wait"):
                    jax.block_until_ready(out)    # surface faults here
                with TraceAnnotation("guard.effects"):
                    self._effects_barrier()
                return out
            except jax.errors.JaxRuntimeError:
                # The fault may be parked on BOTH the value outputs and
                # the ordered-effects token; drain the token here so a
                # stale poisoned one can't fail the next (healthy) rung.
                try:
                    self._effects_barrier()
                except jax.errors.JaxRuntimeError:
                    pass
                raise
            finally:
                ops.set_default_impl(prev)

    def _deadline_check(self, t0: float, deadline: float):
        if deadline and time.monotonic() - t0 > deadline:
            FALLBACK_COUNTS["deadline"] += 1
            raise DeadlineExceeded(
                f"request exceeded {deadline:.3f}s "
                f"(elapsed {time.monotonic() - t0:.3f}s; "
                f"history {list(self._history)[-4:]})")

    def _with_ladder(self, make_call, kind: str, *,
                     deadline_s: Optional[float]):
        """Retry/ladder walk shared by generate, prefill and the
        scheduler's guard.

        ``make_call(rung)`` returns a zero-arg callable for that rung;
        ``kind`` names the call in the ``guard.call`` span.
        """
        deadline = (self.policy.deadline_s if deadline_s is None
                    else deadline_s)
        t0 = time.monotonic()
        errors = []
        self.requests += 1
        for i, rung in enumerate(self.policy.ladder):
            if i > 0:
                FALLBACK_COUNTS[rung] += 1
            for attempt in range(self.policy.max_retries + 1):
                self._deadline_check(t0, deadline)
                if attempt > 0:
                    FALLBACK_COUNTS[f"retry:{rung}"] += 1
                try:
                    out = self._run_rung(rung, make_call(rung), kind,
                                         attempt)
                    self.last_rung = rung
                    return out
                except jax.errors.JaxRuntimeError as e:
                    rec = (rung, attempt, f"{type(e).__name__}: {e}"[:200])
                    errors.append(rec)
                    self._history.append(rec)
        FALLBACK_COUNTS["refused"] += 1
        raise ServeRefused(errors)

    # -- public API ----------------------------------------------------
    def generate(self, tokens, *, max_new: int = 16, temperature: float = 0.0,
                 key=None, embeds=None, max_len: int | None = None,
                 deadline_s: float | None = None):
        from repro.serve.context import ServeContext

        def make_call(rung):
            cfg = self._rung_cfg(rung)
            ctx = ServeContext(cfg=cfg, mesh=self.mesh, lut=self.state.lut,
                               verify=self.policy.verify,
                               residency=self.residency)
            return lambda: _generate(self.state.params, cfg, tokens,
                                     ctx=ctx, max_new=max_new,
                                     max_len=max_len,
                                     temperature=temperature, key=key,
                                     embeds=embeds)
        return self._with_ladder(make_call, "generate",
                                 deadline_s=deadline_s)

    def prefill(self, batch, caches, *, deadline_s: float | None = None):
        def make_call(rung):
            cfg = self._rung_cfg(rung)
            return lambda: _prefill(cfg, self.mesh, self.state.params,
                                    self.state.lut, batch, caches,
                                    residency=self.residency)
        return self._with_ladder(make_call, "prefill",
                                 deadline_s=deadline_s)

    def _guard(self, call, kind: str):
        """Scheduler guard hook: run one jitted engine call (``call(cfg)``,
        kind 'prefill'|'decode'|'replay') under the retry/deadline/ladder
        walk.  Each rung substitutes its suffixed config, so a broken fused
        generate_step re-traces unfused instead of reusing the bad trace.
        'replay' calls are the quarantine bisect's masked sub-batch probes:
        they walk the same ladder, so a probe only reports a subset faulty
        when no rung can serve it — exactly the culprit criterion."""
        return self._with_ladder(
            lambda rung: (lambda: call(self._rung_cfg(rung))), kind,
            deadline_s=None)

    def scheduler(self, **engine_kw):
        """A continuous-batching ``scheduler.Engine`` whose every jitted
        prefill/decode step walks this engine's resilience ladder.  Keyword
        args (``n_slots``, ``max_len``, ``page_size``, ``governor``, ...)
        pass through; the built engine is remembered so ``health()`` /
        ``close()`` cover it."""
        from repro.serve.context import ServeContext
        from repro.serve import scheduler as _sched
        ctx = ServeContext(cfg=self.cfg, mesh=self.mesh, lut=self.state.lut,
                           verify=self.policy.verify,
                           residency=self.residency)
        self._scheduler = _sched.Engine(ctx, self.state.params,
                                        guard=self._guard, **engine_kw)
        return self._scheduler

    def close(self) -> None:
        """Tear down serving workers (residency prefetch thread) —
        idempotent; also usable as a context manager."""
        sched = getattr(self, "_scheduler", None)
        if sched is not None:
            sched.close()
        elif self.residency is not None:
            self.residency.close()

    def __enter__(self) -> "ResilientEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def health(self) -> dict:
        """Snapshot for operators/CI: verify + probe counters + last rung.
        Under tiered residency, includes the manager's hit/miss/prefetch/
        eviction/bytes-fetched snapshot alongside the fallback counters."""
        out = {
            "requests": self.requests,
            "last_rung": self.last_rung,
            "fallbacks": dict(FALLBACK_COUNTS),
            "dispatch": dict(ops.DISPATCH_COUNTS),
            "verify": (self.verify_report.summary()
                       if self.verify_report else None),
            "invariants": (self.invariant_report.summary()
                           if self.invariant_report else None),
            "recent_errors": list(self._history),
        }
        if self.residency is not None:
            out["residency"] = self.residency.snapshot()
        sched = getattr(self, "_scheduler", None)
        if sched is not None and sched.governor is not None:
            out["pressure"] = sched.governor.snapshot()
        return out
