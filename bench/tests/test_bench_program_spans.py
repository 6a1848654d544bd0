"""The readers of the program's spans: device idle put under what the
serving thread was doing, and the length of an admission."""
import gzip

import pytest

from bench import costs, harness, program_spans, spec
from bench import peaks as P
from bench import trace_reduce as TR

E = TR.Event
MS = 1e6                                  # ns
THREAD = "/host:CPU/python"
NEW = ["serve.idle_host_share", "guard.launch_lag_share",
       "guard.notify_lag_share", "sched.admit_ms"]
OLD = ["sched.prefill_share", "model.mfu", "fused_decode_matmul_roofline",
       "device.idle_share"]
RECORDED = spec.BENCH / "tests" / "data" / "v5e_internlm2_decode.xplane.pb.gz"


def _guarded(t, wait_end, kind_end):
    """guard.call [t, kind_end] = dispatch 1 ms, wait, effects 1 ms."""
    return [E("guard.call", t, kind_end),
            E("guard.dispatch", t, t + 1),
            E("PjitFunction(_generate_step)", t, t + 1),   # not a span
            E("guard.wait", t + 1, wait_end),
            E("guard.effects", wait_end, kind_end)]


def _raw(host, ops, modules):
    def ev(items):
        return [E(n, s * MS, e * MS) for n, s, e in items]
    return TR.Raw(ops={"/device:TPU:0": ev(ops)},
                  modules={"/device:TPU:0": ev(modules)},
                  host={THREAD: ev(host),
                        "/host:CPU/other": ev([("serve.step", 0, 100)])})


def _steps():
    """Two steps in a 100 ms window (times in ms).  Idle: [0, 8]
    [20, 37] [50, 72] [90, 100]; host 4+12+9+5, launch lag 3+2+2,
    notify lag 3+4+4, outside any program span 1+7+1 (bench.step,
    bench.submit)."""
    host = [("bench.window", 0, 100), ("bench.step", 0, 60),
            ("serve.step", 1, 59), ("serve.admit", 2, 30),
            ("serve.prefill", 3, 25), ("serve.insert", 25, 29),
            ("serve.decode", 31, 58), ("serve.decode.inputs", 31, 34),
            ("serve.decode.retire", 55, 58),
            ("bench.submit", 60, 65),
            ("serve.step", 66, 99), ("serve.admit", 67, 97),
            ("serve.prefill", 68, 96)]
    host += [(e.name, e.start, e.end) for e in _guarded(4, 23, 24)
             + _guarded(34, 54, 55) + _guarded(69, 94, 95)]
    ops = [("%while.1 = while(...)", 8, 20),
           ("%fusion.1 = fusion()", 9, 12),
           ("%fused_decode_matmul.3 = custom-call()", 37, 50),
           ("%fusion.2 = fusion()", 72, 90)]
    modules = [("jit_prefill(1)", 8, 20), ("jit__generate_step(2)", 37, 50),
               ("jit_prefill(1)", 72, 90)]
    return _raw(host, ops, modules)


def _stall(program):
    """One 3 s decode tick whose program runs over ``program`` (ms)."""
    host = [("bench.window", 0, 3000), ("bench.step", 0, 3000),
            ("serve.step", 0, 3000), ("serve.decode", 1, 2999),
            ("serve.decode.inputs", 1, 5), ("serve.decode.retire", 2995,
                                            2998)]
    host += [(e.name, e.start, e.end) for e in _guarded(5, 2990, 2995)]
    return _raw(host, [("%fused_decode_matmul.1 = custom-call()", *program)],
                [("jit__generate_step(2)", *program)])


# (trace, host ms, launch lag ms, notify lag ms, sched.admit_ms, window ms)
CASES = {
    "two-steps": (_steps, 30, 7, 11, 29, 100),
    "notify-stall": (lambda: _stall((12, 900)), 16, 6, 2090, None, 3000),
    "launch-stall": (lambda: _stall((2100, 2988)), 16, 2094, 2, None,
                     3000),
}


def _ctx(raw, **kw):
    return harness.Ctx(config={}, model=None, summary={}, ledger=None,
                       peaks={}, trace=TR.reduce(raw), **kw)


def _read(names, ctx):
    return {n: spec.metric_reader(n).read(ctx) for n in names}


@pytest.mark.parametrize("case", sorted(CASES))
def test_idle_is_put_under_the_innermost_span(case, monkeypatch):
    make, host, launch, notify, admit, window = CASES[case]
    raw = make()
    monkeypatch.setattr(program_spans, "of_run",
                        lambda ctx: program_spans.split(raw))
    got = _read(NEW, _ctx(raw))
    assert got["serve.idle_host_share"] == pytest.approx(
        100 * host / window)
    assert got["guard.launch_lag_share"] == pytest.approx(
        100 * launch / window)
    assert got["guard.notify_lag_share"] == pytest.approx(
        100 * notify / window)
    assert got["sched.admit_ms"] == pytest.approx(admit)
    idle = _read(["device.idle_share"], _ctx(raw))["device.idle_share"]
    # float rounding aside, the three parts of idle never exceed it
    assert sum(got[n] for n in NEW[:3]) <= idle + 1e-9


def test_a_wait_whose_program_never_started_is_launch_lag():
    raw = _stall((12, 900))
    raw.modules["/device:TPU:0"] = []
    s = program_spans.split(raw)
    assert s.launch_lag_s == pytest.approx(2.096)
    assert s.notify_lag_s == 0.0


def test_a_trace_without_program_spans_reads_none():
    raw = _steps()
    raw.host[THREAD] = [e for e in raw.host[THREAD]
                        if e.name.startswith("bench.")]
    assert program_spans.split(raw) is None


@pytest.fixture
def recorded(tmp_path, monkeypatch):
    """The recorded v5e trace, where a traced run leaves its profile."""
    (tmp_path / "r.xplane.pb").write_bytes(gzip.decompress(
        RECORDED.read_bytes()))
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    return TR.load(RECORDED)


class _Ledger:
    def window_prefills(self):
        return [17]

    def context_lengths(self):
        return [40] * 16


def test_the_recorded_trace_reads_none_and_old_readers_hold(recorded):
    """The recording predates the program's spans: the four new readers
    read None, and the four accepted readers read what they did."""
    ctx = _ctx(recorded, fused_weights=[(2048, 2048, 1 << 20)] * 168,
               rows_per_call=[8, 8])
    ctx.config = spec.config("internlm2-1.8b")
    ctx.model = spec.model("internlm2")
    ctx.summary = {"span_s": 1.959815174}
    ctx.ledger = _Ledger()
    ctx.peaks = P.lookup("TPU v5 lite")
    before = _read(OLD, ctx)
    assert _read(NEW, ctx) == dict.fromkeys(NEW)
    assert _read(OLD, ctx) == before
    assert before["device.idle_share"] == pytest.approx(
        100 * (1 - 1.94821606 / 1.959815174))
    assert before["sched.prefill_share"] == 0.0
    ops, byts = costs.fused_matmul(ctx.fused_weights, [8, 8])
    assert before["fused_decode_matmul_roofline"] == pytest.approx(
        costs.roofline_share(ops, byts, 1.887878419,
                             ctx.peaks["bf16_flops_per_s"],
                             ctx.peaks["hbm_bytes_per_s"]))
    assert before["model.mfu"] > 0


def test_an_untraced_run_reads_none(recorded):
    ctx = _ctx(recorded)
    ctx.trace = None
    assert _read(NEW, ctx) == dict.fromkeys(NEW)
