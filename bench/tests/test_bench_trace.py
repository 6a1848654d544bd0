"""The trace reduction: busy time is a union, gaps are named by the host."""
import pytest

from bench import spec

from bench import trace_reduce as TR

E = TR.Event


def _raw():
    host = {"/host:CPU/python": [
        E("bench.window", 0, 100e6),
        E("bench.step", 0, 60e6),
        E("PjitFunction(_generate_step)", 5e6, 20e6),
        E("bench.submit", 70e6, 90e6)]}
    ops = [E("%while.1 = (s32[]) while(...)", 10e6, 40e6),
           E("%fusion.1 = bf16[8] fusion(...)", 10e6, 20e6),
           E("%fusion.2 = bf16[8] fusion(...)", 25e6, 40e6),
           E("%fused_decode_matmul.3 = bf16[8,1024] custom-call(...)",
             50e6, 60e6),
           E("%grouped_fused_decode_matmul.4 = bf16[8] custom-call()",
             60e6, 62e6),
           E("%fusion.4 = bf16[8] fusion(...)", 95e6, 120e6)]
    modules = [E("jit_prefill(123)", 10e6, 40e6),
               E("jit__generate_step(456)", 50e6, 62e6),
               E("jit__generate_step(456)", 95e6, 120e6)]
    return TR.Raw(ops={"/device:TPU:0": ops},
                  modules={"/device:TPU:0": modules}, host=host)


def test_busy_is_the_union_clipped_to_the_window():
    s = TR.reduce(_raw())
    assert s.window_s == pytest.approx(0.1)
    # [10, 40] + [50, 62] + [95, 100] ms
    assert s.busy_s == pytest.approx(0.047)
    assert s.chips == 1


def test_time_by_program_and_kernel():
    s = TR.reduce(_raw())
    assert s.program_s["jit_prefill"] == pytest.approx(0.030)
    assert s.program_s["jit__generate_step"] == pytest.approx(0.017)
    assert s.time_matching(r"fused_decode_matmul(\.\d+)?") == \
        pytest.approx(0.010)
    assert s.time_matching(r"grouped_fused_decode_matmul(\.\d+)?") == \
        pytest.approx(0.002)
    # self time: the while loop less the fusions nested in it
    assert s.op_s["fusion.1"] == pytest.approx(0.010)
    assert s.op_s["fusion.2"] == pytest.approx(0.015)
    assert s.op_s["while.1"] == pytest.approx(0.005)
    assert TR.short_name("jit_prefill(123)") == "jit_prefill"


def test_idle_gaps_are_named_by_the_innermost_host_span():
    s = TR.reduce(_raw())
    # gaps: [0, 10] in PjitFunction, [40, 50] in bench.step,
    # [62, 95] mid 78.5 in bench.submit
    assert s.gaps == [("bench.submit", pytest.approx(0.033)),
                      ("PjitFunction(_generate_step)", pytest.approx(0.010)),
                      ("bench.step", pytest.approx(0.010))]
    b = s.breakdown(2)
    assert len(b["device_ops"]) == 2 and len(b["idle_gaps"]) == 2


def test_a_trace_without_a_window_or_device_ops_is_an_error():
    raw = _raw()
    with pytest.raises(ValueError):
        TR.reduce(TR.Raw(ops={}, modules={}, host=raw.host))
    with pytest.raises(ValueError):
        TR.reduce(TR.Raw(ops=raw.ops, modules=raw.modules, host={}))


def test_a_trace_recorded_on_the_chip():
    """Two decode ticks of internlm2-1.8b.chat on one TPU v5 lite, traced
    by ``run.py --trace 1`` (a 1 s window)."""
    raw = TR.load(spec.BENCH / "tests" / "data"
                  / "v5e_internlm2_decode.xplane.pb.gz")
    s = TR.reduce(raw)
    assert s.chips == 1
    assert s.window_s == pytest.approx(1.959815174)
    assert s.busy_s == pytest.approx(1.94821606)
    assert set(s.program_s) == {"jit__generate_step"}
    fused = s.time_matching(r"fused_decode_matmul(\.\d+)?")
    assert fused == pytest.approx(1.887878419)
    assert fused <= s.busy_s <= s.window_s
    # self times never exceed the busy time they are part of
    assert sum(s.op_s.values()) == pytest.approx(s.busy_s, rel=1e-3)
    assert s.gaps[0][0] == "$array.py:631 _value"
    assert sum(g for _, g in s.gaps) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-6)
