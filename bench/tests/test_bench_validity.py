"""Each run-validity check passes the fused path and fails the rest."""
import pytest

from bench import validity as V


@pytest.mark.parametrize("dispatch,ok", [
    ({"fused": 3}, True), ({"fused": 3, "grouped_fused": 2}, True),
    ({"fused": 3, "unfused": 1}, False), ({"materialize": 1}, False),
    ({"fused_shard_map": 1}, False), ({}, False)])
def test_dispatch(dispatch, ok):
    assert V.check_dispatch(dispatch)[2] is ok


@pytest.mark.parametrize("kernels,ok", [
    ({"pallas": 9}, True), ({"pallas": 9, "ref": 1}, False),
    ({"interpret": 2}, False), ({}, False)])
def test_kernels(kernels, ok):
    assert V.check_kernels(kernels)[2] is ok


def test_fallbacks_rung_and_compiles():
    assert V.check_fallbacks({})[2]
    assert not V.check_fallbacks({"unfused": 1})[2]
    assert V.check_rung("fused")[2]
    assert not V.check_rung("unfused")[2] and not V.check_rung(None)[2]
    assert V.check_window_compiles(0)[2]
    assert not V.check_window_compiles(1)[2]


def test_request_moves():
    assert V.check_requests({"preempted": 0, "resumed": 0})[2]
    assert not V.check_requests({"preempted": 1})[2]
    checks = [V.check_rung("fused"), V.check_window_compiles(2)]
    assert [c[0] for c in V.failures(checks)] == ["window_compiles"]
