"""Operations and bytes against counts made by hand."""
import pytest

from bench import costs, spec

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_one_projection():
    # internlm2 wq, 2048 x 2048, one decode tick of 8 rows, 1 MiB of planes
    ops, byts = costs.fused_matmul([(2048, 2048, 2**20)], [8])
    assert ops == 2 * 8 * 2048 * 2048
    assert byts == 2**20 + 2 * 8 * (2048 + 2048)


def test_an_expert_stack_and_several_calls():
    # three matrices of one expert (gate, up: 1408 x 2048; down: 2048 x
    # 1408) over a 128-row prefill and an 8-row tick
    w = [(1408, 2048, 100), (1408, 2048, 100), (2048, 1408, 50)]
    ops, byts = costs.fused_matmul(w, [128, 8])
    assert ops == 2 * (128 + 8) * 3 * 1408 * 2048
    assert byts == 2 * 250 + 2 * (128 + 8) * 3 * (1408 + 2048)


def test_roofline_share_takes_the_larger_bound():
    # 197 GFLOP in 2 ms: the compute bound (1 ms) is half of it
    assert costs.roofline_share(197e9, 1.0, 2e-3, **_peaks()) == \
        pytest.approx(50.0)
    # 819 MB in 4 ms: the memory bound (1 ms) is a quarter
    assert costs.roofline_share(1.0, 819e6, 4e-3, **_peaks()) == \
        pytest.approx(25.0)
    assert costs.roofline_share(1.0, 1.0, 0.0, **_peaks()) is None


def _peaks():
    return {"flops_per_s": PEAKS["bf16_flops_per_s"],
            "bytes_per_s": PEAKS["hbm_bytes_per_s"]}


def test_model_ops_internlm2_by_hand():
    c = spec.config("internlm2-1.8b")
    m = spec.model("internlm2")
    d, ff, L, v = 2048, 8192, 24, 92544
    per_layer = 2048 * 2048 * 2 + 2048 * 1024 * 2 + 3 * d * ff
    assert m.matmul_params(c) == L * per_layer
    att = 2 * L * 16 * (128 + 128)            # per attended position
    want = (2 * L * per_layer * 4 + 2 * v * d + att * (4 * 5 / 2)
            + 2 * (L * per_layer + v * d) + att * 9)
    assert costs.model_ops(m, c, [4], [9]) == pytest.approx(want)


def test_model_ops_deepseek_counts_active_experts_only():
    c = spec.config("deepseek-v2-lite-16b")
    m = spec.model("deepseek_v2")
    d = 2048
    attn = d * 16 * 192 + d * (512 + 64) + 512 * 16 * 256 + 16 * 128 * d
    moe = d * 64 + 3 * d * 1408 * 6 + 3 * d * 2816
    assert m.matmul_params(c) == 5 * attn + 3 * d * 10944 + 4 * moe
    assert m.attention(c) == (5, 16, 192, 128)
