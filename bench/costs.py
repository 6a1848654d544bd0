"""Operations and bytes the served work needs, counted from shapes.

Counts are of what the algorithm needs, not of what a kernel happens to
do: real rows (no block padding), one read of each compressed plane per
call, attention over each token's real context.  A kernel that skips
wasted work therefore cannot read above its roofline.
"""
from __future__ import annotations


def model_ops(model, c, prefills, decode_contexts) -> float:
    """Operations of the model over a window: a prefill of L tokens runs
    the layers on L tokens, causal attention over 1..L positions and the
    LM head once (its first token); each decoded token runs the layers,
    the head and attention over its context."""
    p = model.matmul_params(c)
    head = c["vocab_size"] * c["hidden_size"]
    n, heads, dqk, dv = model.attention(c)
    per_pos = 2 * n * heads * (dqk + dv)
    ops = 0.0
    for length in prefills:
        ops += 2.0 * p * length + 2.0 * head \
            + per_pos * length * (length + 1) / 2
    for ctx in decode_contexts:
        ops += 2.0 * (p + head) + per_pos * ctx
    return ops


def fused_matmul(weights, rows_per_call):
    """(ops, bytes) of the compressed matmuls over a set of forward
    calls.  weights: [(N, K, plane_bytes)], one entry per weight and layer
    the fused kernel multiplies; rows_per_call: the real rows M of each
    call (a prefill's prompt length, a decode tick's active requests).
    A call reads every plane once and its bf16 activations in and out."""
    ops = byts = 0.0
    for m in rows_per_call:
        for n, k, planes in weights:
            ops += 2.0 * m * n * k
            byts += planes + 2.0 * m * (n + k)
    return ops, byts


def roofline_share(ops, byts, seconds, flops_per_s, bytes_per_s):
    """Least time the chip could take over the time taken, in %."""
    if not seconds:
        return None
    return 100.0 * max(ops / flops_per_s, byts / bytes_per_s) / seconds
