"""The whole step's share of the chip's bf16 peak: operations the window's
tokens need (``costs.model_ops``: prefills and decoded tokens, attention
over their real context) over the window's length on the host clock."""
from bench import costs


def read(ctx):
    peak = ctx.peaks.get("bf16_flops_per_s")
    span = ctx.summary["span_s"]
    if not peak or not span:
        return None
    ops = costs.model_ops(ctx.model, ctx.config,
                          ctx.ledger.window_prefills(),
                          ctx.ledger.context_lengths())
    return 100.0 * ops / span / peak
