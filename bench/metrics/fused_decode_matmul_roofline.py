"""The fused decode->dequant->matmul kernel's share of its roofline: the
least time its calls in the window could take (ops at the bf16 peak or
plane and activation bytes at the HBM bandwidth, whichever is larger;
``costs.fused_matmul``) over the device time of its events in the trace."""
from bench import costs

# The kernel's ops in the device trace: fused_decode_matmul.<n> (the
# grouped expert kernel's are grouped_fused_decode_matmul.<n>).
PATTERN = r"fused_decode_matmul(\.\d+)?"


def read(ctx):
    t = ctx.trace
    if t is None or not ctx.fused_weights:
        return None
    seconds = t.time_matching(PATTERN)
    if not seconds:
        return None
    ops, byts = costs.fused_matmul(ctx.fused_weights, ctx.rows_per_call)
    return costs.roofline_share(ops, byts, seconds,
                                ctx.peaks["bf16_flops_per_s"],
                                ctx.peaks["hbm_bytes_per_s"])
