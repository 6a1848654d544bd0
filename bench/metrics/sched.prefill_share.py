"""Share of the device's operation time spent in prefill programs (the
jitted programs whose name holds ``prefill``), over the traced window."""


def read(ctx):
    t = ctx.trace
    if t is None:
        return None
    total = sum(t.program_s.values())
    if not total:
        return None
    prefill = sum(s for p, s in t.program_s.items() if "prefill" in p)
    return 100.0 * prefill / total
