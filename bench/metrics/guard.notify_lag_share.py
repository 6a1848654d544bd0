"""Share of the traced window in which the device idles inside a
``guard.wait`` after the program that its ``guard.call`` launched has
ended: the chip finished, the host not yet told (``program_spans``)."""
from bench import program_spans


def read(ctx):
    s = program_spans.of_run(ctx)
    return None if s is None else s.share(s.notify_lag_s)
