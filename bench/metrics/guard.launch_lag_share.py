"""Share of the traced window in which the device idles inside a
``guard.wait`` before the end of the program that its ``guard.call``
launched: the chip waiting for its own program to start
(``program_spans``)."""
from bench import program_spans


def read(ctx):
    s = program_spans.of_run(ctx)
    return None if s is None else s.share(s.launch_lag_s)
