"""Share of the traced window in which the device idles while the serving
thread is inside a program span other than ``guard.wait``: the host
preparing inputs, dispatching, inserting, sampling or retiring
(``program_spans``)."""
from bench import program_spans


def read(ctx):
    s = program_spans.of_run(ctx)
    return None if s is None else s.share(s.idle_host_s)
