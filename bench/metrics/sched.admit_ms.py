"""Median length of the ``serve.admit`` spans inside the traced window:
one queued request's prefill, first-token sampling and fragment insert
(``program_spans``)."""
from bench import program_spans


def read(ctx):
    s = program_spans.of_run(ctx)
    return None if s is None else s.admit_ms()
