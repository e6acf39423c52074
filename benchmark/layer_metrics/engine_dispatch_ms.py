"""Eager engine: fused-program fetch or build and launch on the cycle
thread — the ``hvd/cycle/dispatch`` spans inside the traced window,
summed, over the traced steps (``program_spans.py``)."""

from .. import program_spans


def read(ctx):
    return program_spans.per_step_ms(ctx, "dispatch_s")
