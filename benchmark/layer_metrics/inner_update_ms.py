"""Optimizer and collectives: the wrapped optimizer's own update, eager
dispatches and all — the program's ``hvd/update/inner`` span, median over
the traced updates (``program_spans.py``)."""

from .. import program_spans


def read(ctx):
    return program_spans.median_ms(ctx, "inner")
