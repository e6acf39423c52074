"""Eager engine: the calling thread blocked on the engine, first to last
``synchronize`` — the program's ``hvd/update/wait`` span, median over the
traced updates (``program_spans.py``)."""

from .. import program_spans


def read(ctx):
    return program_spans.median_ms(ctx, "wait")
