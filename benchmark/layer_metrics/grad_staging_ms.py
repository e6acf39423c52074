"""Optimizer and collectives: host time a step moving gradients into and
out of the engine's layout — the program's ``hvd/update/stage`` and
``hvd/update/unpack`` spans summed per update, median over the traced
updates (``program_spans.py``)."""

from .. import program_spans


def read(ctx):
    return program_spans.median_ms(ctx, "stage", "unpack")
