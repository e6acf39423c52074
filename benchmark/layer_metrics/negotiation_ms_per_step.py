"""Eager engine: wall time a step the cycle thread spent negotiating while
a tensor waited for the verdict — the ``hvd/cycle/negotiate`` spans of the
cycles with ``n`` > 0 inside the traced window, summed, over the traced
steps.  Empty lock-step rounds hold nobody up and are left out; a world
that never negotiates (one process, no controller) reads 0
(``program_spans.py``)."""

from .. import program_spans


def read(ctx):
    return program_spans.per_step_ms(ctx, "negotiate_s")
