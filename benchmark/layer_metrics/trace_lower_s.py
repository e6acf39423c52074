"""Compile cache: seconds tracing to a jaxpr and lowering to a module
before the window, which the persistent cache does not save — the compile
ledger's ``trace_s`` + ``lower_s`` over the programs whose first stage
began before the window's start, on the slowest rank."""

from .. import startup_record


def _seconds(rec, cut):
    before = startup_record.ledger_before(rec, cut)
    return before and float(before["trace_s"] + before["lower_s"])


def read(ctx):
    return startup_record.slowest(ctx, _seconds)
