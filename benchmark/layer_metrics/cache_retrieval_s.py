"""Compile cache: seconds reading hits back from the persistent cache
before the window (the compile ledger's ``retrieval_s``; ``compile_s``
counts them as compile time), on the slowest rank."""

from .. import startup_record


def _seconds(rec, cut):
    before = startup_record.ledger_before(rec, cut)
    return before and float(before["retrieval_s"])


def read(ctx):
    return startup_record.slowest(ctx, _seconds)
