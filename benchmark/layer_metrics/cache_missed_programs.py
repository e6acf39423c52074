"""Compile cache: backend compiles before the window that the persistent
cache did not serve — the compile ledger's requests that did not hit
(``asked_cache - hits``, counted on every process, those that write
nothing too) plus the compiles that never asked — on the rank with the
most."""

from .. import startup_record


def _count(rec, cut):
    before = startup_record.ledger_before(rec, cut)
    return before and float(before["missed"] + before["never_asked"])


def read(ctx):
    return startup_record.slowest(ctx, _count)
