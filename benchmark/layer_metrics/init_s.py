"""Launcher: the whole of ``hvd.init()``, the program's ``hvd/init`` span,
on the slowest rank."""

from .. import startup_record


def read(ctx):
    return startup_record.slowest(
        ctx, lambda rec, cut: startup_record.seconds(rec, "hvd/init"))
