"""Launcher: what a rank spends meeting the others inside ``hvd.init()`` —
``jax.distributed.initialize`` (``hvd/init/distributed``: it returns when
every process has connected), the native library's load or build
(``hvd/init/native``) and the controller's connect with its retries
(``hvd/init/controller``) — on the slowest rank.  Only a launched cell
has them all."""

from .. import startup_record


def read(ctx):
    if ctx.get("launched_at") is None:
        return None
    return startup_record.slowest(
        ctx, lambda rec, cut: startup_record.seconds(
            rec, *startup_record.RENDEZVOUS))
