"""Launcher: the first ``jax.devices()`` and with it the TPU client, the
program's ``hvd/init/backend`` span where ``hvd.init()`` opened the backend
(``fresh`` 1), on the slowest rank.  Where the script asked jax for its
devices first (``fresh`` 0) the client opened in the script, the span is a
mesh being built, and this is ``None`` and not a small number: the run's
notes hold ``script_before_init_s``."""

from .. import startup_record


def _fresh(rec, cut):
    span = startup_record.span(rec, "hvd/init/backend")
    return span["seconds"] if span and span.get("fresh") else None


def read(ctx):
    return startup_record.slowest(ctx, _fresh)
