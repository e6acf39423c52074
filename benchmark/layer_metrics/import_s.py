"""Launcher: from the OS's start of a process to the last line of
``horovod_tpu/__init__.py`` — the program's ``hvd/process`` (the
interpreter, the script's imports before the package, jax among them) and
``hvd/import`` spans — along the launch's critical path: the slowest
rank's and, where launched, the launcher's own before it."""

from .. import startup_record


def read(ctx):
    found = startup_record.load(ctx)
    return startup_record.import_seconds(found) if found else None
