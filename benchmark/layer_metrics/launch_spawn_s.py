"""Launcher: ``runner/run.py`` ``main`` entered to its last worker spawned,
the program's ``hvd/launch`` span (argument and host parsing, ports, a chip
a worker, the ``Popen``s) from the launcher's own start-up record.  Only a
launched cell has a launcher."""

from .. import startup_record


def read(ctx):
    found = startup_record.load(ctx)
    if not found or not found["launcher"]:
        return None
    return startup_record.seconds(found["launcher"], "hvd/launch")
