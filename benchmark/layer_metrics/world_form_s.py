"""Launcher: from the launch to the last rank past ``hvd.init()``, on one
host's ``time.time()``.  Only a launched cell has a launcher."""


def read(ctx):
    launched_at = ctx.get("launched_at")
    if launched_at is None:
        return None
    return max(r["world_formed_at"] for r in ctx["ranks"]) - launched_at
