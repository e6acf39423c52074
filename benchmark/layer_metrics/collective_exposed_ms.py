"""Optimizer and collectives: device time of collectives per step during
which no other operation ran on that device (mesh index 0's trace)."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t.get("collective_s"):
        return None
    return t["collective_exposed_s"] / t["steps"] * 1e3
