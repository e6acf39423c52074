"""Step programs: device busy time per traced step."""


def read(ctx):
    t = ctx["trace"]
    return t["busy_s"] / t["steps"] * 1e3 if t else None
