"""Device: 1 - busy union over the traced window."""


def read(ctx):
    return ctx["trace"]["idle_pct"] if ctx["trace"] else None
