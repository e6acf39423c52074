"""Compile cache: backend-compile seconds during set-up, from
``jax.monitoring`` (the slowest rank's; hits and misses are printed beside
it on the rank's own line)."""


def read(ctx):
    return max(r["setup"]["compile_s"] for r in ctx["ranks"])
