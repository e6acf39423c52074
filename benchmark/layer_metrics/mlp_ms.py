"""Step programs: device time a step under the ``mlp`` scope (the dense
SwiGLU and the norm on its output, ``models/olmo_hybrid.py``), forward,
recomputed and backward.  A program without the scope yields nothing."""

from .. import trace_scopes

SCOPES = ("mlp",)


def read(ctx):
    s = trace_scopes.per_step(ctx, SCOPES)
    return None if s is None else s * 1e3
