"""Step programs: device time a step under the ``attn/window`` scope — a
sliding-window attention layer's norm, projections, gate, rotary and the
attention kernels themselves (``models/laguna.py``) — forward, recomputed
and backward.  ``attn_ms`` reads the full layers' ``attn/full`` beside it.
A program without the scope yields nothing."""

from .. import trace_scopes

SCOPES = ("attn/window",)


def read(ctx):
    s = trace_scopes.per_step(ctx, SCOPES)
    return None if s is None else s * 1e3
