"""Step programs: device time a step under the ``attn/latent`` scope — a
main layer's latent attention whole (``models/joyai.py``: the norm, the two
low-rank paths, the rotary, the attention kernels over keys of 192 and
values of 128, ``W_o``) — forward, recomputed and backward.  The low-rank
paths lie under ``attn/latent/proj`` beneath it and are part of this
number; ``latent_proj_ms`` reads them alone.  The prediction module's
attention is ``mtp_ms``'s (the family's ``scopes`` table gives an
instruction to ``mtp`` first).  A program without the scope yields
nothing."""

from .. import trace_scopes

SCOPES = ("attn/latent", "attn/latent/proj")


def read(ctx):
    s = trace_scopes.per_step(ctx, SCOPES)
    return None if s is None else s * 1e3
