"""Step programs: device time a step under ``attn/latent/proj`` — the two
low-rank paths of a main layer's latent attention
(``models/latent_attention.py``: the down-projections to ranks 1536 and
512 + 64, their norms, the up-projections, the rotary, and assembling ``q``
and ``k`` of 192 a head with the one rotary key laid beside every head's
keys) — forward, recomputed and backward: the part of ``attn_latent_ms``
that is no kernel's and not ``W_o``'s.  A program without the scope yields
nothing."""

from .. import trace_scopes

SCOPES = ("attn/latent/proj",)


def read(ctx):
    s = trace_scopes.per_step(ctx, SCOPES)
    return None if s is None else s * 1e3
