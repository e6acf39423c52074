"""Recurrent layers: device time a step under the ``ssm/scan`` scope (the
chunked state-space recurrence of the Mamba-2 layers, ``mamba2.chunked_ssd``,
with the skip ``D x``), forward, recomputed and backward."""

from .. import trace_scopes

SCOPES = ("ssm/scan",)


def read(ctx):
    s = trace_scopes.per_step(ctx, SCOPES)
    return None if s is None else s * 1e3
