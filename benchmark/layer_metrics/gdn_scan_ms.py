"""Recurrent layers: device time a step under the ``gdn/scan`` scope (the
chunked gated delta rule of the Gated DeltaNet layers), forward, recomputed
and backward."""

from .. import trace_scopes

SCOPES = ("gdn/scan",)


def read(ctx):
    s = trace_scopes.per_step(ctx, SCOPES)
    return None if s is None else s * 1e3
