"""Recurrent layers: device time a step under ``gdn/conv`` and ``ssm/conv``
— the depthwise causal convolution and SiLU in front of every recurrence
(``models/gated_delta.py`` ``causal_conv_silu``: the Pallas kernel pair of
``ops/causal_conv.py`` on a TPU where the shape fits its tiles, XLA's code
elsewhere), forward, recomputed and backward.  A family's ``SCOPES`` table
holds the one its mixer has; a program without either yields nothing."""

from .. import trace_scopes

SCOPES = ("gdn/conv", "ssm/conv")


def read(ctx):
    s = trace_scopes.per_step(ctx, SCOPES)
    return None if s is None else s * 1e3
