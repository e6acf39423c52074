"""Recurrent layers: device time a step under ``ssm/proj``, ``ssm/conv``,
``ssm/scan`` and ``ssm/out`` together — the whole Mamba-2 mixer
(``models/mamba2.py``): its projections, the depthwise convolution, the
chunked state-space recurrence and the gated group norm — forward,
recomputed and backward.  The counter of the fixed batch that set-up read
(a Mamba layer: the share of (chunk, head) pairs whose decay across the
chunk exceeds 0.01, the smallest and largest per-token decay; chunks a
sequence) goes into the run's notes as ``decay_stats``.  A program without
these scopes yields nothing."""

from .. import trace_scopes

SCOPES = ("ssm/proj", "ssm/conv", "ssm/scan", "ssm/out")


def read(ctx):
    counters = (ctx["record"].get("kernel") or {}).get("counters") or {}
    if counters.get("decay_stats"):
        ctx.setdefault("notes", {})["decay_stats"] = counters["decay_stats"]
    s = trace_scopes.per_step(ctx, SCOPES)
    return None if s is None else s * 1e3
