"""Recurrent layers: device time a step under ``gdn/proj``, ``gdn/conv``,
``gdn/scan`` and ``gdn/out`` together — the whole Gated DeltaNet mixer
(``models/gated_delta.py``): its projections, the depthwise convolution,
the chunked delta rule and the gated output norm — forward, recomputed and
backward.  The counters of the fixed batch that set-up read (the share of
(token, head) pairs whose beta exceeds 1 and the largest beta, a layer;
chunks a sequence) go into the run's notes as ``beta_stats``.  A program
without these scopes yields nothing."""

from .. import trace_scopes

SCOPES = ("gdn/proj", "gdn/conv", "gdn/scan", "gdn/out")


def read(ctx):
    counters = (ctx["record"].get("kernel") or {}).get("counters")
    if counters:
        ctx.setdefault("notes", {})["beta_stats"] = counters
    s = trace_scopes.per_step(ctx, SCOPES)
    return None if s is None else s * 1e3
