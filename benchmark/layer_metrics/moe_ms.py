"""Expert layer: device time a step under ``moe/route``, ``moe/dispatch``,
``moe/experts`` and ``moe/combine`` (the routed part; the shared expert is
a dense product beside it), forward, recomputed and backward.  The TPU's
grouped matrix product is found by its own name (``trace_scopes``).  The
counters of the fixed batch that set-up read (assignments held here of
all, tokens per held expert, assignments dropped) go into the run's notes
as ``expert_load``."""

from .. import trace_scopes

SCOPES = ("moe/route", "moe/dispatch", "moe/experts", "moe/combine")
KERNELS = ("ragged-dot",)


def read(ctx):
    counters = (ctx["record"].get("kernel") or {}).get("counters")
    if counters:
        ctx.setdefault("notes", {})["expert_load"] = counters
    s = trace_scopes.per_step(ctx, SCOPES, KERNELS)
    return None if s is None else s * 1e3
