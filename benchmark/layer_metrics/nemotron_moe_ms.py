"""Expert layer: ``moe_ms`` as the ``nemotron_h`` family has it — device
time a step under ``moe/route``, ``moe/dispatch``, ``moe/experts`` and
``moe/combine`` and, with the experts in a latent, ``moe/latent`` (the
projection down and back that every chip computes); the shared expert is a
dense product beside it.  Forward, recomputed and backward; the TPU's
grouped matrix product is found by its own name.  The counter of the fixed
batch that set-up read (assignments held here of all, tokens per held
expert, assignments dropped) goes into the run's notes as ``expert_load``.
A name of its own because a metric has one list of cells and ``moe_ms``'s
may not be edited by the PR that adds a cell."""

from .. import trace_scopes
from .moe_ms import KERNELS
from .moe_ms import SCOPES as ROUTED

SCOPES = ROUTED + ("moe/latent",)


def read(ctx):
    counters = (ctx["record"].get("kernel") or {}).get("counters") or {}
    if counters.get("expert_load"):
        ctx.setdefault("notes", {})["expert_load"] = counters["expert_load"]
    s = trace_scopes.per_step(ctx, SCOPES, KERNELS)
    return None if s is None else s * 1e3
