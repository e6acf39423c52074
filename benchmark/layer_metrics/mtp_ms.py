"""Step programs: device time a step under the ``mtp`` scope — everything
of the multi-token-prediction module (``models/joyai.py``: the two norms
and ``W_eh``, the module's own latent attention and expert layer, its last
norm and its pass through the main model's head), forward, recomputed and
backward.  The family's ``scopes`` table gives an instruction to ``mtp``
before any other scope, so ``attn_latent_ms``, ``moe_ms``, ``mlp_ms`` and
``head_ms`` are the main layers' alone — but for the grouped matrix
products, which the TPU's compiler names itself (``ragged-dot``): those of
the module's expert layer are read with the main layers' by ``moe_ms`` and
are not in this number.  A program without the scope yields nothing."""

from .. import trace_scopes

SCOPES = ("mtp",)


def read(ctx):
    s = trace_scopes.per_step(ctx, SCOPES)
    return None if s is None else s * 1e3
