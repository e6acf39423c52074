"""A Jamba-style hybrid decoder step (Mamba-1 state-space layers whose
recurrence is the selective scan, an attention layer once a period on one
key-value head, a dense SwiGLU a layer, a head tied to the embedding)
through horovod_tpu's public entry points, built for one mix:
``families/nemotron_h.py`` with another model.

``jamba.make_train_step`` wrapped in ``shard_map`` over ``hvd.mesh()``
with ``hvd.DistributedOptimizer(optax.adam, op=Average, axis_name="hvd")``,
state donated; the attention layer takes the program's own route (the
Pallas flash kernel on a TPU), the convolution and the scan theirs (the
kernels of ``ops/causal_conv.py`` and ``ops/selective_scan.py`` on a TPU
where their tiles fit).  The weights and the fixed batch come from the
benchmark's own generator (``reference/jamba.py``), made on the device from
the seed in one jitted call, in the configuration's type.

Set-up also runs the fixed batch once through the seed's weights and reads
a counter of the ``kernel`` record, a Mamba layer: the share of (token,
channel, state) triples whose decay over one token, ``exp(delta A)``, is
under 0.5, and the smallest and largest step ``delta``
(``jamba.decay_stats``).  A batch in which that share is under 0.05 or over
0.95 in any Mamba layer is refused: a state that never or always forgets
makes the recurrence trivial.  Beside it go the scan's call sites of the
step that took the kernels and those that kept the plain formulation
(``trace.selective_scan``, read round the step's tracing).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

from horovod_tpu import trace
from horovod_tpu.compat import shard_map
from horovod_tpu.models import jamba

from .. import trace_scopes
from ..reference import jamba as data
from ..reference.common import mesh_batch

# the named scopes of the program that the per-layer readers sum
SCOPES = ("ssm/proj", "ssm/conv", "ssm/scan", "ssm/out", "attn/full", "mlp",
          "head")
FORGETTING_SHARE = (0.05, 0.95)


def config_of(sizes):
    return jamba.JambaConfig(
        vocab_size=sizes["vocab_size"], d_model=sizes["hidden_size"],
        n_layers=sizes["num_hidden_layers"],
        attn_layer_period=sizes["attn_layer_period"],
        attn_layer_offset=sizes["attn_layer_offset"],
        expert_layer_period=sizes["expert_layer_period"],
        expert_layer_offset=sizes["expert_layer_offset"],
        num_experts=sizes["num_experts"], d_ff=sizes["intermediate_size"],
        mamba_expand=sizes["mamba_expand"],
        mamba_d_state=sizes["mamba_d_state"],
        mamba_d_conv=sizes["mamba_d_conv"],
        mamba_dt_rank=sizes["mamba_dt_rank"],
        n_heads=sizes["num_attention_heads"],
        n_kv_heads=sizes["num_key_value_heads"], head_dim=sizes["head_dim"],
        norm_eps=sizes["rms_norm_eps"], dtype=jnp.dtype(sizes["dtype"]),
        use_flash=sizes.get("use_flash"))


# ------------------------------------------- operations and bytes, by shape
def layer_kinds(sizes):
    """(Mamba layers, attention layers)."""
    attn = sum(data.is_attention(sizes, i)
               for i in range(sizes["num_hidden_layers"]))
    return sizes["num_hidden_layers"] - attn, attn


def matmul_params(sizes):
    """Matmul parameters every token meets in a step: the embedding is a
    lookup going in and a product coming out, so the tied matrix counts
    once."""
    d, f = sizes["hidden_size"], sizes["intermediate_size"]
    di, n, r, _ = data.mamba_dims(sizes)
    h, kv, hd = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                 sizes["head_dim"])
    ssm = d * 2 * di + di * (r + 2 * n) + r * di + di * d
    attn = d * (h + 2 * kv) * hd + h * hd * d
    ssm_layers, attn_layers = layer_kinds(sizes)
    return (ssm_layers * ssm + attn_layers * attn
            + sizes["num_hidden_layers"] * 3 * d * f
            + d * sizes["vocab_size"])


def attention_flops(sizes):
    """The attention layers' products of one sequence's step, forward (4
    per causal pair and head dimension) and backward (8): the scores
    recomputed in the backward pass do not count."""
    t = sizes["seq_len"]
    return (12.0 * (t * (t + 1) // 2) * sizes["head_dim"]
            * sizes["num_attention_heads"] * layer_kinds(sizes)[1])


def attention_bytes(sizes):
    """Least HBM traffic of the attention kernels for one sequence: q, k,
    v and the output read or written once forward; q, k, v, o, do read and
    dq, dk, dv written once backward (``families/llama.py``'s count)."""
    t, hd = sizes["seq_len"], sizes["head_dim"]
    q = t * hd * sizes["num_attention_heads"]
    k = t * hd * sizes["num_key_value_heads"]
    item = jnp.dtype(sizes["dtype"]).itemsize
    return float(item * layer_kinds(sizes)[1] * (6 * q + 6 * k))


def selective_scan_bytes(sizes):
    """Least HBM traffic of the selective scan for one sequence's step,
    from the shapes alone, whatever implements it: forward x, B and C read
    in the configuration's type, the step delta in float32, y written; the
    forward again where the layer is recomputed; backward those and dy
    read, dx, ddelta, dB and dC written."""
    item = jnp.dtype(sizes["dtype"]).itemsize
    di, n, _, _ = data.mamba_dims(sizes)
    x, bc, step = di * item, 2 * n * item, di * 4
    forward = x + step + bc + x
    backward = (x + step + bc + x) + (x + step + bc)
    return float((2 * forward + backward) * sizes["seq_len"]
                 * layer_kinds(sizes)[0])


def model_flops_per_item(sizes):
    """Forward plus backward of the step for one token: 6 per matmul
    parameter it meets and the attention pairs; a multiply-add is 2,
    nothing recomputed.  The selective scan's work is elementwise (about
    ten operations and an ``exp`` a (token, channel, state) triple and
    pass) and is **not** counted: model FLOPs here are matrix products,
    what ``mfu_pct`` holds against the MXU's peak."""
    return (6.0 * matmul_params(sizes)
            + attention_flops(sizes) / sizes["seq_len"])


def decay_stats(share, least, most):
    """The counter of the fixed batch from ``jamba.decay_stats``."""
    share = np.asarray(share, float)
    return {"token_decay_under_0.5_share": [float(s) for s in share],
            "delta_least": [float(a) for a in np.asarray(least, float)],
            "delta_most": [float(a) for a in np.asarray(most, float)],
            "least_share": float(share.min()),
            "most_share": float(share.max())}


def build(hvd, cell, key, annotate):
    sizes = cell.sizes
    if cell.mix["step_mode"] != "spmd":
        raise SystemExit("benchmark: the jamba family has the spmd step "
                         "only")
    cfg = config_of(sizes)
    weights = jax.jit(lambda k: data.init_weights(k, sizes))
    params = hvd.broadcast_parameters(weights(key), root_rank=0)
    adam = data.ADAM
    optimizer = hvd.DistributedOptimizer(
        optax.adam(adam["lr"], b1=adam["b1"], b2=adam["b2"], eps=adam["eps"]),
        op=hvd.Average, axis_name="hvd")
    mesh = hvd.mesh()
    batch = mesh_batch(data.make_batch, key, sizes, mesh, P("hvd"))
    decay = decay_stats(*jax.jit(
        lambda p, t: jamba.decay_stats(p, t, cfg))(params, batch[0]))
    low, high = FORGETTING_SHARE
    if not low <= decay["least_share"] <= decay["most_share"] <= high:
        raise SystemExit(
            f"benchmark: a token's decay is under 0.5 for "
            f"{decay['token_decay_under_0.5_share']} of the (token, "
            f"channel, state) triples a layer: outside {low}..{high} the "
            f"state never or always forgets and the recurrence is trivial")
    state = (params, optimizer.init(params))
    # the scan's call sites by the path each took, counted while the step
    # is traced
    before = dict(trace.selective_scan)
    compiled = jax.jit(shard_map(
        jamba.make_train_step(cfg, optimizer), mesh=mesh,
        in_specs=(P(), P(), P("hvd"), P("hvd")),
        out_specs=(P(), P(), P()), check_vma=False),
        donate_argnums=(0, 1)).lower(*state, *batch).compile()

    def step(state, batch):
        with annotate("bench/enqueue"):
            *state, loss = compiled(*state, *batch)
        return tuple(state), loss

    b1 = adam["b1"]
    sequences = sizes["batch_per_chip"]
    return {
        "step": step, "state": state, "batch": batch,
        "items_per_step_per_chip": sequences * sizes["seq_len"],
        "flops_per_item": model_flops_per_item(sizes),
        "params_of": lambda s: s[0],
        # Adam's first moment after one step is (1 - b1) times the
        # gradient the optimizer was given.  Divided in float32 and kept in
        # the moment's own type, in one program: a float32 copy of 1.6 B
        # moments is 6.4 GB beside 9.6 GB of state and does not fit twice.
        "first_gradient_of": jax.jit(lambda s: jax.tree_util.tree_map(
            lambda m: (m.astype(jnp.float32) / (1.0 - b1)).astype(m.dtype),
            s[1].inner_state[0].mu)),
        "seed_params": lambda: weights(key),
        "temp_bytes": int(compiled.memory_analysis().temp_size_in_bytes),
        "kernel": {
            # the attention kernels, as ``jamba_flash_roofline`` reads them
            "flops_per_step": attention_flops(sizes) * sequences,
            "bytes_per_step": attention_bytes(sizes) * sequences,
            # no matrix operation: the bound is the HBM's
            "selective_scan": {
                "flops_per_step": 0.0,
                "bytes_per_step": selective_scan_bytes(sizes) * sequences},
            "counters": {"decay_stats": decay, "selective_scan": {
                path: trace.selective_scan[path] - n
                for path, n in before.items()}},
            "scopes": trace_scopes.within(SCOPES, compiled.as_text()),
        },
    }
