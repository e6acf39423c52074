"""A Qwen3-Next-style hybrid decoder step (Gated DeltaNet layers, gated
attention, a dropless expert layer told which experts it holds) through
horovod_tpu's public entry points, built for one mix: ``families/llama.py``
with another model.

``qwen3_next.make_train_step`` wrapped in ``shard_map`` over ``hvd.mesh()``
with ``hvd.DistributedOptimizer(optax.adam, op=Average, axis_name="hvd")``,
state donated; the full-attention layer takes the program's own route (the
Pallas flash kernel on a TPU).  The weights and the fixed batch come from
the benchmark's own generator (``reference/qwen3_next.py``), made on the
device from the seed in one jitted call, in the configuration's type.

Set-up also routes the fixed batch once through the seed's weights and
counts, layer by layer, the assignments that land on the experts held here
(``qwen3_next.expert_load``): the counters of the ``kernel`` record, from
which the operations of the share's step follow.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

from horovod_tpu.compat import shard_map
from horovod_tpu.models import qwen3_next

from .. import trace_scopes
from ..reference import qwen3_next as data
from ..reference.common import mesh_batch

# the named scopes of the program that the per-layer readers sum
SCOPES = ("gdn/proj", "gdn/conv", "gdn/scan", "gdn/out", "attn/full",
          "moe/route", "moe/dispatch", "moe/experts", "moe/shared",
          "moe/combine", "head")


def config_of(sizes):
    return qwen3_next.Qwen3NextConfig(
        vocab_size=sizes["vocab_size"], d_model=sizes["hidden_size"],
        n_layers=sizes["num_hidden_layers"],
        full_attention_interval=sizes["full_attention_interval"],
        n_heads=sizes["num_attention_heads"],
        n_kv_heads=sizes["num_key_value_heads"], head_dim=sizes["head_dim"],
        partial_rotary_factor=sizes["partial_rotary_factor"],
        rope_theta=sizes["rope_theta"],
        lin_k_heads=sizes["linear_num_key_heads"],
        lin_v_heads=sizes["linear_num_value_heads"],
        lin_k_dim=sizes["linear_key_head_dim"],
        lin_v_dim=sizes["linear_value_head_dim"],
        conv_kernel=sizes["linear_conv_kernel_dim"],
        chunk=sizes["chunk"], n_experts=sizes["num_experts_published"],
        top_k=sizes["num_experts_per_tok"],
        d_expert=sizes["moe_intermediate_size"],
        d_shared=sizes["shared_expert_intermediate_size"],
        first_expert=sizes["first_expert"],
        experts_held=sizes["num_experts"], norm_eps=sizes["rms_norm_eps"],
        dtype=jnp.dtype(sizes["dtype"]), use_flash=sizes.get("use_flash"))


# ------------------------------------------- operations and bytes, by shape
def layer_kinds(sizes):
    """(Gated DeltaNet layers, full-attention layers)."""
    full = sum(data.is_full_attention(i, sizes)
               for i in range(sizes["num_hidden_layers"]))
    return sizes["num_hidden_layers"] - full, full


def dense_matmul_params(sizes):
    """Matmul parameters every token meets in a step (the embedding is a
    lookup, the routed experts are counted from the assignments)."""
    d = sizes["hidden_size"]
    h, kv, hd = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                 sizes["head_dim"])
    hk, hv, dk, dv = (sizes["linear_num_key_heads"],
                      sizes["linear_num_value_heads"],
                      sizes["linear_key_head_dim"],
                      sizes["linear_value_head_dim"])
    gdn = d * (2 * hk * dk + 2 * hv * dv + 2 * hv) + hv * dv * d
    attn = d * (2 * h * hd + 2 * kv * hd) + h * hd * d
    expert_layer = (d * sizes["num_experts_published"] + d
                    + 3 * d * sizes["shared_expert_intermediate_size"])
    gdn_layers, full_layers = layer_kinds(sizes)
    return (gdn_layers * gdn + full_layers * attn
            + sizes["num_hidden_layers"] * expert_layer
            + d * sizes["vocab_size"])


def expert_params(sizes):
    """One routed expert's matmul parameters."""
    return 3 * sizes["hidden_size"] * sizes["moe_intermediate_size"]


def attention_flops(sizes):
    """The full layers' attention products of one sequence's step, forward
    (4 per causal pair and head dimension) and backward (8)."""
    t = sizes["seq_len"]
    return (12.0 * (t * (t + 1) // 2) * sizes["head_dim"]
            * sizes["num_attention_heads"] * layer_kinds(sizes)[1])


def gdn_scan_flops(sizes):
    """The chunked delta rule's products for one sequence's step, forward
    and backward (twice the forward).  Per chunk of C tokens and head,
    forward: K K^T and Q K^T (2 C^2 dk each), the unit-triangular solve
    against [V | K] (C^2 (dk + dv)), W S, K^T V' and Q S (2 C dk dv each),
    and the in-chunk attention times V' (2 C^2 dv)."""
    c, dk, dv = (sizes["chunk"], sizes["linear_key_head_dim"],
                 sizes["linear_value_head_dim"])
    chunk = c * c * (5 * dk + 3 * dv) + 6 * c * dk * dv
    chunks = -(-sizes["seq_len"] // c)
    return (3.0 * chunk * chunks * sizes["linear_num_value_heads"]
            * layer_kinds(sizes)[0])


def gdn_scan_bytes(sizes):
    """Least HBM traffic of the delta rule for one sequence's step: q and
    k (at the key heads), v, g and beta read and o written forward; those
    and do read, dq, dk, dv, dg and dbeta written backward."""
    item = jnp.dtype(sizes["dtype"]).itemsize
    hk, hv = sizes["linear_num_key_heads"], sizes["linear_num_value_heads"]
    qk = 2 * hk * sizes["linear_key_head_dim"] * item
    v = hv * sizes["linear_value_head_dim"] * item
    gates = 2 * hv * 4
    token = (qk + v + gates + v) + (qk + v + gates + v) + (qk + v + gates)
    return float(token * sizes["seq_len"] * layer_kinds(sizes)[0])


def model_flops_per_item(sizes, held_assignments_per_token):
    """Forward plus backward of this share's step for one token: 6 per
    matmul parameter it meets (the routed experts by the assignments that
    land here, summed over the layers), the attention pairs and the delta
    rule's products; a multiply-add is 2, nothing recomputed."""
    matmul = dense_matmul_params(sizes) + (held_assignments_per_token
                                           * expert_params(sizes))
    return 6.0 * matmul + (attention_flops(sizes)
                           + gdn_scan_flops(sizes)) / sizes["seq_len"]


def expert_bytes(sizes):
    """The held experts' weights read forward and backward and their
    gradient written, a step."""
    item = jnp.dtype(sizes["dtype"]).itemsize
    return float(3 * sizes["num_hidden_layers"] * sizes["num_experts"]
                 * expert_params(sizes) * item)


def counters(counts, tokens, sizes):
    """The counters of the fixed batch from ``expert_load``'s ``[layers,
    experts held]``."""
    counts = np.asarray(counts, np.int64)
    made = tokens * sizes["num_experts_per_tok"]    # a layer; its buffer
    return {"assignments": int(made * counts.shape[0]),
            "assignments_held": int(counts.sum()),
            "held_share": float(counts.sum() / (made * counts.shape[0])),
            "tokens_per_held_expert": {
                "least": int(counts.min()), "mean": float(counts.mean()),
                "most": int(counts.max())},
            "assignments_dropped": int(sum(
                max(0, int(layer.sum()) - made) for layer in counts))}


def build(hvd, cell, key, annotate):
    sizes = cell.sizes
    if cell.mix["step_mode"] != "spmd":
        raise SystemExit("benchmark: the qwen3_next family has the spmd "
                         "step only")
    cfg = config_of(sizes)
    weights = jax.jit(lambda k: data.init_weights(k, sizes))
    params = hvd.broadcast_parameters(weights(key), root_rank=0)
    adam = data.ADAM
    optimizer = hvd.DistributedOptimizer(
        optax.adam(adam["lr"], b1=adam["b1"], b2=adam["b2"], eps=adam["eps"]),
        op=hvd.Average, axis_name="hvd")
    mesh = hvd.mesh()
    batch = mesh_batch(data.make_batch, key, sizes, mesh, P("hvd"))
    counted = counters(
        jax.jit(lambda p, t: qwen3_next.expert_load(p, t, cfg))(
            params, batch[0]), batch[0].size, sizes)
    if counted["assignments_dropped"]:
        raise SystemExit(f"benchmark: the expert layer dropped "
                         f"{counted['assignments_dropped']} assignments")
    state = (params, optimizer.init(params))
    compiled = jax.jit(shard_map(
        qwen3_next.make_train_step(cfg, optimizer), mesh=mesh,
        in_specs=(P(), P(), P("hvd"), P("hvd")),
        out_specs=(P(), P(), P()), check_vma=False),
        donate_argnums=(0, 1)).lower(*state, *batch).compile()

    def step(state, batch):
        with annotate("bench/enqueue"):
            *state, loss = compiled(*state, *batch)
        return tuple(state), loss

    b1 = adam["b1"]
    sequences = sizes["batch_per_chip"]
    held_per_chip = counted["assignments_held"] / mesh.size
    return {
        "step": step, "state": state, "batch": batch,
        "items_per_step_per_chip": sequences * sizes["seq_len"],
        "flops_per_item": model_flops_per_item(
            sizes, held_per_chip / (sequences * sizes["seq_len"])),
        "params_of": lambda s: s[0],
        # Adam's first moment after one step is (1 - b1) times the
        # gradient the optimizer was given.
        "first_gradient_of": lambda s: jax.tree_util.tree_map(
            lambda m: m.astype(jnp.float32) / (1.0 - b1),
            s[1].inner_state[0].mu),
        "seed_params": lambda: weights(key),
        "temp_bytes": int(compiled.memory_analysis().temp_size_in_bytes),
        "kernel": {
            "gdn_scan": {"flops_per_step": gdn_scan_flops(sizes) * sequences,
                         "bytes_per_step": gdn_scan_bytes(sizes) * sequences},
            "experts": {"flops_per_step": 6.0 * expert_params(sizes)
                        * held_per_chip,
                        "bytes_per_step": expert_bytes(sizes)},
            "counters": counted,
            "scopes": trace_scopes.within(SCOPES, compiled.as_text()),
        },
    }
