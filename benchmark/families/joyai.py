"""A JoyAI-LLM-Flash-style expert decoder step (latent attention whose keys
are 192 wide and whose values 128, a leading dense layer, a dropless expert
layer told which experts it holds and whose selection bias the step moves,
a multi-token-prediction module that reads the embedding and the head a
second time) through horovod_tpu's public entry points, built for one mix:
``families/laguna.py`` with another model.

``joyai.make_train_step`` wrapped in ``shard_map`` over ``hvd.mesh()`` with
``hvd.DistributedOptimizer(optax.adam, op=Average, axis_name="hvd")``, state
donated, the experts' counts summed over the same axis; every attention
takes the program's own route (the Pallas flash kernels on a TPU).  The
weights and the fixed batch come from the benchmark's own generator
(``reference/joyai.py``), made on the device from the seed in one jitted
call, in the configuration's type; the generator lays the attention's
columns out as published, and ``joyai.from_published`` permutes them to the
program's order in the same call (no norm of a leaf sees a permutation).

Set-up also routes the fixed batch once through the seed's weights and
counts, expert layer by expert layer (the module's last), the assignments
that land on the experts held here (``joyai.expert_load``), from which the
operations of the share's step follow; beside them go the attention call
sites of the step by path (``trace.attention``, read round the step's
tracing: ``latent_plain`` above 0 on the chip is a fall to the plain path),
and, when the harness first asks for the parameters (after the followed
steps), what the steps made of the selection bias: its largest magnitude's
change and how many experts it raised and lowered.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

from horovod_tpu import trace
from horovod_tpu.compat import shard_map
from horovod_tpu.models import joyai, latent_attention

from .. import trace_scopes
from ..reference import joyai as data
from ..reference.common import mesh_batch
from .llama import attended_pairs
from .qwen3_next import counters

# the named scopes of the program that the per-layer readers sum; an
# instruction goes to the first that its op_name holds, so everything of the
# prediction module is ``mtp``'s and the others are the main layers' alone
SCOPES = ("mtp", "attn/latent/proj", "attn/latent", "mlp", "moe/route",
          "moe/dispatch", "moe/experts", "moe/shared", "moe/combine", "head")


def dims_of(sizes):
    return latent_attention.LatentDims(
        d_model=sizes["hidden_size"], n_heads=sizes["num_attention_heads"],
        q_rank=sizes["q_lora_rank"], kv_rank=sizes["kv_lora_rank"],
        d_nope=sizes["qk_nope_head_dim"], d_rope=sizes["qk_rope_head_dim"],
        d_v=sizes["v_head_dim"], rope_theta=float(sizes["rope_theta"]),
        norm_eps=sizes["rms_norm_eps"])


def config_of(sizes):
    if sizes["qk_head_dim"] != (sizes["qk_nope_head_dim"]
                                + sizes["qk_rope_head_dim"]):
        raise SystemExit("benchmark: qk_head_dim is not qk_nope_head_dim + "
                         "qk_rope_head_dim")
    if (sizes["scoring_func"], sizes["n_group"], sizes["topk_group"]) != (
            "sigmoid", 1, 1) or not sizes["norm_topk_prob"]:
        raise SystemExit("benchmark: the joyai family routes by sigmoid "
                         "scores in one group, the chosen renormalised")
    return joyai.JoyAIConfig(
        vocab_size=sizes["vocab_size"], n_layers=sizes["num_hidden_layers"],
        first_dense=sizes["first_k_dense_replace"], attn=dims_of(sizes),
        d_ff=sizes["intermediate_size"],
        n_experts=sizes["n_routed_experts_published"],
        top_k=sizes["num_experts_per_tok"],
        routed_scale=sizes["routed_scaling_factor"],
        d_expert=sizes["moe_intermediate_size"],
        d_shared=sizes["n_shared_experts"] * sizes["moe_intermediate_size"],
        first_expert=sizes["first_expert"],
        experts_held=sizes["n_routed_experts"],
        mtp_modules=sizes["num_nextn_predict_layers"],
        mtp_weight=sizes["mtp_loss_weight"],
        bias_speed=sizes["bias_update_speed"],
        norm_eps=sizes["rms_norm_eps"], dtype=jnp.dtype(sizes["dtype"]),
        use_flash=sizes.get("use_flash"))


# ------------------------------------------- operations and bytes, by shape
def attention_calls(sizes):
    """Attention blocks of a step: the main layers' and the module's."""
    return sizes["num_hidden_layers"] + sizes["num_nextn_predict_layers"]


def expert_layers(sizes):
    """Expert layers of a step: the main stack's and the module's."""
    return data.sparse_layers(sizes) + sizes["num_nextn_predict_layers"]


def attention_params(sizes):
    """One attention block's matmul parameters."""
    d, h = sizes["hidden_size"], sizes["num_attention_heads"]
    rq, rkv = sizes["q_lora_rank"], sizes["kv_lora_rank"]
    dn, dr, dv = (sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"],
                  sizes["v_head_dim"])
    return (d * rq + rq * h * (dn + dr) + d * (rkv + dr)
            + rkv * h * (dn + dv) + h * dv * d)


def dense_matmul_params(sizes):
    """Matmul parameters every token meets in a step (the embedding is a
    lookup, the routed experts are counted from the assignments): the head
    twice, ``W_eh`` once."""
    d = sizes["hidden_size"]
    shared = sizes["n_shared_experts"] * sizes["moe_intermediate_size"]
    module = sizes["num_nextn_predict_layers"]
    return (attention_calls(sizes) * attention_params(sizes)
            + sizes["first_k_dense_replace"] * 3 * d
            * sizes["intermediate_size"]
            + expert_layers(sizes) * d * (
                sizes["n_routed_experts_published"] + 3 * shared)
            + (1 + module) * d * sizes["vocab_size"] + module * 2 * d * d)


def expert_params(sizes):
    """One routed expert's matmul parameters."""
    return 3 * sizes["hidden_size"] * sizes["moe_intermediate_size"]


def attention_flops(sizes, calls):
    """The products of the attention kernels of ``calls`` blocks for one
    sequence's step: per pair and head ``2 (d_qk + d_v)`` forward (the
    scores over the keys' width, the sum over the values') and twice that
    backward; the scores recomputed in the backward pass do not count."""
    width = sizes["qk_head_dim"] + sizes["v_head_dim"]
    return (6.0 * width * attended_pairs(sizes["seq_len"], None)
            * sizes["num_attention_heads"] * calls)


def attention_bytes(sizes, calls):
    """Least HBM traffic of those kernels for one sequence: q, k (the keys'
    width) and v, o (the values') read or written once forward; q, k, v, o,
    do read and dq, dk, dv written once backward
    (``families/laguna.py``'s count, every head with keys of its own)."""
    rows = sizes["seq_len"] * sizes["num_attention_heads"] * calls
    return float(jnp.dtype(sizes["dtype"]).itemsize * 6 * rows
                 * (sizes["qk_head_dim"] + sizes["v_head_dim"]))


def model_flops_per_item(sizes, held_assignments_per_token):
    """Forward plus backward of this share's step for one token: 6 per
    matmul parameter it meets (the routed experts by the assignments that
    land here, summed over the expert layers) and the attention pairs of
    every call; a multiply-add is 2, nothing recomputed."""
    matmul = dense_matmul_params(sizes) + (held_assignments_per_token
                                           * expert_params(sizes))
    return 6.0 * matmul + attention_flops(
        sizes, attention_calls(sizes)) / sizes["seq_len"]


def expert_bytes(sizes):
    """The held experts' weights read forward and backward and their
    gradient written, a step."""
    item = jnp.dtype(sizes["dtype"]).itemsize
    return float(3 * expert_layers(sizes) * sizes["n_routed_experts"]
                 * expert_params(sizes) * item)


def bias_moves(params, seed_params):
    """What the steps so far made of the selection bias, over every expert
    layer: how many experts it raised and lowered, and the largest move."""
    biases = lambda tree: np.concatenate([
        np.asarray(leaf, np.float64).ravel() for path, leaf in
        jax.tree_util.tree_flatten_with_path(tree)[0]
        if "router_bias" in jax.tree_util.keystr(path)])
    moved = biases(params) - biases(seed_params)
    return {"raised": int((moved > 0).sum()),
            "lowered": int((moved < 0).sum()),
            "largest_move": float(np.abs(moved).max())}


def build(hvd, cell, key, annotate):
    sizes = cell.sizes
    if cell.mix["step_mode"] != "spmd":
        raise SystemExit("benchmark: the joyai family has the spmd step "
                         "only")
    cfg = config_of(sizes)
    weights = jax.jit(lambda k: joyai.from_published(
        data.init_weights(k, sizes), cfg))
    params = hvd.broadcast_parameters(weights(key), root_rank=0)
    adam = data.ADAM
    optimizer = hvd.DistributedOptimizer(
        optax.adam(adam["lr"], b1=adam["b1"], b2=adam["b2"], eps=adam["eps"]),
        op=hvd.Average, axis_name="hvd")
    mesh = hvd.mesh()
    batch = mesh_batch(data.make_batch, key, sizes, mesh, P("hvd"))
    counted = counters(
        jax.jit(lambda p, t, n: joyai.expert_load(p, t, n, cfg))(
            params, *batch), batch[0].size, sizes)
    if counted["assignments_dropped"]:
        raise SystemExit(f"benchmark: the expert layer dropped "
                         f"{counted['assignments_dropped']} assignments")
    state = (params, optimizer.init(params))
    # the attention call sites by path, counted while the step is traced
    before = dict(trace.attention)
    compiled = jax.jit(shard_map(
        joyai.make_train_step(cfg, optimizer, axis_name="hvd"), mesh=mesh,
        in_specs=(P(), P(), P("hvd"), P("hvd")),
        out_specs=(P(), P(), P()), check_vma=False),
        donate_argnums=(0, 1)).lower(*state, *batch).compile()

    def step(state, batch):
        with annotate("bench/enqueue"):
            *state, loss = compiled(*state, *batch)
        return tuple(state), loss

    def params_of(state):
        """The harness asks first after the followed steps: what they made
        of the bias goes into the record's counters then."""
        if "router_bias" not in counted:
            counted["router_bias"] = bias_moves(state[0], weights(key))
        return state[0]

    counted["attention"] = {path: trace.attention[path] - n
                            for path, n in before.items()}

    b1 = adam["b1"]
    sequences = sizes["batch_per_chip"]
    held_per_chip = counted["assignments_held"] / mesh.size
    main = sizes["num_hidden_layers"]
    return {
        "step": step, "state": state, "batch": batch,
        "items_per_step_per_chip": sequences * sizes["seq_len"],
        "flops_per_item": model_flops_per_item(
            sizes, held_per_chip / (sequences * sizes["seq_len"])),
        "params_of": params_of,
        # Adam's first moment after one step is (1 - b1) times the
        # gradient the optimizer was given.  Divided in float32 and kept in
        # the moment's own type, in one program (``families/jamba.py``'s).
        "first_gradient_of": jax.jit(lambda s: jax.tree_util.tree_map(
            lambda m: (m.astype(jnp.float32) / (1.0 - b1)).astype(m.dtype),
            s[1].inner_state[0].mu)),
        "seed_params": lambda: weights(key),
        "temp_bytes": int(compiled.memory_analysis().temp_size_in_bytes),
        "kernel": {
            # the main layers' attention kernels, as
            # ``latent_flash_roofline`` reads them (the module's are under
            # ``mtp``)
            "latent_flash": {
                "flops_per_step": attention_flops(sizes, main) * sequences,
                "bytes_per_step": attention_bytes(sizes, main) * sequences},
            "experts": {"flops_per_step": 6.0 * expert_params(sizes)
                        * held_per_chip,
                        "bytes_per_step": expert_bytes(sizes)},
            "counters": counted,
            "scopes": trace_scopes.within(SCOPES, compiled.as_text()),
        },
    }
