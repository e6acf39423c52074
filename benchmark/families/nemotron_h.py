"""A Nemotron-H-style hybrid decoder step (Mamba-2 state-space layers, a
LatentMoE expert layer told which experts it holds, position-free grouped
attention, one mixer a layer) through horovod_tpu's public entry points,
built for one mix: ``families/llama.py`` with another model.

``nemotron_h.make_train_step`` wrapped in ``shard_map`` over ``hvd.mesh()``
with ``hvd.DistributedOptimizer(optax.adam, op=Average, axis_name="hvd")``,
state donated; the attention layer takes the program's own route (the
Pallas flash kernel on a TPU).  The weights and the fixed batch come from
the benchmark's own generator (``reference/nemotron_h.py``), made on the
device from the seed in one jitted call, in the configuration's type.

Set-up also runs the fixed batch once through the seed's weights and reads
two counters of the ``kernel`` record: the assignments that land on the
experts held here, layer by layer (``nemotron_h.expert_load``), from which
the operations of the share's step follow; and, a Mamba layer, the share of
(chunk, head) pairs whose decay across the whole chunk exceeds 0.01 and the
smallest and largest per-token decay (``nemotron_h.decay_stats``).  A batch
in which that share is under a tenth in any Mamba layer is refused: there
the scan over the chunk states does nothing, and the cell would measure
128-token blocks.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

from horovod_tpu.compat import shard_map
from horovod_tpu.models import nemotron_h

from .. import trace_scopes
from ..reference import nemotron_h as data
from ..reference.common import mesh_batch
# the expert layer's counter is made from the same key of ``sizes``
# (``num_experts_per_tok``) as the sibling family's
from .qwen3_next import counters as expert_load

# the named scopes of the program that the per-layer readers sum
SCOPES = ("ssm/proj", "ssm/conv", "ssm/scan", "ssm/out", "attn/full",
          "moe/route", "moe/latent", "moe/dispatch", "moe/experts",
          "moe/shared", "moe/combine", "head")
LEAST_SHARE_CARRIED = 0.1


def config_of(sizes):
    return nemotron_h.NemotronHConfig(
        vocab_size=sizes["vocab_size"], d_model=sizes["hidden_size"],
        pattern=data.pattern(sizes), ssm_heads=sizes["mamba_num_heads"],
        ssm_head_dim=sizes["mamba_head_dim"], ssm_groups=sizes["n_groups"],
        ssm_state=sizes["ssm_state_size"], conv_kernel=sizes["conv_kernel"],
        chunk=sizes["chunk_size"], step_min=sizes["time_step_min"],
        step_max=sizes["time_step_max"], step_floor=sizes["time_step_floor"],
        n_heads=sizes["num_attention_heads"],
        n_kv_heads=sizes["num_key_value_heads"], head_dim=sizes["head_dim"],
        n_experts=sizes["num_experts_published"],
        top_k=sizes["num_experts_per_tok"],
        routed_scale=float(sizes["routed_scaling_factor"]),
        d_latent=sizes["moe_latent_size"],
        d_expert=sizes["moe_intermediate_size"],
        d_shared=sizes["moe_shared_expert_intermediate_size"],
        first_expert=sizes["first_expert"],
        experts_held=sizes["n_routed_experts"], norm_eps=sizes["norm_eps"],
        dtype=jnp.dtype(sizes["dtype"]), use_flash=sizes.get("use_flash"))


# ------------------------------------------- operations and bytes, by shape
def layer_kinds(sizes):
    """(Mamba-2 layers, expert layers, attention layers)."""
    kinds = data.pattern(sizes)
    return kinds.count("M"), kinds.count("E"), kinds.count("*")


def dense_matmul_params(sizes):
    """Matmul parameters every token meets in a step (the embedding is a
    lookup, the routed experts are counted from the assignments)."""
    d = sizes["hidden_size"]
    H, P, G, N = data.ssm_dims(sizes)
    h, kv, hd = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                 sizes["head_dim"])
    ssm = d * (2 * H * P + 2 * G * N + H) + H * P * d
    attn = d * (h + 2 * kv) * hd + h * hd * d
    expert_layer = (d * sizes["num_experts_published"]
                    + 2 * d * sizes["moe_latent_size"]
                    + 2 * d * sizes["moe_shared_expert_intermediate_size"])
    ssm_layers, expert_layers, attn_layers = layer_kinds(sizes)
    return (ssm_layers * ssm + attn_layers * attn
            + expert_layers * expert_layer + d * sizes["vocab_size"])


def expert_params(sizes):
    """One routed expert's matmul parameters: two matrices in the latent."""
    return 2 * sizes["moe_latent_size"] * sizes["moe_intermediate_size"]


def attention_flops(sizes):
    """The attention layers' products of one sequence's step, forward (4
    per causal pair and head dimension) and backward (8): the scores
    recomputed in the backward pass do not count."""
    t = sizes["seq_len"]
    return (12.0 * (t * (t + 1) // 2) * sizes["head_dim"]
            * sizes["num_attention_heads"] * layer_kinds(sizes)[2])


def attention_bytes(sizes):
    """Least HBM traffic of the attention kernels for one sequence: q, k,
    v and the output read or written once forward; q, k, v, o, do read and
    dq, dk, dv written once backward (``families/llama.py``'s count)."""
    t, hd = sizes["seq_len"], sizes["head_dim"]
    q = t * hd * sizes["num_attention_heads"]
    k = t * hd * sizes["num_key_value_heads"]
    item = jnp.dtype(sizes["dtype"]).itemsize
    return float(item * layer_kinds(sizes)[2] * (6 * q + 6 * k))


def chunks_per_sequence(sizes):
    return -(-sizes["seq_len"] // sizes["chunk_size"])


def ssm_scan_flops(sizes):
    """The chunked recurrence's products for one sequence's step, forward
    and backward (twice the forward).  Per chunk of C tokens, forward: a
    head's scores times its inputs (2 C^2 P), its own state and what the
    states before give (2 C N P each); a group's C B^T (2 C^2 N) once for
    the heads that share it."""
    c = sizes["chunk_size"]
    H, P, G, N = data.ssm_dims(sizes)
    chunk = H * (2 * c * c * P + 4 * c * N * P) + G * 2 * c * c * N
    return 3.0 * chunk * chunks_per_sequence(sizes) * layer_kinds(sizes)[0]


def ssm_scan_bytes(sizes):
    """Least HBM traffic of the recurrence for one sequence's step: x, B
    and C (at the groups), the step and the log decay read and y written
    forward; those and dy read, dx, dB, dC and the two gates' gradients
    written backward."""
    item = jnp.dtype(sizes["dtype"]).itemsize
    H, P, G, N = data.ssm_dims(sizes)
    x, bc, gates = H * P * item, 2 * G * N * item, 2 * H * 4
    token = (x + bc + gates + x) + (x + bc + gates + x) + (x + bc + gates)
    return float(token * sizes["seq_len"] * layer_kinds(sizes)[0])


def model_flops_per_item(sizes, held_assignments_per_token):
    """Forward plus backward of this share's step for one token: 6 per
    matmul parameter it meets (the routed experts by the assignments that
    land here, summed over the expert layers), the attention pairs and the
    recurrence's products; a multiply-add is 2, nothing recomputed."""
    matmul = dense_matmul_params(sizes) + (held_assignments_per_token
                                           * expert_params(sizes))
    return 6.0 * matmul + (attention_flops(sizes)
                           + ssm_scan_flops(sizes)) / sizes["seq_len"]


def expert_bytes(sizes):
    """The held experts' weights read forward and backward and their
    gradient written, a step."""
    item = jnp.dtype(sizes["dtype"]).itemsize
    return float(3 * layer_kinds(sizes)[1] * sizes["n_routed_experts"]
                 * expert_params(sizes) * item)


def decay_stats(share, least, most, sizes):
    """The counter of the fixed batch from ``nemotron_h.decay_stats``."""
    share = np.asarray(share, float)
    return {"chunk_decay_over_0.01_share": [float(s) for s in share],
            "decay_least": [float(a) for a in np.asarray(least, float)],
            "decay_most": [float(a) for a in np.asarray(most, float)],
            "least_share_carried": float(share.min()),
            "chunks_per_sequence": chunks_per_sequence(sizes)}


def build(hvd, cell, key, annotate):
    sizes = cell.sizes
    if cell.mix["step_mode"] != "spmd":
        raise SystemExit("benchmark: the nemotron_h family has the spmd "
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
    load = expert_load(
        jax.jit(lambda p, t: nemotron_h.expert_load(p, t, cfg))(
            params, batch[0]), batch[0].size, sizes)
    if load["assignments_dropped"]:
        raise SystemExit(f"benchmark: the expert layer dropped "
                         f"{load['assignments_dropped']} assignments")
    decay = decay_stats(
        *jax.jit(lambda p, t: nemotron_h.decay_stats(p, t, cfg))(
            params, batch[0]), sizes)
    if decay["least_share_carried"] < LEAST_SHARE_CARRIED:
        raise SystemExit(f"benchmark: a chunk's decay exceeds 0.01 for "
                         f"{decay['chunk_decay_over_0.01_share']} of the "
                         f"(chunk, head) pairs a layer: the scan over the "
                         f"chunk states does nothing in this batch")
    state = (params, optimizer.init(params))
    compiled = jax.jit(shard_map(
        nemotron_h.make_train_step(cfg, optimizer), mesh=mesh,
        in_specs=(P(), P(), P("hvd"), P("hvd")),
        out_specs=(P(), P(), P()), check_vma=False),
        donate_argnums=(0, 1)).lower(*state, *batch).compile()

    def step(state, batch):
        with annotate("bench/enqueue"):
            *state, loss = compiled(*state, *batch)
        return tuple(state), loss

    b1 = adam["b1"]
    sequences = sizes["batch_per_chip"]
    held_per_chip = load["assignments_held"] / mesh.size
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
            # the attention kernels, as ``flash_roofline`` reads them
            "flops_per_step": attention_flops(sizes) * sequences,
            "bytes_per_step": attention_bytes(sizes) * sequences,
            "ssm_scan": {"flops_per_step": ssm_scan_flops(sizes) * sequences,
                         "bytes_per_step": ssm_scan_bytes(sizes) * sequences},
            "experts": {"flops_per_step": 6.0 * expert_params(sizes)
                        * held_per_chip,
                        "bytes_per_step": expert_bytes(sizes)},
            "counters": {"expert_load": load, "decay_stats": decay},
            "scopes": trace_scopes.within(SCOPES, compiled.as_text()),
        },
    }
