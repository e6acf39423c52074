"""A Laguna-style expert decoder step (window layers of 72 query heads
beside full layers of 48 on 8 key-value heads, a gate a head, two rotaries,
a leading dense layer, a dropless expert layer told which experts it holds)
through horovod_tpu's public entry points, built for one mix:
``families/qwen3_next.py`` with another model.

``laguna.make_train_step`` wrapped in ``shard_map`` over ``hvd.mesh()``
with ``hvd.DistributedOptimizer(optax.adam, op=Average, axis_name="hvd")``,
state donated; every attention layer takes the program's own route (the
Pallas flash kernels on a TPU, a sliding layer's over its band's blocks).
The weights and the fixed batch come from the benchmark's own generator
(``reference/laguna.py``), made on the device from the seed in one jitted
call, in the configuration's type.

The harness hands a family the file's scalars (``cell.sizes``), so the file
states the layers run and both rotaries a second time as scalars (the
``*_run`` strings, the ``full_rope_*`` and ``sliding_rope_*`` keys) beside
the published lists and the published ``rope_parameters``, which it keeps
whole; ``published_as_run`` holds the two against each other, and a file in
which they differ is refused.

Set-up also routes the fixed batch once through the seed's weights and
counts, expert layer by expert layer, the assignments that land on the
experts held here (``laguna.expert_load``), from which the operations of
the share's step follow; beside them go the attention call sites of the
step by layer kind and path (``trace.attention``, read round the step's
tracing: ``*_plain`` above 0 on the chip is a fall to the plain path).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax
from jax.sharding import PartitionSpec as P

from horovod_tpu import trace
from horovod_tpu.compat import shard_map
from horovod_tpu.models import blocks, laguna

from .. import trace_scopes
from ..reference import laguna as data
from ..reference.common import mesh_batch
from .llama import attended_pairs
from .qwen3_next import counters

# the named scopes of the program that the per-layer readers sum
SCOPES = ("attn/full", "attn/window", "mlp", "moe/route", "moe/dispatch",
          "moe/experts", "moe/shared", "moe/combine", "head")
KINDS = {"full": "full_attention", "sliding": "sliding_attention"}


def published_as_run(config):
    """The scalars that the published lists and ``rope_parameters`` of a
    configuration file give for the layers it runs: what its ``*_run``
    strings and flat rotary keys have to say."""
    n = config["num_hidden_layers"]
    flat = {
        "layer_types_run": " ".join(config["layer_types"][:n]),
        "num_attention_heads_per_layer_run": " ".join(
            str(h) for h in config["num_attention_heads_per_layer"][:n]),
        "mlp_layer_types_run": " ".join(config["mlp_layer_types"][:n])}
    for kind, published in KINDS.items():
        for key, value in config["rope_parameters"][published].items():
            flat[f"{kind}_{key}" if key.startswith(("rope_", "partial_"))
                 else f"{kind}_rope_{key}"] = value
    return flat


def rotary_of(sizes, kind):
    factor = sizes[f"{kind}_partial_rotary_factor"]
    yarn = sizes[f"{kind}_rope_type"] == "yarn"
    return blocks.Rotary(
        width=int(sizes["head_dim"] * factor),
        theta=float(sizes[f"{kind}_rope_theta"]),
        kind=sizes[f"{kind}_rope_type"],
        **({"factor": float(sizes[f"{kind}_rope_factor"]),
            "original_max": sizes[
                f"{kind}_rope_original_max_position_embeddings"],
            "beta_fast": float(sizes[f"{kind}_rope_beta_fast"]),
            "beta_slow": float(sizes[f"{kind}_rope_beta_slow"]),
            "attention_factor": sizes.get(f"{kind}_rope_attention_factor")}
           if yarn else {}))


def config_of(sizes):
    kinds, heads, mlps = zip(*data.layer_table(sizes))
    return laguna.LagunaConfig(
        vocab_size=sizes["vocab_size"], d_model=sizes["hidden_size"],
        layer_types=kinds, heads_per_layer=heads, mlp_layer_types=mlps,
        n_kv_heads=sizes["num_key_value_heads"], head_dim=sizes["head_dim"],
        sliding_window=sizes["sliding_window"],
        rope_full=rotary_of(sizes, "full"),
        rope_sliding=rotary_of(sizes, "sliding"),
        d_ff=sizes["intermediate_size"],
        n_experts=sizes["num_experts_published"],
        top_k=sizes["num_experts_per_tok"],
        routed_scale=sizes["moe_routed_scaling_factor"],
        d_expert=sizes["moe_intermediate_size"],
        d_shared=sizes["shared_expert_intermediate_size"],
        first_expert=sizes["first_expert"],
        experts_held=sizes["num_experts"], norm_eps=sizes["rms_norm_eps"],
        dtype=jnp.dtype(sizes["dtype"]), use_flash=sizes.get("use_flash"))


# ------------------------------------------- operations and bytes, by shape
def heads_by_kind(sizes):
    """``{"full": [query heads of each full layer], "sliding": [...]}``."""
    table = data.layer_table(sizes)
    return {kind: [h for t, h, _ in table if t == published]
            for kind, published in KINDS.items()}


def sparse_layers(sizes):
    return sum(mlp == "sparse" for _, _, mlp in data.layer_table(sizes))


def dense_matmul_params(sizes):
    """Matmul parameters every token meets in a step (the embedding is a
    lookup, the routed experts are counted from the assignments)."""
    d, hd, kv = (sizes["hidden_size"], sizes["head_dim"],
                 sizes["num_key_value_heads"])
    attn = sum(d * (2 * h * hd + 2 * kv * hd + h)
               for _, h, _ in data.layer_table(sizes))
    sparse = sparse_layers(sizes)
    return (attn
            + (sizes["num_hidden_layers"] - sparse) * 3 * d
            * sizes["intermediate_size"]
            + sparse * d * (sizes["num_experts_published"]
                            + 3 * sizes["shared_expert_intermediate_size"])
            + d * sizes["vocab_size"])


def expert_params(sizes):
    """One routed expert's matmul parameters."""
    return 3 * sizes["hidden_size"] * sizes["moe_intermediate_size"]


def pairs(sizes, kind):
    """(query, key) pairs of one head of a layer of ``kind``: the causal
    triangle, or the band's own pairs (``sum_t min(t + 1, window)``)."""
    return attended_pairs(sizes["seq_len"], sizes["sliding_window"]
                          if kind == "sliding" else None)


def attention_flops(sizes, kind):
    """The products of the attention kernels of the layers of ``kind`` for
    one sequence's step, forward (4 per pair and head dimension) and
    backward (8): the scores recomputed in the backward pass do not
    count."""
    return (12.0 * pairs(sizes, kind) * sizes["head_dim"]
            * sum(heads_by_kind(sizes)[kind]))


def attention_bytes(sizes, kind):
    """Least HBM traffic of those kernels for one sequence: q, k, v and
    the output read or written once forward; q, k, v, o, do read and dq,
    dk, dv written once backward (``families/llama.py``'s count)."""
    t, hd = sizes["seq_len"], sizes["head_dim"]
    heads = heads_by_kind(sizes)[kind]
    q = t * hd * sum(heads)
    k = t * hd * sizes["num_key_value_heads"] * len(heads)
    return float(jnp.dtype(sizes["dtype"]).itemsize * (6 * q + 6 * k))


def model_flops_per_item(sizes, held_assignments_per_token):
    """Forward plus backward of this share's step for one token: 6 per
    matmul parameter it meets (the routed experts by the assignments that
    land here, summed over the expert layers) and the attention pairs of
    both layer kinds; a multiply-add is 2, nothing recomputed."""
    matmul = dense_matmul_params(sizes) + (held_assignments_per_token
                                           * expert_params(sizes))
    return 6.0 * matmul + (attention_flops(sizes, "full") + attention_flops(
        sizes, "sliding")) / sizes["seq_len"]


def expert_bytes(sizes):
    """The held experts' weights read forward and backward and their
    gradient written, a step."""
    item = jnp.dtype(sizes["dtype"]).itemsize
    return float(3 * sparse_layers(sizes) * sizes["num_experts"]
                 * expert_params(sizes) * item)


def build(hvd, cell, key, annotate):
    sizes = cell.sizes
    if cell.mix["step_mode"] != "spmd":
        raise SystemExit("benchmark: the laguna family has the spmd step "
                         "only")
    stated = {k: cell.config.get(k) for k in published_as_run(cell.config)}
    if stated != published_as_run(cell.config):
        raise SystemExit(
            f"benchmark: the configuration's scalars {stated} are not what "
            f"its published lists and rope_parameters give for its "
            f"{cell.config['num_hidden_layers']} layers: "
            f"{published_as_run(cell.config)}")
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
        jax.jit(lambda p, t: laguna.expert_load(p, t, cfg))(
            params, batch[0]), batch[0].size, sizes)
    if counted["assignments_dropped"]:
        raise SystemExit(f"benchmark: the expert layer dropped "
                         f"{counted['assignments_dropped']} assignments")
    state = (params, optimizer.init(params))
    # the attention call sites by layer kind and path, counted while the
    # step is traced
    before = dict(trace.attention)
    compiled = jax.jit(shard_map(
        laguna.make_train_step(cfg, optimizer), mesh=mesh,
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
    flash = lambda kind: {
        "flops_per_step": attention_flops(sizes, kind) * sequences,
        "bytes_per_step": attention_bytes(sizes, kind) * sequences}
    return {
        "step": step, "state": state, "batch": batch,
        "items_per_step_per_chip": sequences * sizes["seq_len"],
        "flops_per_item": model_flops_per_item(
            sizes, held_per_chip / (sequences * sizes["seq_len"])),
        "params_of": lambda s: s[0],
        # Adam's first moment after one step is (1 - b1) times the
        # gradient the optimizer was given.  Divided in float32 and kept in
        # the moment's own type, in one program (``families/jamba.py``'s:
        # a float32 copy of 1.1 B moments is 4.5 GB beside 6.7 GB of
        # state).
        "first_gradient_of": jax.jit(lambda s: jax.tree_util.tree_map(
            lambda m: (m.astype(jnp.float32) / (1.0 - b1)).astype(m.dtype),
            s[1].inner_state[0].mu)),
        "seed_params": lambda: weights(key),
        "temp_bytes": int(compiled.memory_analysis().temp_size_in_bytes),
        "kernel": {
            # the attention kernels by layer kind, as
            # ``full_flash_roofline`` and ``window_flash_roofline`` read
            # them
            "full_flash": flash("full"),
            "window_flash": flash("sliding"),
            "experts": {"flops_per_step": 6.0 * expert_params(sizes)
                        * held_per_chip,
                        "bytes_per_step": expert_bytes(sizes)},
            "counters": {**counted, "attention": {
                path: trace.attention[path] - n
                for path, n in before.items()}},
            "scopes": trace_scopes.within(SCOPES, compiled.as_text()),
        },
    }
