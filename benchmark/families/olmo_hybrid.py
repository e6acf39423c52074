"""An Olmo-Hybrid-style decoder step (Gated DeltaNet layers with beta in
(0, 2), position-free full attention, the norm after each sublayer) through
horovod_tpu's public entry points, built for one mix: ``families/llama.py``
with another model.

``olmo_hybrid.make_train_step`` wrapped in ``shard_map`` over ``hvd.mesh()``
with ``hvd.DistributedOptimizer(optax.adam, op=Average, axis_name="hvd")``,
state donated; the full-attention layer takes the program's own route (the
Pallas flash kernel on a TPU).  The weights and the fixed batch come from
the benchmark's own generator (``reference/olmo_hybrid.py``), made on the
device from the seed in one jitted call, in the configuration's type.

Set-up also runs the fixed batch once through the seed's weights and reads,
layer by layer, the share of (token, head) pairs whose beta exceeds 1 and
the largest beta (``olmo_hybrid.beta_stats``): the counters of the
``kernel`` record.  A batch in which under a quarter of the betas exceed 1
does not exercise the negative eigenvalues and is refused.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

from horovod_tpu.compat import shard_map
from horovod_tpu.models import olmo_hybrid

from .. import trace_scopes
from ..reference import olmo_hybrid as data
from ..reference.common import mesh_batch
# the chunked delta rule's products and bytes and the two kinds of layer
# are counted from the same keys of ``sizes`` as the sibling family's: here
# with keys of 96, values of 192 and as many key heads as value heads
from .qwen3_next import (gdn_scan_bytes, gdn_scan_flops,  # noqa: F401
                         layer_kinds)

# the named scopes of the program that the per-layer readers sum
SCOPES = ("gdn/proj", "gdn/conv", "gdn/scan", "gdn/out", "attn/full", "mlp",
          "head")
LEAST_SHARE_OVER_ONE = 0.25


def config_of(sizes):
    if sizes["num_key_value_heads"] != sizes["num_attention_heads"]:
        raise SystemExit("benchmark: the olmo_hybrid family has no grouped "
                         "attention")
    return olmo_hybrid.OlmoHybridConfig(
        vocab_size=sizes["vocab_size"], d_model=sizes["hidden_size"],
        n_layers=sizes["num_hidden_layers"],
        full_attention_interval=sizes["full_attention_interval"],
        n_heads=sizes["num_attention_heads"],
        d_ff=sizes["intermediate_size"],
        lin_k_heads=sizes["linear_num_key_heads"],
        lin_v_heads=sizes["linear_num_value_heads"],
        lin_k_dim=sizes["linear_key_head_dim"],
        lin_v_dim=sizes["linear_value_head_dim"],
        conv_kernel=sizes["linear_conv_kernel_dim"], chunk=sizes["chunk"],
        allow_neg_eigval=sizes["linear_allow_neg_eigval"],
        norm_eps=sizes["rms_norm_eps"],
        dtype=jnp.dtype(sizes["dtype"]), use_flash=sizes.get("use_flash"))


# ------------------------------------------- operations and bytes, by shape
def matmul_params(sizes):
    """Matmul parameters every token meets in a step (the embedding is a
    lookup)."""
    d = sizes["hidden_size"]
    hk, hv, dk, dv = (sizes["linear_num_key_heads"],
                      sizes["linear_num_value_heads"],
                      sizes["linear_key_head_dim"],
                      sizes["linear_value_head_dim"])
    gdn = d * (2 * hk * dk + 2 * hv * dv + 2 * hv) + hv * dv * d
    attn = 4 * d * d
    mlp = 3 * d * sizes["intermediate_size"]
    gdn_layers, full_layers = layer_kinds(sizes)
    return (gdn_layers * gdn + full_layers * attn
            + sizes["num_hidden_layers"] * mlp + d * sizes["vocab_size"])


def head_dim(sizes):
    return sizes["hidden_size"] // sizes["num_attention_heads"]


def attention_flops(sizes):
    """The full layers' attention products of one sequence's step, forward
    (4 per causal pair and head dimension) and backward (8): the scores
    recomputed in the backward pass do not count."""
    t = sizes["seq_len"]
    return (12.0 * (t * (t + 1) // 2) * head_dim(sizes)
            * sizes["num_attention_heads"] * layer_kinds(sizes)[1])


def attention_bytes(sizes):
    """Least HBM traffic of the attention kernels for one sequence: q, k,
    v and the output read or written once forward; q, k, v, o, do read and
    dq, dk, dv written once backward (``families/llama.py``'s count, every
    head with keys of its own)."""
    item = jnp.dtype(sizes["dtype"]).itemsize
    return float(item * layer_kinds(sizes)[1] * 12 * sizes["seq_len"]
                 * sizes["hidden_size"])


def chunks_per_sequence(sizes):
    return -(-sizes["seq_len"] // sizes["chunk"])


def model_flops_per_item(sizes):
    """Forward plus backward of the stage's step for one token: 6 per
    matmul parameter it meets, the attention pairs and the delta rule's
    products; a multiply-add is 2, nothing recomputed."""
    return 6.0 * matmul_params(sizes) + (
        attention_flops(sizes) + gdn_scan_flops(sizes)) / sizes["seq_len"]


def counters(share, largest, sizes):
    """The counters of the fixed batch from ``beta_stats``."""
    share, largest = np.asarray(share, float), np.asarray(largest, float)
    return {"beta_over_one_share": [float(s) for s in share],
            "beta_largest": [float(b) for b in largest],
            "least_share_over_one": float(share.min()),
            "chunks_per_sequence": chunks_per_sequence(sizes)}


def build(hvd, cell, key, annotate):
    sizes = cell.sizes
    if cell.mix["step_mode"] != "spmd":
        raise SystemExit("benchmark: the olmo_hybrid family has the spmd "
                         "step only")
    cfg = config_of(sizes)
    published = tuple(cell.config["layer_types"][:cfg.n_layers])
    if published != cfg.layer_types:
        raise SystemExit(f"benchmark: full_attention_interval "
                         f"{cfg.full_attention_interval} gives "
                         f"{cfg.layer_types}, the published layer_types "
                         f"begin {published}")
    weights = jax.jit(lambda k: data.init_weights(k, sizes))
    params = hvd.broadcast_parameters(weights(key), root_rank=0)
    adam = data.ADAM
    optimizer = hvd.DistributedOptimizer(
        optax.adam(adam["lr"], b1=adam["b1"], b2=adam["b2"], eps=adam["eps"]),
        op=hvd.Average, axis_name="hvd")
    mesh = hvd.mesh()
    batch = mesh_batch(data.make_batch, key, sizes, mesh, P("hvd"))
    counted = counters(
        *jax.jit(lambda p, t: olmo_hybrid.beta_stats(p, t, cfg))(
            params, batch[0]), sizes)
    if (sizes["linear_allow_neg_eigval"]
            and counted["least_share_over_one"] < LEAST_SHARE_OVER_ONE):
        raise SystemExit(f"benchmark: beta exceeds 1 for "
                         f"{counted['beta_over_one_share']} of the (token, "
                         f"head) pairs a layer: the negative eigenvalues "
                         f"are not in play in this batch")
    state = (params, optimizer.init(params))
    compiled = jax.jit(shard_map(
        olmo_hybrid.make_train_step(cfg, optimizer), mesh=mesh,
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
            "gdn_scan": {"flops_per_step": gdn_scan_flops(sizes) * sequences,
                         "bytes_per_step": gdn_scan_bytes(sizes) * sequences},
            "counters": counted,
            "scopes": trace_scopes.within(SCOPES, compiled.as_text()),
        },
    }
