"""A Llama/Mistral-style decoder step through horovod_tpu's public entry
points, built for one mix.

``llama.make_train_step`` wrapped in ``shard_map`` over ``hvd.mesh()`` with
``hvd.DistributedOptimizer(optax.adam, op=Average, axis_name="hvd")``, as
``chip_smoke.py``'s ``llama_phase`` does; attention takes the program's
own route (the Pallas kernel on a TPU).  The weights and the fixed batch
come from the benchmark's own generator (``reference/llama.py``), made on
the device from the seed in one jitted call, in the configuration's type.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax
from jax.sharding import PartitionSpec as P

from horovod_tpu.compat import shard_map
from horovod_tpu.models import llama

from ..reference import llama as data
from ..reference.common import mesh_batch

ITEM = "token"


def attended_pairs(seq, window):
    """(query, key) pairs a causal sliding window keeps, per sequence."""
    w = min(window or seq, seq)
    return w * (w + 1) // 2 + (seq - w) * w


def model_flops_per_item(sizes):
    """Forward plus backward for one token: 6 per matmul parameter (the
    embedding is a lookup) plus attention's two products over the causal
    in-window pairs; a multiply-add is 2, nothing recomputed."""
    d, h, kv, hd = (sizes["hidden_size"], sizes["num_attention_heads"],
                    sizes["num_key_value_heads"], sizes["head_dim"])
    layer = d * hd * (2 * h + 2 * kv) + 3 * d * sizes["intermediate_size"]
    matmul = sizes["num_hidden_layers"] * layer + d * sizes["vocab_size"]
    return 6.0 * matmul + attention_flops(sizes) / sizes["seq_len"]


def attention_flops(sizes):
    """What the attention kernels of one sequence's step have to compute:
    forward QK^T and PV (4 per pair and head dimension), backward dP, dV,
    dK and dQ (8): the scores recomputed in the backward pass do not
    count."""
    pairs = attended_pairs(sizes["seq_len"], sizes.get("sliding_window"))
    return (12.0 * pairs * sizes["head_dim"] * sizes["num_attention_heads"]
            * sizes["num_hidden_layers"])


def attention_bytes(sizes):
    """Least HBM traffic of those kernels for one sequence: q, k, v and the
    output read or written once forward; q, k, v, o, do read and dq, dk, dv
    written once backward."""
    t, hd = sizes["seq_len"], sizes["head_dim"]
    q = t * hd * sizes["num_attention_heads"]
    k = t * hd * sizes["num_key_value_heads"]
    item = jnp.dtype(sizes["dtype"]).itemsize
    return float(item * sizes["num_hidden_layers"]
                 * ((2 * q + 2 * k) + (4 * q + 4 * k)))


def build(hvd, cell, key, annotate):
    sizes = cell.sizes
    if cell.mix["step_mode"] != "spmd":
        raise SystemExit("benchmark: the llama family has the spmd step only")
    cfg = llama.LlamaConfig(
        vocab_size=sizes["vocab_size"], d_model=sizes["hidden_size"],
        n_layers=sizes["num_hidden_layers"],
        n_heads=sizes["num_attention_heads"],
        n_kv_heads=sizes["num_key_value_heads"],
        d_ff=sizes["intermediate_size"], max_seq=sizes["seq_len"],
        rope_theta=sizes["rope_theta"], dtype=jnp.dtype(sizes["dtype"]),
        sliding_window=sizes.get("sliding_window"),
        norm_eps=sizes["rms_norm_eps"], dp_axis=None, tp_axis=None,
        sp_axis=None, use_flash=sizes.get("use_flash"))
    if cfg.head_dim != sizes["head_dim"]:
        raise SystemExit("benchmark: head_dim is not hidden_size / heads")
    weights = jax.jit(lambda k: data.init_weights(k, sizes))
    params = hvd.broadcast_parameters(weights(key), root_rank=0)
    adam = data.ADAM
    optimizer = hvd.DistributedOptimizer(
        optax.adam(adam["lr"], b1=adam["b1"], b2=adam["b2"], eps=adam["eps"]),
        op=hvd.Average, axis_name="hvd")
    state = (params, optimizer.init(params))
    mesh = hvd.mesh()
    batch = mesh_batch(data.make_batch, key, sizes, mesh, P("hvd"))
    compiled = jax.jit(shard_map(
        llama.make_train_step(cfg, optimizer), mesh=mesh,
        in_specs=(P(), P(), P("hvd"), P("hvd")),
        out_specs=(P(), P(), P()), check_vma=False),
        donate_argnums=(0, 1)).lower(*state, *batch).compile()

    def step(state, batch):
        with annotate("bench/enqueue"):
            *state, loss = compiled(*state, *batch)
        return tuple(state), loss

    b1 = adam["b1"]
    return {
        "step": step, "state": state, "batch": batch,
        "items_per_step_per_chip": sizes["batch_per_chip"] * sizes["seq_len"],
        "flops_per_item": model_flops_per_item(sizes),
        "params_of": lambda s: s[0],
        # Adam's first moment after one step is (1 - b1) times the
        # gradient the optimizer was given.
        "first_gradient_of": lambda s: jax.tree_util.tree_map(
            lambda m: m.astype(jnp.float32) / (1.0 - b1),
            s[1].inner_state[0].mu),
        "seed_params": lambda: weights(key),
        "temp_bytes": int(compiled.memory_analysis().temp_size_in_bytes),
        "compiled_text": compiled.as_text,
        "kernel": {"flops_per_step": attention_flops(sizes)
                   * sizes["batch_per_chip"],
                   "bytes_per_step": attention_bytes(sizes)
                   * sizes["batch_per_chip"]},
    }
