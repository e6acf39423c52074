"""An Ouro-style looped decoder step (one stack of layers run
``total_ut_steps`` times on the same weights, a head and an exit gate after
every pass, the expected-exit loss) through horovod_tpu's public entry
points, built for one mix: ``families/llama.py`` with another model.

``ouro.make_train_step`` wrapped in ``shard_map`` over ``hvd.mesh()`` with
``hvd.DistributedOptimizer(optax.adam, op=Average, axis_name="hvd")``,
state donated; attention takes the program's own route (the Pallas flash
kernel on a TPU).  The weights and the fixed batch come from the
benchmark's own generator (``reference/ouro.py``), made on the device from
the seed in one jitted call, in the configuration's type.

Set-up also runs the fixed batch once through the seed's weights and reads
the exit distribution (``ouro.exit_stats``): the mean ``p(r)`` of every
pass, the mean entropy and each pass's mean loss, the counters of the
``kernel`` record, printed at set-up as one ``{"exit_stats": ...}`` line of
the run's output.  A batch in which a pass's mean ``p(r)`` is under 0.05
is refused: a gate stuck open or shut trains one exit, and the mixture of
the passes' losses is not in play.
"""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

from horovod_tpu.compat import shard_map
from horovod_tpu.models import ouro

from .. import trace_scopes
from ..reference import ouro as data
from ..reference.common import mesh_batch

# the named scopes of the program, for per-layer readers to sum
SCOPES = ("attn/full", "mlp", "head", "loop/exit", "loop/carry")
LEAST_MEAN_EXIT = 0.05


def config_of(sizes):
    if sizes["num_key_value_heads"] != sizes["num_attention_heads"]:
        raise SystemExit("benchmark: the ouro family has no grouped "
                         "attention")
    if sizes.get("rope_scaling") or sizes.get("sliding_window"):
        raise SystemExit("benchmark: the ouro family has neither rotary "
                         "scaling nor a window")
    return ouro.OuroConfig(
        vocab_size=sizes["vocab_size"], d_model=sizes["hidden_size"],
        n_layers=sizes["num_hidden_layers"],
        n_heads=sizes["num_attention_heads"], head_dim=sizes["head_dim"],
        d_ff=sizes["intermediate_size"],
        total_ut_steps=sizes["total_ut_steps"],
        rope_theta=sizes["rope_theta"], norm_eps=sizes["rms_norm_eps"],
        entropy_beta=sizes["entropy_beta"],
        dtype=jnp.dtype(sizes["dtype"]), use_flash=sizes.get("use_flash"))


# ------------------------------------------- operations and bytes, by shape
def layer_applications(sizes):
    return sizes["num_hidden_layers"] * sizes["total_ut_steps"]


def matmul_params(sizes):
    """Matmul parameters a token meets in ONE pass (the embedding is a
    lookup): the layers held, the head over the whole vocabulary and the
    gate."""
    d = sizes["hidden_size"]
    e = sizes["num_attention_heads"] * sizes["head_dim"]
    layer = 4 * d * e + 3 * d * sizes["intermediate_size"]
    return (sizes["num_hidden_layers"] * layer + d * sizes["vocab_size"]
            + d)


def attention_flops(sizes):
    """The attention products of one sequence's step over every layer
    application, forward (4 per causal pair and head dimension) and
    backward (8): the scores recomputed in the backward pass do not
    count."""
    t = sizes["seq_len"]
    return (12.0 * (t * (t + 1) // 2) * sizes["head_dim"]
            * sizes["num_attention_heads"] * layer_applications(sizes))


def attention_bytes(sizes):
    """Least HBM traffic of the attention kernels for one sequence: q, k,
    v and the output read or written once forward; q, k, v, o, do read and
    dq, dk, dv written once backward (``families/llama.py``'s count, every
    head with keys of its own), a layer application."""
    item = jnp.dtype(sizes["dtype"]).itemsize
    return float(item * layer_applications(sizes) * 12 * sizes["seq_len"]
                 * sizes["num_attention_heads"] * sizes["head_dim"])


def model_flops_per_item(sizes):
    """Forward plus backward of the stage's step for one token: 6 per
    matmul parameter it meets, in every pass (the head and the gate
    ``total_ut_steps`` times too), and the attention pairs; a multiply-add
    is 2, nothing recomputed."""
    return (6.0 * sizes["total_ut_steps"] * matmul_params(sizes)
            + attention_flops(sizes) / sizes["seq_len"])


def counters(stats, sizes):
    """The counters of the fixed batch from ``exit_stats``."""
    p = [float(x) for x in np.asarray(stats["p_mean"], float)]
    return {"exit_p_mean": p, "least_exit_p_mean": min(p),
            "exit_entropy_mean": float(stats["entropy_mean"]),
            "nll_mean": [float(x) for x in np.asarray(stats["nll_mean"],
                                                      float)],
            "layer_applications_per_step": layer_applications(sizes)}


def build(hvd, cell, key, annotate):
    sizes = cell.sizes
    if cell.mix["step_mode"] != "spmd":
        raise SystemExit("benchmark: the ouro family has the spmd step only")
    cfg = config_of(sizes)
    weights = jax.jit(lambda k: data.init_weights(k, sizes))
    params = hvd.broadcast_parameters(weights(key), root_rank=0)
    adam = data.ADAM
    optimizer = hvd.DistributedOptimizer(
        optax.adam(adam["lr"], b1=adam["b1"], b2=adam["b2"], eps=adam["eps"]),
        op=hvd.Average, axis_name="hvd")
    mesh = hvd.mesh()
    batch = mesh_batch(data.make_batch, key, sizes, mesh, P("hvd"))
    counted = counters(jax.jit(
        lambda p, t, y: ouro.exit_stats(p, t, y, cfg))(params, *batch),
        sizes)
    if counted["least_exit_p_mean"] < LEAST_MEAN_EXIT:
        raise SystemExit(f"benchmark: the passes' mean exit probabilities "
                         f"are {counted['exit_p_mean']}: a gate is stuck "
                         f"and the mixture of the passes' losses is not in "
                         f"play in this batch")
    print(json.dumps({"exit_stats": counted}), flush=True)
    state = (params, optimizer.init(params))
    compiled = jax.jit(shard_map(
        ouro.make_train_step(cfg, optimizer), mesh=mesh,
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
            "counters": counted,
            "scopes": trace_scopes.within(SCOPES, compiled.as_text()),
        },
    }
