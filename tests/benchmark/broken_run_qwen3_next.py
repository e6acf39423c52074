"""Drives a rehearsal run of the ``qwen3_next`` family with the program's
own mechanisms broken underneath (a child process of
``test_benchmark_broken_qwen3_next.py``): a function of the model is
replaced before the step is built, everything else is ``run.py`` as it
stands."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import dataclasses                              # noqa: E402

import jax                                      # noqa: E402
import jax.numpy as jnp                         # noqa: E402

from benchmark import run                       # noqa: E402
from horovod_tpu.models import moe, qwen3_next  # noqa: E402


def chunk_state_not_carried(rule):
    """Every chunk starts from a zero state."""
    def bad(q, k, v, g, beta, chunk=64):
        return jnp.concatenate(
            [rule(*(x[:, i:i + chunk] for x in (q, k, v, g, beta)), chunk)
             for i in range(0, q.shape[1], chunk)], axis=1)
    return bad


def capacity_dropped(_layer):
    """An expert takes the assignments that fit a capacity (1.0 times the
    mean load over ALL experts) and drops the rest, as the capacity router
    does: a token's weight on a dropped assignment never counts."""
    def bad(x, params, cfg):
        ids, weights = moe.dropless_route(x, params["router"], cfg)
        capacity = x.shape[0] * cfg.top_k // cfg.n_experts
        flat = ids.reshape(-1)
        y = jnp.zeros_like(x)
        for e in range(cfg.held):
            mine = flat == cfg.first_expert + e
            kept = (mine & (jnp.cumsum(mine) <= capacity)).reshape(ids.shape)
            w = jnp.sum(jnp.where(kept, weights, 0.0), axis=-1)
            hidden = jax.nn.silu(x @ params["w1"][e]) * (x @ params["w3"][e])
            y = y + w[:, None] * (hidden @ params["w2"][e])
        hidden = jax.nn.silu(x @ params["shared_w1"]) * (
            x @ params["shared_w3"])
        return y + jax.nn.sigmoid(x @ params["shared_gate"])[:, None] * (
            hidden @ params["shared_w2"]), jnp.zeros((cfg.held,), jnp.int32)
    return bad


def shared_gate_left_out(layer):
    """The shared expert is added whole, without its sigmoid gate."""
    def bad(x, params, cfg):
        y, counts = layer(x, params, dataclasses.replace(cfg, d_shared=0))
        hidden = jax.nn.silu(x @ params["shared_w1"]) * (
            x @ params["shared_w3"])
        return y + hidden @ params["shared_w2"], counts
    return bad


FAULTS = {"chunk_state_not_carried": (qwen3_next, "chunked_gated_delta_rule",
                                      chunk_state_not_carried),
          "capacity_dropped": (moe, "dropless_moe_ffn", capacity_dropped),
          "shared_gate_left_out": (moe, "dropless_moe_ffn",
                                   shared_gate_left_out)}

if __name__ == "__main__":
    fault = sys.argv.pop(1)
    if fault not in FAULTS:
        raise SystemExit(f"unknown fault {fault}")
    module, name, broken = FAULTS[fault]
    setattr(module, name, broken(getattr(module, name)))
    run.main()
