"""Drives a rehearsal run of the ``jamba`` family with the step broken
underneath (a child process of ``test_benchmark_broken_jamba.py``): a
function of the model is replaced before the step is built, everything else
is ``run.py`` as it stands."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax                                      # noqa: E402
import jax.numpy as jnp                         # noqa: E402

from benchmark import run                       # noqa: E402
from horovod_tpu.models import jamba, mamba     # noqa: E402


def with_params(change):
    """The mixer given a layer's parameters changed."""
    def broken(mixer):
        def bad(u, p, dims):
            return mixer(u, change(p), dims)
        return bad
    return broken


# ``y_t = h_t C_t`` without ``+ D x_t``
skip_left_out = with_params(lambda p: dict(p, D=jnp.zeros_like(p["D"])))
# ``delta = softplus(dt_r W_dt)`` without ``+ b_dt``
step_bias_left_out = with_params(
    lambda p: dict(p, dt_bias=jnp.zeros_like(p["dt_bias"])))
# ``A[c, s] = A[c, 0]``: one decay a channel, the state index ignored
one_decay_a_channel = with_params(
    lambda p: dict(p, A_log=jnp.broadcast_to(
        p["A_log"][:, :1], p["A_log"].shape)))


def inner_norms_left_out(norm):
    """``dt_r``, ``B`` and ``C`` go on as ``W_x`` made them."""
    return lambda x, w, eps: x


def head_part_dropped(logits):
    """The tied matrix takes the lookup's gradient alone: the head reads it
    as a constant."""
    def bad(params, x, cfg):
        return logits(dict(params, embed=jax.lax.stop_gradient(
            params["embed"])), x, cfg)
    return bad


def half_the_batch(loss_fn):
    """The loss is taken over the first half of each sequence's tokens."""
    def bad(params, tokens, targets, cfg):
        half = tokens.shape[1] // 2
        return loss_fn(params, tokens[:, :half], targets[:, :half], cfg)
    return bad


FAULTS = {"skip_left_out": (mamba, "mamba", skip_left_out),
          "inner_norms_left_out": (mamba, "_rmsnorm", inner_norms_left_out),
          "step_bias_left_out": (mamba, "mamba", step_bias_left_out),
          "one_decay_a_channel": (mamba, "mamba", one_decay_a_channel),
          "head_part_dropped": (jamba, "_logits", head_part_dropped),
          "half_the_batch": (jamba, "loss_fn", half_the_batch)}

if __name__ == "__main__":
    fault = sys.argv.pop(1)
    if fault not in FAULTS:
        raise SystemExit(f"unknown fault {fault}")
    module, name, broken = FAULTS[fault]
    setattr(module, name, broken(getattr(module, name)))
    run.main()
