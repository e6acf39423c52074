"""Drives a run of the ``laguna`` family with the step broken underneath
(a child process of ``test_benchmark_broken_laguna.py``, which also plants
``FAULTS`` in its own process; on the chip, the cell's own sizes): a
function of the model is replaced before the step is built, everything else
is ``run.py`` as it stands."""

import dataclasses
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax                                      # noqa: E402
import jax.numpy as jnp                         # noqa: E402

from benchmark import run                       # noqa: E402
from horovod_tpu.models import blocks, laguna, moe     # noqa: E402


def window_left_off(block):
    """A sliding layer attends every earlier token."""
    def bad(p, x, cfg, layer):
        return block(p, x, dataclasses.replace(
            cfg, sliding_window=x.shape[1] + 1), layer)
    return bad


def gate_left_out(gate):
    """Every head's output goes on as the softmax made it."""
    return lambda u, wg: jnp.ones_like(gate(u, wg))


def whole_head_turned(rotary):
    """A full layer turns all of a head, not its first half."""
    return lambda x, rot: rotary(x, dataclasses.replace(
        rot, width=x.shape[-1]) if rot.kind == "yarn" else rot)


def attention_factor_left_out(rotary):
    """YaRN's frequencies with ``cos`` and ``sin`` unscaled."""
    return lambda x, rot: rotary(x, dataclasses.replace(
        rot, attention_factor=1.0))


def routed_scale_left_out(route):
    return lambda x, w, cfg, bias=None: route(
        x, w, dataclasses.replace(cfg, routed_scale=1.0), bias)


def not_renormalised(route):
    """The chosen experts weigh by their scores as they are, not over
    their sum."""
    def bad(x, w, cfg, bias=None):
        ids, _ = route(x, w, cfg, bias)
        scores = jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.float32), w.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        return ids, cfg.routed_scale * jnp.take_along_axis(scores, ids, -1)
    return bad


def layer_0_given_an_expert_layer(hidden):
    """Layer 0's dense SwiGLU is skipped and layer 1's expert layer (its
    norm too) runs in its place."""
    def bad(params, tokens, cfg):
        first, second = params["layers"][:2]
        swapped = {k: v for k, v in first.items() if k != "mlp"}
        swapped.update(moe=second["moe"], mlp_norm=second["mlp_norm"])
        return hidden(dict(params, layers=[swapped] + list(
            params["layers"][1:])), tokens, cfg)
    return bad


def half_the_batch(loss_fn):
    """The loss is taken over the first half of each sequence's tokens."""
    def bad(params, tokens, targets, cfg):
        half = tokens.shape[1] // 2
        return loss_fn(params, tokens[:, :half], targets[:, :half], cfg)
    return bad


FAULTS = {
    "window_left_off": (laguna, "_attention_block", window_left_off),
    "gate_left_out": (laguna, "_head_gate", gate_left_out),
    "whole_head_turned": (blocks, "rotary", whole_head_turned),
    "attention_factor_left_out": (blocks, "rotary",
                                  attention_factor_left_out),
    "routed_scale_left_out": (moe, "dropless_route", routed_scale_left_out),
    "not_renormalised": (moe, "dropless_route", not_renormalised),
    "layer_0_given_an_expert_layer": (laguna, "_hidden",
                                      layer_0_given_an_expert_layer),
    "half_the_batch": (laguna, "loss_fn", half_the_batch)}

if __name__ == "__main__":
    fault = sys.argv.pop(1)
    if fault not in FAULTS:
        raise SystemExit(f"unknown fault {fault}")
    module, name, broken = FAULTS[fault]
    setattr(module, name, broken(getattr(module, name)))
    run.main()
