"""Drives a run of the ``joyai`` family with the step broken underneath (a
child process of ``test_benchmark_broken_joyai.py``, which also plants
``FAULTS`` in its own process; on the chip, the cell's own sizes): a
function of the model is replaced before the step is built, everything else
is ``run.py`` as it stands."""

import dataclasses
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax.numpy as jnp                         # noqa: E402

from benchmark import run                       # noqa: E402
from horovod_tpu.models import joyai, latent_attention, moe   # noqa: E402


def module_term_dropped(loss_fn):
    """``lambda`` 0: the loss is the main term alone, and nothing of the
    prediction module takes a gradient."""
    return lambda params, tokens, targets, cfg: loss_fn(
        params, tokens, targets, dataclasses.replace(cfg, mtp_weight=0.0))


def rope_score_dropped(qkv):
    """The rotary part of every query is zeros: a score is ``q_nope .
    k_nope / sqrt(192)`` alone and no head sees a position."""
    def bad(p, h, dims):
        q, k, v = qkv(p, h, dims)
        return q.at[..., :dims.d_rope].set(0), k, v
    return bad


def bias_left_unchanged(update_bias):
    """The step ends with the optimizer's update: the selection bias stays
    what the seed made it."""
    return lambda params, counts, cfg: params


def bias_weighs(route):
    """The chosen experts weigh by ``s + b`` over its sum, not by ``s``."""
    def bad(x, w, cfg, bias=None):
        ids, weights = route(x, w, cfg, bias)
        top = weights / cfg.routed_scale
        lifted = top * (1.0 + 50.0 * jnp.take(bias, ids))
        return ids, cfg.routed_scale * lifted / lifted.sum(-1, keepdims=True)
    return bad


FAULTS = {
    "module_term_dropped": (joyai, "loss_fn", module_term_dropped),
    "rope_score_dropped": (latent_attention, "qkv", rope_score_dropped),
    "bias_left_unchanged": (joyai, "update_bias", bias_left_unchanged),
    "bias_weighs": (moe, "dropless_route", bias_weighs)}



def plant(fault):
    if fault not in FAULTS:
        raise SystemExit(f"unknown fault {fault}")
    module, name, broken = FAULTS[fault]
    setattr(module, name, broken(getattr(module, name)))


if __name__ == "__main__":
    plant(sys.argv.pop(1))
    run.main()
