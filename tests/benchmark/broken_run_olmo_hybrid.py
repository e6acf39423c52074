"""Drives a rehearsal run of the ``olmo_hybrid`` family with the step
broken underneath (a child process of
``test_benchmark_broken_olmo_hybrid.py``): a function of the model is
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
from horovod_tpu.models import gated_delta, olmo_hybrid  # noqa: E402


def beta_not_doubled(mixer):
    """The mixer leaves beta in (0, 1), as ``qwen3_next``'s does; the
    counters of set-up (``beta_stats``) still read the doubled betas."""
    def bad(x, p, dims, rule=None):
        return mixer(x, p, dataclasses.replace(dims, beta_scale=1.0), rule)
    return bad


def norm_first(_block):
    """The norm BEFORE the mixer, where most decoders have it."""
    def bad(p, x, cfg):
        h = olmo_hybrid._rmsnorm(x, p["mixer_norm"], cfg.norm_eps)
        return x + (olmo_hybrid._full_attention(h, p["attn"], cfg)
                    if "attn" in p else gated_delta.gated_delta_net(
                        h, p["gdn"], cfg.gdn_dims()))
    return bad


def half_the_batch(loss_fn):
    """The loss is taken over the first half of each sequence's targets."""
    def bad(params, tokens, targets, cfg):
        logits = olmo_hybrid.forward(params, tokens, cfg)
        half = tokens.shape[1] // 2
        logp = jax.nn.log_softmax(logits[:, :half], axis=-1)
        return -jnp.mean(jnp.take_along_axis(
            logp, targets[:, :half, None], axis=-1))
    return bad


FAULTS = {"beta_not_doubled": (gated_delta, "gated_delta_net",
                               beta_not_doubled),
          "norm_first": (olmo_hybrid, "_mixer_block", norm_first),
          "half_the_batch": (olmo_hybrid, "loss_fn", half_the_batch)}

if __name__ == "__main__":
    fault = sys.argv.pop(1)
    if fault not in FAULTS:
        raise SystemExit(f"unknown fault {fault}")
    module, name, broken = FAULTS[fault]
    setattr(module, name, broken(getattr(module, name)))
    run.main()
