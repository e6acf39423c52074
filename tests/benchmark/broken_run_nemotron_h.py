"""Drives a rehearsal run of the ``nemotron_h`` family with the step broken
underneath (a child process of ``test_benchmark_broken_nemotron_h.py``): a
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
from horovod_tpu.models import mamba2, moe      # noqa: E402


def skip_left_out(mixer):
    """``y_t = S_t C_t`` without ``+ D x_t``."""
    def bad(u, p, dims, scan=None):
        return mixer(u, dict(p, D=jnp.zeros_like(p["D"])), dims, scan)
    return bad


def norm_over_all_channels(norm):
    """The gated norm's mean square over all of a token's channels instead
    of a group's."""
    def bad(y, z, w, groups, eps):
        return norm(y, z, w, 1, eps)
    return bad


def weights_from_score_plus_bias(route):
    """The chosen experts weighed by ``s + b`` instead of ``s``."""
    def bad(x, router_w, cfg, bias=None):
        ids, _ = route(x, router_w, cfg, bias)
        scores = jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.float32), router_w.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST)) + bias.astype(jnp.float32)
        top = jnp.take_along_axis(scores, ids, axis=-1)
        return ids, cfg.routed_scale * top / jnp.sum(top, axis=-1,
                                                     keepdims=True)
    return bad


FAULTS = {"skip_left_out": (mamba2, "mamba2", skip_left_out),
          "norm_over_all_channels": (mamba2, "gated_group_norm",
                                     norm_over_all_channels),
          "weights_from_score_plus_bias": (moe, "dropless_route",
                                           weights_from_score_plus_bias)}

if __name__ == "__main__":
    fault = sys.argv.pop(1)
    if fault not in FAULTS:
        raise SystemExit(f"unknown fault {fault}")
    module, name, broken = FAULTS[fault]
    setattr(module, name, broken(getattr(module, name)))
    run.main()
