"""Drives a rehearsal run of the ``ouro`` family with the step broken
underneath (a child process of ``test_benchmark_broken_ouro.py``): a
function of the model is replaced before the step is built, everything else
is ``run.py`` as it stands."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import dataclasses                              # noqa: E402

import jax                                      # noqa: E402
import jax.numpy as jnp                         # noqa: E402
from jax import lax                             # noqa: E402

from benchmark import run                       # noqa: E402
from horovod_tpu.models import ouro             # noqa: E402


def three_passes(looped_stack):
    """The stack is run three times where the configuration says four: the
    third pass takes the remainder."""
    def bad(cfg, layers, final_norm, x0):
        return looped_stack(dataclasses.replace(cfg, total_ut_steps=3),
                            layers, final_norm, x0)
    return bad


def norm_outside_the_loop(_looped_stack):
    """The final norm only before the head and the gate, as in a decoder
    that is not looped: the next pass starts from the un-normed stream."""
    def bad(cfg, layers, final_norm, x0):
        xs, h = [], x0
        for _ in range(cfg.total_ut_steps):
            h = lax.scan(lambda y, p: (ouro._layer(p, y, cfg), None), h,
                         layers)[0]
            xs.append(ouro._close(final_norm, h, cfg))
        return jnp.stack(xs)
    return bad


def last_pass_gated(_exit_log_probs):
    """The last pass's probability is gated like the others', so the exit
    distribution does not sum to one."""
    def bad(z):
        stay = jax.nn.log_sigmoid(-z[:-1])
        before = jnp.concatenate([jnp.zeros_like(z[:1]),
                                  jnp.cumsum(stay, axis=0)])
        return before + jax.nn.log_sigmoid(z)
    return bad


def no_output_norm(_layer):
    """The MLP's output goes into the residual without its norm."""
    def bad(p, x, cfg):
        B, T, _ = x.shape
        h, hd, eps = cfg.n_heads, cfg.head_dim, cfg.norm_eps
        with jax.named_scope("attn/full"):
            a = ouro._rmsnorm(x, p["attn_norm"], eps)
            pos = jnp.arange(T)
            q = ouro._rope((a @ p["wq"]).reshape(B, T, h, hd), pos,
                           cfg.rope_theta)
            k = ouro._rope((a @ p["wk"]).reshape(B, T, h, hd), pos,
                           cfg.rope_theta)
            v = (a @ p["wv"]).reshape(B, T, h, hd)
            o = ouro.local_flash_attention(q, k, v, causal=True).reshape(
                B, T, h * hd)
            x = x + ouro._rmsnorm(o @ p["wo"], p["attn_out_norm"], eps)
        with jax.named_scope("mlp"):
            m = ouro._rmsnorm(x, p["mlp_norm"], eps)
            return x + (jax.nn.silu(m @ p["w_gate"]) * (m @ p["w_up"])) @ p[
                "w_down"]
    return bad


FAULTS = {"three_passes": ("looped_stack", three_passes),
          "norm_outside_the_loop": ("looped_stack", norm_outside_the_loop),
          "last_pass_gated": ("exit_log_probs", last_pass_gated),
          "no_output_norm": ("_layer", no_output_norm)}

if __name__ == "__main__":
    fault = sys.argv.pop(1)
    if fault not in FAULTS:
        raise SystemExit(f"unknown fault {fault}")
    name, broken = FAULTS[fault]
    setattr(ouro, name, broken(getattr(ouro, name)))
    run.main()
