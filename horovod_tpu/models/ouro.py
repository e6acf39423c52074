"""Ouro-style looped decoder: one stack of layers applied ``total_ut_steps``
times to the same weights, a head and an exit gate after every pass, and
the expected-exit loss.

The published ``ouro`` model ("Scaling Latent Reasoning via Looped Language
Models") as a training step on the normal path: ``make_train_step(cfg,
optimizer)`` has the shape of ``llama.make_train_step`` and the hybrids'
and runs inside ``shard_map`` over ``hvd.mesh()`` with an in-graph
``hvd.DistributedOptimizer`` (the gradient exchange is the optimizer's; the
loss here is this rank's own mean).

With ``R = total_ut_steps``, ``L`` layers and positions ``0..T-1`` the same
in every pass::

    x(0) = E[tokens]
    for r = 1..R:                       # the same weights in every pass
        h = x(r-1)
        for l = 1..L:
            a = RMSNorm(h; g1_l)
            q, k, v = a Wq_l, a Wk_l, a Wv_l    # heads with keys of their own
            q, k = rope(q), rope(k)             # the whole head rotates
            o = causal_softmax_attention(q, k, v)   # scale head_dim^-1/2
            h = h + RMSNorm(o Wo_l; g2_l)
            m = RMSNorm(h; g3_l)
            h = h + RMSNorm((silu(m Wg_l) * (m Wu_l)) Wd_l; g4_l)
        x(r) = RMSNorm(h; g_f)          # closes every pass, feeds the next
        nll_i(r) = -log softmax(x_i(r) W_head)[target_i]
        lam_i(r) = sigmoid(x_i(r) . w_gate + b_gate)
    p_i(r) = lam_i(r) prod_{j<r} (1 - lam_i(j))  for r < R
    p_i(R) = prod_{j<R} (1 - lam_i(j))           # the last pass takes the rest
    loss = mean_i [ sum_r p_i(r) nll_i(r) - beta H(p_i) ]
    H(p) = -sum_r p(r) log p(r)

Parameters: ``embed``, ``layers`` (ONE dict of arrays stacked over the
layers: ``attn_norm wq wk wv wo attn_out_norm mlp_norm w_gate w_up w_down
mlp_out_norm``), ``final_norm``, ``gate`` (``w [d]``, ``b []``),
``lm_head``.

What the published ``config.json`` does not settle, and what is assumed
here (``benchmark/configs/ouro-2_6b-16l.json`` lists the same under
``assumed``):

1. four norms a layer, on the input AND the output of both sublayers (the
   published modelling code's ``input_layernorm``, ``input_layernorm_2``,
   ``post_attention_layernorm``, ``post_attention_layernorm_2``), the
   output norms inside the residual;
2. the final norm sits INSIDE the loop: it closes every pass, and its
   output is both what the head and the gate read and what the next pass
   starts from;
3. the gate is ``Linear(hidden, 1)`` with a bias on the normed state, one
   gate for all passes;
4. ``beta`` (0.05) weighs the entropy of the exit distribution (the
   paper's first-stage objective; the config does not carry it);
5. no rotary scaling (``rope_scaling`` is null), no bias on any projection;
6. ``early_exit_threshold`` 1.0 means that no pass is skipped at inference;
   it is unused in training, which always runs every pass.

The stack is a ``lax.scan`` over the stacked weights inside a ``lax.scan``
over the passes, so the backward pass sums ``R`` contributions into every
weight's gradient, in the weights' own type.  Each layer application is
recomputed in the backward pass (its input is what is kept: ``R x L``
residual streams), and each pass's head ``HEAD_TOKENS`` tokens at a time,
recomputed too, so that ``R`` heads over a whole vocabulary never hold
``R`` arrays of float32 logits.

The parts of a step carry ``jax.named_scope`` names a device trace shows:
``attn/full`` (norms, projections, rotary and kernels), ``mlp`` (the
SwiGLU and its two norms), ``head`` (logits and each token's loss),
``loop/carry`` (the norm that closes a pass), ``loop/exit`` (the gate, the
exit distribution, the mixture of the passes' losses and the entropy).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import blocks as _blocks
from .llama import _rope
from ..parallel.ring_attention import local_flash_attention

# tokens of a sequence whose logits over the whole vocabulary are held
# together, in the forward pass and again in the backward pass
HEAD_TOKENS = 2048


@dataclasses.dataclass(frozen=True)
class OuroConfig:
    vocab_size: int = 49152
    d_model: int = 2048
    n_layers: int = 48
    n_heads: int = 16               # every head has keys of its own
    head_dim: int = 128
    d_ff: int = 5632
    total_ut_steps: int = 4         # passes over the stack
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    entropy_beta: float = 0.05
    dtype: Any = jnp.bfloat16
    # Pallas flash attention: True/False, or None = on a TPU (see
    # ops/flash_attention.resolve_flash).
    use_flash: Optional[bool] = None


def tiny(**kw) -> OuroConfig:
    """Two layers, four passes, heads of 16, at test size."""
    base = dict(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                head_dim=16, d_ff=96, dtype=jnp.float32, use_flash=False)
    base.update(kw)
    return OuroConfig(**base)


def ouro_2_6b() -> OuroConfig:
    """The published sizes."""
    return OuroConfig()


# ------------------------------------------------------------------- params
def init_params(cfg: OuroConfig, key) -> Dict:
    d, f, n, dt = cfg.d_model, cfg.d_ff, cfg.n_layers, cfg.dtype
    e = cfg.n_heads * cfg.head_dim
    keys = iter(jax.random.split(key, 10))

    def dense(fan_in, shape):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                / np.sqrt(fan_in)).astype(dt)

    ones = lambda: jnp.ones((n, d), dt)
    layers = {"attn_norm": ones(), "wq": dense(d, (n, d, e)),
              "wk": dense(d, (n, d, e)), "wv": dense(d, (n, d, e)),
              "wo": dense(e, (n, e, d)), "attn_out_norm": ones(),
              "mlp_norm": ones(), "w_gate": dense(d, (n, d, f)),
              "w_up": dense(d, (n, d, f)), "w_down": dense(f, (n, f, d)),
              "mlp_out_norm": ones()}
    return {"embed": dense(d, (cfg.vocab_size, d)), "layers": layers,
            "final_norm": jnp.ones((d,), dt),
            "gate": {"w": dense(d, (d,)), "b": jnp.zeros((), dt)},
            "lm_head": dense(d, (d, cfg.vocab_size))}


# ------------------------------------------------------------------ forward
_rmsnorm = _blocks.rmsnorm


def _layer(p, x, cfg: OuroConfig):
    """One application of one layer; ``p`` holds that layer's weights."""
    from ..ops.flash_attention import flash_attention, resolve_flash
    B, T, _ = x.shape
    h, hd, eps = cfg.n_heads, cfg.head_dim, cfg.norm_eps
    with jax.named_scope("attn/full"):
        a = _rmsnorm(x, p["attn_norm"], eps)
        positions = jnp.arange(T)
        q = _rope((a @ p["wq"]).reshape(B, T, h, hd), positions,
                  cfg.rope_theta)
        k = _rope((a @ p["wk"]).reshape(B, T, h, hd), positions,
                  cfg.rope_theta)
        v = (a @ p["wv"]).reshape(B, T, h, hd)
        attend = (flash_attention if resolve_flash(cfg.use_flash, seq=T,
                                                   causal=True)
                  else local_flash_attention)
        o = attend(q, k, v, causal=True).reshape(B, T, h * hd)
        x = x + _rmsnorm(o @ p["wo"], p["attn_out_norm"], eps)
    with jax.named_scope("mlp"):
        m = _rmsnorm(x, p["mlp_norm"], eps)
        y = (jax.nn.silu(m @ p["w_gate"]) * (m @ p["w_up"])) @ p["w_down"]
        return x + _rmsnorm(y, p["mlp_out_norm"], eps)


def _close(final_norm, h, cfg: OuroConfig):
    """The norm that ends a pass: the head, the gate and the next pass all
    start from its output."""
    with jax.named_scope("loop/carry"):
        return _rmsnorm(h, final_norm, cfg.norm_eps)


def _layer_of(layers, l):
    return jax.tree_util.tree_map(
        lambda w: lax.dynamic_index_in_dim(w, l, keepdims=False), layers)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def looped_stack(cfg: OuroConfig, layers, final_norm, x0):
    """``x(1) .. x(R)`` stacked, ``[R, B, T, d]``: the stack applied
    ``total_ut_steps`` times to the same ``layers``, each pass closed by
    the final norm.

    Its backward pass is written out, because the weights are shared: every
    layer application is recomputed from the state that entered it (``R x
    L`` residual streams are what the forward pass keeps) and adds its
    weights' gradient into ONE accumulator, in place and in the weights'
    own type.  Left to ``lax.scan``'s transpose the inner loop would return
    a gradient of the whole stack a pass, held beside the sum of the passes
    before it: a second copy of every layer's gradient."""
    return _looped_forward(cfg, layers, final_norm, x0)[0]


def _looped_forward(cfg, layers, final_norm, x0):
    def one(x, _):
        h, entered = lax.scan(lambda h, p: (_layer(p, h, cfg), h), x, layers)
        x = _close(final_norm, h, cfg)
        return x, (x, entered, h)
    xs, entered, hs = lax.scan(one, x0, None, length=cfg.total_ut_steps)[1]
    return xs, (layers, final_norm, entered, hs)


def _looped_backward(cfg, kept, ct_xs):
    layers, final_norm, entered, hs = kept
    n = cfg.n_layers

    def layer_back(carry, at):
        ct_h, sums = carry
        x, l = at
        # the layer's forward again, then its backward
        ct_p, ct_h = jax.vjp(lambda p, y: _layer(p, y, cfg),
                             _layer_of(layers, l), x)[1](ct_h)
        sums = jax.tree_util.tree_map(
            lambda s, g: lax.dynamic_update_index_in_dim(
                s, lax.dynamic_index_in_dim(s, l, keepdims=False) + g, l, 0),
            sums, ct_p)
        return (ct_h, sums), None

    def pass_back(carry, at):
        ct_next, ct_norm, sums = carry      # ct_next: from the pass after
        ct_x, x_in, h = at
        ct_w, ct_h = jax.vjp(lambda w, y: _close(w, y, cfg),
                             final_norm, h)[1](ct_x + ct_next)
        (ct_h, sums), _ = lax.scan(layer_back, (ct_h, sums),
                                   (x_in, jnp.arange(n)), reverse=True)
        return (ct_h, ct_norm + ct_w, sums), None

    zeros = jax.tree_util.tree_map(jnp.zeros_like, (final_norm, layers))
    (ct_x0, ct_norm, sums), _ = lax.scan(
        pass_back, (jnp.zeros_like(ct_xs[0]),) + zeros,
        (ct_xs, entered, hs), reverse=True)
    return sums, ct_norm, ct_x0


looped_stack.defvjp(_looped_forward, _looped_backward)


def _passes(params, tokens, cfg: OuroConfig, read):
    """``read(x(r))`` for ``r = 1..R``, stacked over the passes."""
    return lax.map(read, looped_stack(cfg, params["layers"],
                                      params["final_norm"],
                                      params["embed"][tokens]))


def _gate_logit(params, x):
    """``x . w_gate + b_gate`` in float32, ``[B, T]``."""
    with jax.named_scope("loop/exit"):
        gate = params["gate"]
        return jnp.einsum("btd,d->bt", x, gate["w"],
                          preferred_element_type=jnp.float32) + gate[
                              "b"].astype(jnp.float32)


def _logits(params, x):
    return jnp.einsum("btd,dv->btv", x, params["lm_head"],
                      preferred_element_type=jnp.float32)


def _token_nll(params, x, targets):
    """Each token's ``-log softmax(x W_head)[target]`` in float32, ``[B,
    T]``: ``HEAD_TOKENS`` tokens at a time, each block recomputed in the
    backward pass."""
    B, T, d = x.shape
    block = min(HEAD_TOKENS, T)
    pad = (-T) % block

    def of_block(args):
        xb, tb = args
        logits = _logits(params, xb)
        return jax.scipy.special.logsumexp(logits, axis=-1) - (
            jnp.take_along_axis(logits, tb[..., None], axis=-1)[..., 0])

    def blocks(y):                      # [B, T, ...] -> [T / block, B, ...]
        y = jnp.pad(y, ((0, 0), (0, pad)) + ((0, 0),) * (y.ndim - 2))
        return jnp.moveaxis(y.reshape((B, -1, block) + y.shape[2:]), 1, 0)

    with jax.named_scope("head"):
        nll = lax.map(jax.checkpoint(of_block), (blocks(x), blocks(targets)))
        return jnp.moveaxis(nll, 0, 1).reshape(B, -1)[:, :T]


def exit_log_probs(z):
    """``log p(r)`` from the gates' logits ``z [R, ...]``: ``p(r) = lam(r)
    prod_{j<r} (1 - lam(j))`` with ``lam = sigmoid(z)``, and the last pass
    takes what is left (its own gate is not read)."""
    stay = jax.nn.log_sigmoid(-z[:-1])              # log (1 - lam(j))
    before = jnp.concatenate([jnp.zeros_like(z[:1]),
                              jnp.cumsum(stay, axis=0)])
    return before + jnp.concatenate([jax.nn.log_sigmoid(z[:-1]),
                                     jnp.zeros_like(z[:1])])


def expected_exit_loss(nll, z, beta):
    """``mean_i [sum_r p_i(r) nll_i(r) - beta H(p_i)]`` from ``nll`` and the
    gates' logits ``z``, both ``[R, B, T]`` float32."""
    with jax.named_scope("loop/exit"):
        logp = exit_log_probs(z)
        p = jnp.exp(logp)
        entropy = -jnp.sum(p * logp, axis=0)
        return jnp.mean(jnp.sum(p * nll, axis=0) - beta * entropy)


def _exits(params, tokens, targets, cfg: OuroConfig):
    """``(nll, z)``, each ``[R, B, T]`` float32: every pass's token losses
    and gate logits."""
    return _passes(params, tokens, cfg, lambda x: (
        _token_nll(params, x, targets), _gate_logit(params, x)))


def forward(params, tokens, cfg: OuroConfig):
    """``(logits [R, B, T, vocab], p [R, B, T])`` in float32: every pass's
    head and the exit distribution.  Whole, for the tests' sizes."""
    logits, z = _passes(params, tokens, cfg, lambda x: (
        _logits(params, x), _gate_logit(params, x)))
    return logits, jnp.exp(exit_log_probs(z))


def exit_stats(params, tokens, targets, cfg: OuroConfig):
    """Of a batch at these weights: the mean exit probability of each pass
    ``[R]``, the mean entropy of the exit distribution, and each pass's
    mean loss ``[R]``.  A counter for set-up, not for a step: the passes
    run forward once more."""
    nll, z = _exits(params, tokens, targets, cfg)
    logp = exit_log_probs(z)
    p = jnp.exp(logp)
    return {"p_mean": jnp.mean(p, axis=(1, 2)),
            "entropy_mean": jnp.mean(-jnp.sum(p * logp, axis=0)),
            "nll_mean": jnp.mean(nll, axis=(1, 2))}


def loss_fn(params, tokens, targets, cfg: OuroConfig):
    """The expected-exit loss over this rank's tokens."""
    return expected_exit_loss(*_exits(params, tokens, targets, cfg),
                              cfg.entropy_beta)


# --------------------------------------------------------------- train step
def make_train_step(cfg: OuroConfig, optimizer):
    """:func:`blocks.train_step` of this module's ``loss_fn``, looked up
    when the step runs."""
    return _blocks.train_step(
        lambda p, tokens, targets: loss_fn(p, tokens, targets, cfg),
        optimizer)
