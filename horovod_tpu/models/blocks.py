"""What the decoder families share, written once: the RMSNorm with a plain
weight, the mean next-token cross-entropy, position-free grouped attention,
and the training step.

A family (``qwen3_next``, ``olmo_hybrid``, ``nemotron_h``, ``ouro``,
``jamba``) owns its config, its parameters, its mixers, its layer pattern
and its ``loss_fn``; what it would otherwise copy from the family before it
is here.  Nothing here knows a family: each function is told what it needs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..parallel.ring_attention import local_flash_attention


def rmsnorm(x, w, eps):
    """``x / rms(x) * w`` over the last axis, in float32."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * lax.rsqrt(var + eps) * w.astype(jnp.float32)).astype(x.dtype)


def grouped_attention(x, p, cfg):
    """Causal attention of ``cfg.n_heads`` query heads on ``cfg.n_kv_heads``
    key and value heads of ``cfg.head_dim``, no bias, **no rotary**: the
    heads see no position but the causal mask.  The Pallas flash kernel
    where ``cfg.use_flash`` resolves to it (``ops/flash_attention``)."""
    from ..ops.flash_attention import flash_attention, resolve_flash
    B, T, _ = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    with jax.named_scope("attn/full"):
        q = (x @ p["wq"]).reshape(B, T, h, hd)
        k = (x @ p["wk"]).reshape(B, T, kv, hd)
        v = (x @ p["wv"]).reshape(B, T, kv, hd)
        attend = (flash_attention if resolve_flash(cfg.use_flash, seq=T,
                                                   causal=True)
                  else local_flash_attention)
        o = attend(q, k, v, causal=True)
        return o.reshape(B, T, h * hd) @ p["wo"]


def next_token_loss(logits, targets):
    """Mean of ``-log softmax(logits)[target]`` over every token."""
    with jax.named_scope("head"):
        logp = logits - jax.scipy.special.logsumexp(logits, axis=-1,
                                                    keepdims=True)
        return -jnp.mean(jnp.take_along_axis(logp, targets[..., None],
                                             axis=-1))


def train_step(loss, optimizer):
    """``step(params, opt_state, tokens, targets) -> (params, opt_state,
    loss)`` of ``loss(params, tokens, targets)``, for use inside
    ``shard_map``; ``optimizer`` is an in-graph ``hvd.DistributedOptimizer``
    (or plain optax), which exchanges the gradients."""
    import optax

    def step(params, opt_state, tokens, targets):
        with jax.named_scope("forward"):
            value, backward = jax.vjp(lambda p: loss(p, tokens, targets),
                                      params)
        with jax.named_scope("backward"):
            grads, = backward(jnp.ones_like(value))
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return params, opt_state, value

    return step
