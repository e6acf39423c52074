"""What the decoder families share, written once: the RMSNorm with a plain
weight, the mean next-token cross-entropy (whole, or the head some tokens at
a time), position-free grouped attention,
a rotary told its kind (plain or YaRN) and the width it turns, and the
training step.

A family (``qwen3_next``, ``olmo_hybrid``, ``nemotron_h``, ``ouro``,
``jamba``, ``laguna``, ``joyai``) owns its config, its parameters, its
mixers, its layer pattern and its ``loss_fn``; what it would otherwise copy from the
family before it is here.  Nothing here knows a family: each function is
told what it needs.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
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


@dataclasses.dataclass(frozen=True)
class Rotary:
    """A rotary embedding as a published ``rope_parameters`` entry states
    it: the leading ``width`` numbers of a head turn, the rest pass.

    ``kind`` ``default``: frequencies ``theta ** (-2j / width)``.
    ``yarn``: each is a blend of itself (extrapolation) and itself over
    ``factor`` (interpolation) by a linear ramp over the frequency's index,
    between the two indices at which a frequency makes ``beta_fast`` and
    ``beta_slow`` turns in ``original_max`` positions (the fast ones are
    kept, the slow ones divided), and ``cos`` and ``sin`` are multiplied by
    ``attention_factor`` (``None``: ``0.1 ln(factor) + 1``)."""
    width: int
    theta: float = 10000.0
    kind: str = "default"
    factor: float = 1.0
    original_max: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float | None = None

    def __post_init__(self):
        if self.kind not in ("default", "yarn"):
            raise ValueError(f"rotary kind {self.kind!r}: default or yarn")
        if self.width % 2:
            raise ValueError(f"a rotary turns pairs: width {self.width}")


def rotary_frequencies(rot: Rotary):
    """``(frequencies [width / 2] float64, scale of cos and sin)``, in
    NumPy: constants of a traced program."""
    half = rot.width // 2
    plain = float(rot.theta) ** (-np.arange(half, dtype=np.float64) / half)
    if rot.kind == "default":
        return plain, 1.0

    def index_of(turns):
        """The (fractional) index of the frequency that makes ``turns``
        turns in ``original_max`` positions."""
        return (rot.width * math.log(rot.original_max / (turns * 2 * math.pi))
                / (2 * math.log(rot.theta)))

    low = max(math.floor(index_of(rot.beta_fast)), 0)
    high = min(math.ceil(index_of(rot.beta_slow)), rot.width - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half, dtype=np.float64) - low) / (high - low),
                   0.0, 1.0)
    scale = (0.1 * math.log(rot.factor) + 1.0
             if rot.attention_factor is None else rot.attention_factor)
    return plain / rot.factor * ramp + plain * (1.0 - ramp), float(scale)


def rotary(x, rot: Rotary):
    """x ``[B, T, H, hd]`` with its leading ``rot.width`` numbers turned by
    their position's angle, pairs ``(i, i + width / 2)`` together (the
    half-split pairing); positions are ``0 .. T - 1``."""
    half = rot.width // 2
    freqs, scale = rotary_frequencies(rot)
    ang = (jnp.arange(x.shape[1], dtype=jnp.float32)[:, None]
           * jnp.asarray(freqs, jnp.float32)[None])
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    a = x[..., :half].astype(jnp.float32)
    b = x[..., half:rot.width].astype(jnp.float32)
    return jnp.concatenate(
        [(a * cos - b * sin).astype(x.dtype),
         (b * cos + a * sin).astype(x.dtype), x[..., rot.width:]], axis=-1)


def next_token_loss(logits, targets):
    """Mean of ``-log softmax(logits)[target]`` over every token."""
    with jax.named_scope("head"):
        logp = logits - jax.scipy.special.logsumexp(logits, axis=-1,
                                                    keepdims=True)
        return -jnp.mean(jnp.take_along_axis(logp, targets[..., None],
                                             axis=-1))


def next_token_loss_in_blocks(x, targets, logits_of, block, mask=None):
    """Mean next-token cross-entropy of the last layer's output ``x [B, T,
    d]``, the head ``block`` tokens at a time (``logits_of(x_block)`` gives
    float32 logits), each block recomputed in the backward pass: logits
    over a vocabulary are hundreds of MB a thousand tokens.  With a
    ``mask [B, T]`` the mean is over the positions it keeps (a prediction
    module's last positions have no target)."""
    B, T, _ = x.shape
    block = min(block, T)
    pad = (-T) % block

    def of_block(args):
        xb, tb = args
        logits = logits_of(xb)
        return jax.scipy.special.logsumexp(logits, axis=-1) - (
            jnp.take_along_axis(logits, tb[..., None], axis=-1)[..., 0])

    def blocks(y):                      # [B, T, ...] -> [T / block, B, ...]
        y = jnp.pad(y, ((0, 0), (0, pad)) + ((0, 0),) * (y.ndim - 2))
        return jnp.moveaxis(y.reshape((B, -1, block) + y.shape[2:]), 1, 0)

    with jax.named_scope("head"):
        nll = lax.map(jax.checkpoint(of_block), (blocks(x), blocks(targets)))
        nll = jnp.moveaxis(nll, 0, 1).reshape(B, -1)[:, :T]
        if mask is None:
            return jnp.mean(nll)
        return jnp.sum(jnp.where(mask, nll, 0.0)) / jnp.sum(mask)


def train_step(loss, optimizer, after_update=None):
    """``step(params, opt_state, tokens, targets) -> (params, opt_state,
    loss)`` of ``loss(params, tokens, targets)``, for use inside
    ``shard_map``; ``optimizer`` is an in-graph ``hvd.DistributedOptimizer``
    (or plain optax), which exchanges the gradients.  A state that changes
    by another rule than the gradient's (a router's selection bias, moved by
    the experts' load) has ``after_update``: ``loss`` then returns ``(value,
    aux)`` and the step ends with ``params = after_update(params, aux)``."""
    import optax

    def step(params, opt_state, tokens, targets):
        with jax.named_scope("forward"):
            value, backward, *aux = jax.vjp(
                lambda p: loss(p, tokens, targets), params,
                has_aux=after_update is not None)
        with jax.named_scope("backward"):
            grads, = backward(jnp.ones_like(value))
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            if after_update is not None:
                params = after_update(params, *aux)
        return params, opt_state, value

    return step
