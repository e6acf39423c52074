"""Qwen3-Next-style hybrid decoder: Gated DeltaNet layers, gated attention,
and a dropless expert layer that is told which experts it holds.

The published ``qwen3_next`` model (HF transformers) as a training step on
the normal path: ``make_train_step(cfg, optimizer)`` has the shape of
``llama.make_train_step`` and runs inside ``shard_map`` over ``hvd.mesh()``
with an in-graph ``hvd.DistributedOptimizer`` (the gradient exchange is the
optimizer's; the loss here is this rank's own mean).

Blocks are composed, not flagged: layer ``i`` is a gated full-attention
layer when ``(i + 1) % full_attention_interval == 0`` and a Gated DeltaNet
layer otherwise; every layer ends in the expert layer of ``models/moe.py``
(``dropless_moe_ffn``), which routes over all published experts and
computes the part of the experts ``first_expert .. first_expert +
experts_held``.  Parameters are a list of per-layer dicts, each holding
``attn`` or ``gdn`` beside ``moe``.

- ``RMSNorm0(x; w) = x / rms(x) * (1 + w)`` (zero-centred weight).
- **Gated DeltaNet**: ``[q|k|v|z] = h W_qkvz``, ``[b|a] = h W_ba``; causal
  depthwise convolution + SiLU over ``[q|k|v]``; q, k repeated to the value
  heads and L2-normalised; per head ``S <- exp(g_t) S + k_t (beta_t (v_t -
  S^T k_t))^T``, ``o_t = S^T q_t``, computed in the **chunked** form;
  gated RMSNorm with ``SiLU(z)``; ``W_o``.  The mixer is
  ``models/gated_delta.py``'s (:func:`gated_delta.gated_delta_net`, shared
  with ``olmo_hybrid``), told this config's sizes and beta in (0, 1).
- **Gated attention**: a query and a gate per head from ``W_q``, RMSNorm0 on
  q and k heads, rotary on the first ``partial_rotary_factor`` of the
  head, causal attention (the Pallas flash kernel on a TPU), the result
  times ``sigmoid(gate)``, ``W_o``.

Departures from the published implementation: ``W_qkvz``'s columns are
``[q|k|v|z]`` over all heads (HF groups them per key head), which matters
only to a checkpoint converter; no multi-token-prediction head; no
auxiliary router loss (the published config has no coefficient).

The parts of a step carry ``jax.named_scope`` names a device trace shows:
``gdn/proj``, ``gdn/conv``, ``gdn/scan``, ``gdn/out``, ``attn/full``,
``moe/route``, ``moe/dispatch``, ``moe/experts``, ``moe/shared``,
``moe/combine``, ``head``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import blocks as _blocks
from . import gated_delta as _gdn
from . import moe as _moe
from .gated_delta import chunked_gated_delta_rule  # noqa: F401  (this
#   module's name for the rule: ``_gated_delta_net`` calls it by that name)
from ..parallel.ring_attention import local_flash_attention


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    vocab_size: int = 151936
    d_model: int = 2048
    n_layers: int = 48
    full_attention_interval: int = 4
    # gated attention
    n_heads: int = 16
    n_kv_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    # Gated DeltaNet
    lin_k_heads: int = 16
    lin_v_heads: int = 32
    lin_k_dim: int = 128
    lin_v_dim: int = 128
    conv_kernel: int = 4
    chunk: int = 64
    # expert layer: the router's width, and the share held here
    n_experts: int = 512
    top_k: int = 10
    d_expert: int = 512
    d_shared: int = 512
    first_expert: int = 0
    experts_held: Optional[int] = None      # None = all of them
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    # Pallas flash attention: True/False, or None = on a TPU (see
    # ops/flash_attention.resolve_flash).
    use_flash: Optional[bool] = None

    def is_full_attention(self, i: int) -> bool:
        return (i + 1) % self.full_attention_interval == 0

    def gdn_dims(self) -> _gdn.GatedDeltaDims:
        return _gdn.GatedDeltaDims(
            k_heads=self.lin_k_heads, v_heads=self.lin_v_heads,
            k_dim=self.lin_k_dim, v_dim=self.lin_v_dim,
            conv_kernel=self.conv_kernel, chunk=self.chunk,
            norm_eps=self.norm_eps)

    def moe_cfg(self) -> _moe.DroplessMoEConfig:
        return _moe.DroplessMoEConfig(
            d_model=self.d_model, d_ff=self.d_expert,
            n_experts=self.n_experts, top_k=self.top_k,
            first_expert=self.first_expert, experts_held=self.experts_held,
            d_shared=self.d_shared, dtype=self.dtype)


def tiny(**kw) -> Qwen3NextConfig:
    """One period at test size: 16 experts of which 4 are held, top-2."""
    base = dict(vocab_size=256, d_model=64, n_layers=4, n_heads=4,
                n_kv_heads=2, head_dim=16, lin_k_heads=2, lin_v_heads=4,
                lin_k_dim=16, lin_v_dim=16, n_experts=16, top_k=2,
                d_expert=32, d_shared=32, experts_held=4,
                dtype=jnp.float32, use_flash=False)
    base.update(kw)
    return Qwen3NextConfig(**base)


def qwen3_next_80b_a3b() -> Qwen3NextConfig:
    """The published sizes, every expert held."""
    return Qwen3NextConfig()


# ------------------------------------------------------------------- params
def init_params(cfg: Qwen3NextConfig, key) -> Dict:
    d, dt = cfg.d_model, cfg.dtype
    keys = iter(jax.random.split(key, 2 + 12 * cfg.n_layers))

    def dense(fan_in, shape):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                / np.sqrt(fan_in)).astype(dt)

    def gdn():
        return _gdn.init_params(cfg.gdn_dims(), d, dt, keys)

    def attn():
        h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        return {"wq": dense(d, (d, h * 2 * hd)), "wk": dense(d, (d, kv * hd)),
                "wv": dense(d, (d, kv * hd)), "q_norm": jnp.zeros((hd,), dt),
                "k_norm": jnp.zeros((hd,), dt),
                "wo": dense(h * hd, (h * hd, d))}

    layers = []
    for i in range(cfg.n_layers):
        full = cfg.is_full_attention(i)
        layers.append({
            "mixer_norm": jnp.zeros((d,), dt),
            "attn" if full else "gdn": attn() if full else gdn(),
            "moe_norm": jnp.zeros((d,), dt),
            "moe": _moe.dropless_init_params(cfg.moe_cfg(), next(keys))})
    return {"embed": dense(d, (cfg.vocab_size, d)), "layers": layers,
            "final_norm": jnp.zeros((d,), dt),
            "lm_head": dense(d, (d, cfg.vocab_size))}


# ------------------------------------------------------------------ forward
def _rmsnorm0(x, w, eps):
    """Zero-centred RMSNorm: ``x / rms(x) * (1 + w)``, in float32."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * lax.rsqrt(var + eps)
            * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def _partial_rope(x, rotary, theta):
    """x [B, T, H, hd]: the first ``rotary`` of the head rotate, pairs
    (i, i + rotary / 2) together; the rest passes."""
    half = rotary // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs[None]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a = x[..., :half].astype(jnp.float32)
    b = x[..., half:rotary].astype(jnp.float32)
    return jnp.concatenate(
        [(a * cos - b * sin).astype(x.dtype),
         (b * cos + a * sin).astype(x.dtype), x[..., rotary:]], axis=-1)


def _gated_delta_net(x, p, cfg: Qwen3NextConfig):
    return _gdn.gated_delta_net(x, p, cfg.gdn_dims(),
                                rule=chunked_gated_delta_rule)


def _gated_attention(x, p, cfg: Qwen3NextConfig):
    from ..ops.flash_attention import flash_attention, resolve_flash
    B, T, _ = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    rotary = int(hd * cfg.partial_rotary_factor)
    with jax.named_scope("attn/full"):
        qg = (x @ p["wq"]).reshape(B, T, h, 2 * hd)
        q, gate = qg[..., :hd], qg[..., hd:]
        k = (x @ p["wk"]).reshape(B, T, kv, hd)
        v = (x @ p["wv"]).reshape(B, T, kv, hd)
        q = _partial_rope(_rmsnorm0(q, p["q_norm"], cfg.norm_eps), rotary,
                          cfg.rope_theta)
        k = _partial_rope(_rmsnorm0(k, p["k_norm"], cfg.norm_eps), rotary,
                          cfg.rope_theta)
        attend = (flash_attention if resolve_flash(cfg.use_flash, seq=T,
                                                   causal=True)
                  else local_flash_attention)
        o = attend(q, k, v, causal=True)
        o = (o.astype(jnp.float32)
             * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(x.dtype)
        return o.reshape(B, T, h * hd) @ p["wo"]


def _mixer_block(p, x, cfg: Qwen3NextConfig):
    h = _rmsnorm0(x, p["mixer_norm"], cfg.norm_eps)
    return x + (_gated_attention(h, p["attn"], cfg) if "attn" in p
                else _gated_delta_net(h, p["gdn"], cfg))


def _moe_block(p, x, cfg: Qwen3NextConfig):
    """``(x, held_counts [experts_held])``."""
    B, T, D = x.shape
    h = _rmsnorm0(x, p["moe_norm"], cfg.norm_eps)
    y, counts = _moe.dropless_moe_ffn(h.reshape(B * T, D), p["moe"],
                                      cfg.moe_cfg())
    return x + y.reshape(B, T, D), counts


def _forward(params, tokens, cfg: Qwen3NextConfig):
    """``(logits float32 [B, T, V], held_counts [n_layers,
    experts_held])``."""
    x = params["embed"][tokens]
    # Each mixer and each expert layer is recomputed in the backward pass
    # (their activations at 16 k tokens are several GB, their inputs 67
    # MB), as two regions a layer, so that the backward pass never holds a
    # mixer's and an expert layer's intermediates together.
    mixer = jax.checkpoint(_mixer_block, static_argnums=(2,))
    experts = jax.checkpoint(_moe_block, static_argnums=(2,))
    counts = []
    for p in params["layers"]:
        x, c = experts(p, mixer(p, x, cfg), cfg)
        counts.append(c)
    with jax.named_scope("head"):
        x = _rmsnorm0(x, params["final_norm"], cfg.norm_eps)
        logits = jnp.einsum("btd,dv->btv", x, params["lm_head"],
                            preferred_element_type=jnp.float32)
    return logits, jnp.stack(counts)


def forward(params, tokens, cfg: Qwen3NextConfig):
    """Logits ``[B, T, vocab]`` in float32."""
    return _forward(params, tokens, cfg)[0]


def expert_load(params, tokens, cfg: Qwen3NextConfig):
    """Assignments that land on each held expert, ``[n_layers,
    experts_held]`` int32, for a batch of tokens: the counter the
    benchmark reads in set-up.  ``tokens.size * top_k`` assignments are
    made in each layer."""
    return _forward(params, tokens, cfg)[1]


def loss_fn(params, tokens, targets, cfg: Qwen3NextConfig):
    """Mean next-token cross-entropy over this rank's tokens."""
    return _blocks.next_token_loss(forward(params, tokens, cfg), targets)


# --------------------------------------------------------------- train step
def make_train_step(cfg: Qwen3NextConfig, optimizer):
    """:func:`blocks.train_step` of this module's ``loss_fn``, looked up
    when the step runs."""
    return _blocks.train_step(
        lambda p, tokens, targets: loss_fn(p, tokens, targets, cfg),
        optimizer)
