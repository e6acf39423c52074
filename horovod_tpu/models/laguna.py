"""Laguna-style expert decoder: window and full attention layers with head
counts of their own, a gate a head, two rotaries, a leading dense layer
before the expert layers.

The published ``laguna`` model as a training step on the normal path:
``make_train_step(cfg, optimizer)`` has the shape of
``llama.make_train_step`` and the hybrids' and runs inside ``shard_map``
over ``hvd.mesh()`` with an in-graph ``hvd.DistributedOptimizer`` (the
gradient exchange is the optimizer's; the loss here is this rank's own
mean).

The layer table is read from three per-layer lists, as the published
config states them: ``layer_types`` (``full_attention`` or
``sliding_attention``), ``heads_per_layer`` (the published
``num_attention_heads_per_layer``) and ``mlp_layer_types`` (``dense`` or
``sparse``).  **The layers' parameters differ in shape** (``Wq`` is
``d_model x 128 heads_i``; an MLP is one SwiGLU or an expert layer), so the
stack is a Python loop over a list of per-layer dicts, each holding
``attn_norm``, ``attn``, ``mlp_norm`` and ``mlp`` or ``moe``.  Every layer
is ``x <- x + Attn_i(RMSNorm(x))``, then ``x <- x + MLP_i(RMSNorm(x))``; a
final RMSNorm, then the untied head.

- **Attention** of layer ``i``: ``heads_i`` query heads on ``n_kv_heads``
  key and value heads of ``head_dim``, no bias; ``g = sigmoid(h Wg)``, a
  gate a head from a projection of its own (``d_model x heads_i``); rotary
  on q and k — a full layer's by ``rope_full`` (the published one: YaRN on
  the first half of a head), a sliding layer's by ``rope_sliding`` (plain,
  on all of it), both ``blocks.rotary``; causal scores ``q k^T /
  sqrt(head_dim)``, and in a sliding layer key ``j`` is seen by query ``t``
  only where ``0 <= t - j < sliding_window``; each head's output times its
  gate; ``Wo``.  The Pallas flash kernels on a TPU
  (``ops/flash_attention``: a sliding layer's walk only the band's blocks).
- **MLP**: ``dense`` is ``W_down (SiLU(x W_gate) * x W_up)`` at ``d_ff``;
  ``sparse`` is ``models/moe.py``'s ``dropless_moe_ffn`` told sigmoid
  scoring (the selection bias is zeros: the config has none), the chosen
  ``top_k`` renormalised and scaled by ``routed_scale``, SwiGLU experts and
  an ungated shared expert.  It routes over all published experts and
  computes the part of the experts ``first_expert .. first_expert +
  experts_held``.

What the published ``config.json`` does not settle, and what is assumed
here (``benchmark/configs/laguna-s-2_1-5l.json`` lists the same under
``assumed``): sigmoid scoring without a selection bias; the shared expert
ungated and added to the routed sum; the gate read from the normed input and
applied before ``Wo``; no norm on q or k; pre-norm blocks; no auxiliary
loss; the rotated half of a full layer's head is its first 64 numbers in
the half-split pairing; YaRN's ramp as the published YaRN code computes it.

Each attention block and each dense MLP is recomputed in the backward pass
as its own region, an expert layer too, and the head :data:`HEAD_TOKENS`
tokens at a time, recomputed too.
``trace.attention`` counts, once a traced call site, which path a layer
kind took: ``full_flash``, ``full_plain``, ``window_flash``,
``window_plain``.

The parts of a step carry ``jax.named_scope`` names a device trace shows:
``attn/full`` (a full layer's norm, projections, gate, rotary and kernels),
``attn/window`` (the same of a sliding layer), ``mlp`` (layer 0's SwiGLU
and its norm), ``moe/route``, ``moe/dispatch``, ``moe/experts``,
``moe/shared``, ``moe/combine``, ``head``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import blocks as _blocks
from . import moe as _moe
from .. import trace
from ..parallel.ring_attention import local_flash_attention

FULL, SLIDING = "full_attention", "sliding_attention"
DENSE, SPARSE = "dense", "sparse"
PUBLISHED_LAYERS = 48
# tokens of a sequence whose logits over the vocabulary's rows are held
# together, in the forward pass and again in the backward pass
HEAD_TOKENS = 2048
ROPE_FULL = _blocks.Rotary(
    width=64, theta=500000.0, kind="yarn", factor=128.0, original_max=8192,
    beta_fast=32.0, beta_slow=1.0, attention_factor=1.4852030263919618)
ROPE_SLIDING = _blocks.Rotary(width=128, theta=10000.0)


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    vocab_size: int = 100352
    d_model: int = 3072
    # a layer an entry, the three lists equally long
    layer_types: Tuple[str, ...] = (FULL, SLIDING, SLIDING,
                                    SLIDING) * (PUBLISHED_LAYERS // 4)
    heads_per_layer: Tuple[int, ...] = (48, 72, 72, 72) * (
        PUBLISHED_LAYERS // 4)
    mlp_layer_types: Tuple[str, ...] = (DENSE,) + (SPARSE,) * (
        PUBLISHED_LAYERS - 1)
    # attention
    n_kv_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 512
    rope_full: _blocks.Rotary = ROPE_FULL
    rope_sliding: _blocks.Rotary = ROPE_SLIDING
    # the dense layers' SwiGLU
    d_ff: int = 12288
    # expert layers: the router's width, and the share held here
    n_experts: int = 256
    top_k: int = 10
    routed_scale: float = 2.5
    d_expert: int = 1024
    d_shared: int = 1024
    first_expert: int = 0
    experts_held: Optional[int] = None      # None = all of them
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    # Pallas flash attention: True/False, or None = on a TPU (see
    # ops/flash_attention.resolve_flash).
    use_flash: Optional[bool] = None

    def __post_init__(self):
        n = len(self.layer_types)
        if not n or len(self.heads_per_layer) != n or len(
                self.mlp_layer_types) != n:
            raise ValueError(
                f"layer_types ({n}), heads_per_layer "
                f"({len(self.heads_per_layer)}) and mlp_layer_types "
                f"({len(self.mlp_layer_types)}) name a layer an entry")
        if set(self.layer_types) - {FULL, SLIDING}:
            raise ValueError(f"layer_types {self.layer_types}: {FULL} or "
                             f"{SLIDING}")
        if set(self.mlp_layer_types) - {DENSE, SPARSE}:
            raise ValueError(f"mlp_layer_types {self.mlp_layer_types}: "
                             f"{DENSE} or {SPARSE}")
        if any(h % self.n_kv_heads for h in self.heads_per_layer):
            raise ValueError(f"heads_per_layer {self.heads_per_layer}: "
                             f"multiples of {self.n_kv_heads} key-value "
                             f"heads")
        for rot in (self.rope_full, self.rope_sliding):
            if rot.width > self.head_dim:
                raise ValueError(f"a rotary of {rot.width} on heads of "
                                 f"{self.head_dim}")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    def is_sliding(self, layer: int) -> bool:
        return self.layer_types[layer] == SLIDING

    def is_sparse(self, layer: int) -> bool:
        return self.mlp_layer_types[layer] == SPARSE

    def moe_cfg(self) -> _moe.DroplessMoEConfig:
        return _moe.DroplessMoEConfig(
            d_model=self.d_model, d_ff=self.d_expert,
            n_experts=self.n_experts, top_k=self.top_k,
            first_expert=self.first_expert, experts_held=self.experts_held,
            d_shared=self.d_shared, dtype=self.dtype, scoring="sigmoid",
            routed_scale=self.routed_scale, shared_gate=False)


def tiny(**kw) -> LagunaConfig:
    """The first five layers at test size: a full layer of 4 heads and the
    dense MLP, three sliding layers of 6 heads and a full one, all four on
    expert layers of 16 experts of which 4 are held, top-3; 2 key-value
    heads, a window of 8, the full layers' YaRN starting from 16
    positions."""
    base = dict(
        vocab_size=256, d_model=64,
        layer_types=(FULL, SLIDING, SLIDING, SLIDING, FULL),
        heads_per_layer=(4, 6, 6, 6, 4),
        mlp_layer_types=(DENSE, SPARSE, SPARSE, SPARSE, SPARSE),
        n_kv_heads=2, head_dim=16, sliding_window=8,
        rope_full=dataclasses.replace(ROPE_FULL, width=8, factor=4.0,
                                      original_max=16,
                                      attention_factor=None),
        rope_sliding=dataclasses.replace(ROPE_SLIDING, width=16),
        d_ff=96, n_experts=16, top_k=3, d_expert=32, d_shared=32,
        experts_held=4, dtype=jnp.float32, use_flash=False)
    base.update(kw)
    return LagunaConfig(**base)


def laguna_s_2_1() -> LagunaConfig:
    """The published sizes, every expert held."""
    return LagunaConfig()


# ------------------------------------------------------------------- params
def init_params(cfg: LagunaConfig, key) -> Dict:
    d, dt, hd, kv = cfg.d_model, cfg.dtype, cfg.head_dim, cfg.n_kv_heads
    keys = iter(jax.random.split(key, 2 + 9 * cfg.n_layers))

    def dense(fan_in, shape):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                / np.sqrt(fan_in)).astype(dt)

    layers = []
    for i, h in enumerate(cfg.heads_per_layer):
        layer = {
            "attn_norm": jnp.ones((d,), dt),
            "attn": {"wq": dense(d, (d, h * hd)), "wk": dense(d, (d, kv * hd)),
                     "wv": dense(d, (d, kv * hd)), "wg": dense(d, (d, h)),
                     "wo": dense(h * hd, (h * hd, d))},
            "mlp_norm": jnp.ones((d,), dt)}
        if cfg.is_sparse(i):
            layer["moe"] = _moe.dropless_init_params(cfg.moe_cfg(),
                                                     next(keys))
        else:
            layer["mlp"] = {"w_gate": dense(d, (d, cfg.d_ff)),
                            "w_up": dense(d, (d, cfg.d_ff)),
                            "w_down": dense(cfg.d_ff, (cfg.d_ff, d))}
        layers.append(layer)
    return {"embed": dense(d, (cfg.vocab_size, d)), "layers": layers,
            "final_norm": jnp.ones((d,), dt),
            "lm_head": dense(d, (d, cfg.vocab_size))}


# ------------------------------------------------------------------ forward
_rmsnorm = _blocks.rmsnorm


def _head_gate(u, wg):
    """``sigmoid(u Wg)`` ``[B, T, heads]``: one logit a (token, head) weighs
    a head's whole output, so it stays float32 until it has."""
    return jax.nn.sigmoid(jnp.dot(u, wg, preferred_element_type=jnp.float32))


def _attention_block(p, x, cfg: LagunaConfig, layer: int):
    from ..ops.flash_attention import flash_attention, resolve_flash
    B, T, _ = x.shape
    h, kv, hd = cfg.heads_per_layer[layer], cfg.n_kv_heads, cfg.head_dim
    sliding = cfg.is_sliding(layer)
    kind = "window" if sliding else "full"
    rot = cfg.rope_sliding if sliding else cfg.rope_full
    with jax.named_scope(f"attn/{kind}"):
        u, w = _rmsnorm(x, p["attn_norm"], cfg.norm_eps), p["attn"]
        q = _blocks.rotary((u @ w["wq"]).reshape(B, T, h, hd), rot)
        k = _blocks.rotary((u @ w["wk"]).reshape(B, T, kv, hd), rot)
        v = (u @ w["wv"]).reshape(B, T, kv, hd)
        gate = _head_gate(u, w["wg"])
        flash = resolve_flash(cfg.use_flash, seq=T, causal=True)
        trace.attention[f"{kind}_{'flash' if flash else 'plain'}"] += 1
        attend = flash_attention if flash else local_flash_attention
        o = attend(q, k, v, causal=True,
                   window=cfg.sliding_window if sliding else None)
        o = (o.astype(jnp.float32) * gate[..., None]).astype(x.dtype)
        return x + o.reshape(B, T, h * hd) @ w["wo"]


def _mlp_block(p, x, cfg: LagunaConfig):
    """A dense layer's SwiGLU behind the second norm."""
    with jax.named_scope("mlp"):
        u, w = _rmsnorm(x, p["mlp_norm"], cfg.norm_eps), p["mlp"]
        return x + (jax.nn.silu(u @ w["w_gate"]) * (u @ w["w_up"])
                    ) @ w["w_down"]


def _expert_block(p, x, cfg: LagunaConfig):
    """``(x, held_counts [experts_held])``: a sparse layer's expert layer
    behind the second norm, over all of the tokens at once (the layer
    holds rows for a block of the held assignments, not for every
    assignment made anywhere)."""
    B, T, D = x.shape
    y, counts = _moe.dropless_moe_ffn(
        _rmsnorm(x, p["mlp_norm"], cfg.norm_eps).reshape(B * T, D), p["moe"],
        cfg.moe_cfg())
    return x + y.reshape(B, T, D), counts


def _hidden(params, tokens, cfg: LagunaConfig):
    """``(the last layer's output [B, T, d_model], held_counts [expert
    layers, experts_held])``, before the final norm."""
    x = params["embed"][tokens]
    # Each attention block, each dense MLP and each expert layer is
    # recomputed in the backward pass, as regions of
    # their own, so that the backward pass never holds an attention
    # block's and an MLP's intermediates together (at 16 k tokens a sliding
    # layer's q and o are 302 MB each, the dense MLP's three products 403
    # MB each; a block's input is 101 MB).
    attention = jax.checkpoint(_attention_block, static_argnums=(2, 3))
    mlp = jax.checkpoint(_mlp_block, static_argnums=(2,))
    experts = jax.checkpoint(_expert_block, static_argnums=(2,))
    counts = []
    for i, p in enumerate(params["layers"]):
        x = attention(p, x, cfg, i)
        if "moe" in p:
            x, c = experts(p, x, cfg)
            counts.append(c)
        else:
            x = mlp(p, x, cfg)
    held = cfg.moe_cfg().held
    return x, (jnp.stack(counts) if counts
               else jnp.zeros((0, held), jnp.int32))


def _logits(params, x, cfg: LagunaConfig):
    """Float32 logits of the final norm's output through the untied
    head."""
    return jnp.einsum("btd,dv->btv",
                      _rmsnorm(x, params["final_norm"], cfg.norm_eps),
                      params["lm_head"], preferred_element_type=jnp.float32)


def forward(params, tokens, cfg: LagunaConfig):
    """Logits ``[B, T, vocab]`` in float32, whole: for tests' sizes."""
    x = _hidden(params, tokens, cfg)[0]
    with jax.named_scope("head"):
        return _logits(params, x, cfg)


def expert_load(params, tokens, cfg: LagunaConfig):
    """Assignments that land on each held expert, ``[expert layers,
    experts_held]`` int32, for a batch of tokens: the counter the benchmark
    reads in set-up.  ``tokens.size * top_k`` assignments are made in each
    expert layer."""
    return _hidden(params, tokens, cfg)[1]


def loss_fn(params, tokens, targets, cfg: LagunaConfig):
    """Mean next-token cross-entropy over this rank's tokens, the head
    :data:`HEAD_TOKENS` tokens at a time, each block recomputed in the
    backward pass."""
    return _blocks.next_token_loss_in_blocks(
        _hidden(params, tokens, cfg)[0], targets,
        lambda x: _logits(params, x, cfg), HEAD_TOKENS)


# --------------------------------------------------------------- train step
def make_train_step(cfg: LagunaConfig, optimizer):
    """:func:`blocks.train_step` of this module's ``loss_fn``, looked up
    when the step runs."""
    return _blocks.train_step(
        lambda p, tokens, targets: loss_fn(p, tokens, targets, cfg),
        optimizer)
