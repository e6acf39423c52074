"""Olmo-Hybrid-style decoder: Gated DeltaNet layers with negative
eigenvalues, position-free full attention, and the Olmo family's block with
the norm **after** each sublayer.

The published ``olmo_hybrid`` model as a training step on the normal path:
``make_train_step(cfg, optimizer)`` has the shape of
``llama.make_train_step`` and ``qwen3_next.make_train_step`` and runs
inside ``shard_map`` over ``hvd.mesh()`` with an in-graph
``hvd.DistributedOptimizer`` (the gradient exchange is the optimizer's; the
loss here is this rank's own mean).

Layer ``i`` is a full-attention layer when ``(i + 1) %
full_attention_interval == 0`` (the published ``layer_types``: three
``linear_attention`` then one ``full_attention``, repeated) and a Gated
DeltaNet layer otherwise; every layer ends in a dense SwiGLU.  Parameters
are a list of per-layer dicts, each holding ``attn`` or ``gdn`` beside
``mlp`` and the two norms.

- ``RMSNorm(x; w) = x / rms(x) * w``, eps 1e-6, float32 inside (a plain
  weight).
- **Block**: ``h = x + RMSNorm(Mixer_i(x); w_mixer)``, ``y = h +
  RMSNorm(MLP(h); w_mlp)``; ``MLP(h) = (silu(h W_gate) * h W_up) W_down``;
  logits ``= RMSNorm(x_L; w_final) W_head`` in float32.
- **Full attention**: ``q = RMSNorm(x W_q; w_q)``, ``k = RMSNorm(x W_k;
  w_k)`` over the **whole** projection, ``v = x W_v``; ``n_heads`` heads; no
  rotary, no other positional signal (positions reach it through the
  recurrent layers only); causal softmax attention, scale ``head_dim **
  -0.5`` (the Pallas flash kernel on a TPU); ``W_o``.
- **Gated DeltaNet**: ``models/gated_delta.py``'s mixer (shared with
  ``qwen3_next``), told this config's sizes — key and value widths that
  differ, as many key heads as value heads — and ``beta = 2 * sigmoid(b)``
  where ``allow_neg_eigval`` (the published ``linear_allow_neg_eigval``).

What the published ``config.json`` does not settle, and what is assumed
here (``benchmark/configs/olmo-hybrid-7b-4l.json`` lists the same under
``assumed``):

1. the norm sits on each sublayer's **output**, inside the residual, and
   q and k are normalised over all their columns: the convention of
   ``olmo2`` / ``olmo3``, whose ``model_type`` prefix this is;
2. ``rope_parameters.rope_theta`` null means that no rotary is applied;
3. the linear-attention layer is the reference Gated DeltaNet layer that
   the config's ``linear_*`` keys (the same as ``qwen3_next``'s) and sizes
   (keys 0.75 x hidden, values 1.5 x hidden) identify, with its output
   gate ``SiLU(z)`` and per-head output norm;
4. the columns of the fused projections are ordered ``[q|k|v|z]`` and
   ``[b|a]`` over all heads, which matters only to a checkpoint converter.

Each mixer and each MLP is recomputed in the backward pass as its own
region.  On a TPU the delta rule is the kernel pair of ``ops/delta_rule.py``
(``gated_delta_net`` chooses at trace time): the 96 x 192 heads run at 128
x 256, padded with zeros, a chunk's float32 algebra stays in VMEM and all
30 heads go at once.  Everywhere else — the CPU tests and rehearsals — it
is the plain rule this module hands in, a group of heads at a time
(``gated_delta.by_head_groups``, ``RULE_TOKEN_HEADS`` (token, head) pairs
together, each group recomputed too), so that a 16 k-token step never holds
every head's float32 chunk algebra at once.

The parts of a step carry ``jax.named_scope`` names a device trace shows:
``gdn/proj``, ``gdn/conv``, ``gdn/scan``, ``gdn/out`` (the shared mixer),
``attn/full``, ``mlp`` (the SwiGLU and its norm), ``head``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import blocks as _blocks
from . import gated_delta as _gdn
from ..parallel.ring_attention import local_flash_attention

# (token, head) pairs of a sequence whose chunked delta rule is computed
# together where the plain rule runs (gated_delta.by_head_groups; not on a
# TPU, where the kernel pair takes the shape): 6 of 30 heads at 16384
# tokens, and every head at once up to 4369 tokens
RULE_TOKEN_HEADS = 1 << 17


@dataclasses.dataclass(frozen=True)
class OlmoHybridConfig:
    vocab_size: int = 100352
    d_model: int = 3840
    n_layers: int = 32
    full_attention_interval: int = 4
    n_heads: int = 30               # full attention: no grouping
    d_ff: int = 11008
    # Gated DeltaNet
    lin_k_heads: int = 30
    lin_v_heads: int = 30
    lin_k_dim: int = 96
    lin_v_dim: int = 192
    conv_kernel: int = 4
    chunk: int = 64
    allow_neg_eigval: bool = True   # beta in (0, 2) instead of (0, 1)
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    # Pallas flash attention: True/False, or None = on a TPU (see
    # ops/flash_attention.resolve_flash).
    use_flash: Optional[bool] = None

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    def is_full_attention(self, i: int) -> bool:
        return (i + 1) % self.full_attention_interval == 0

    @property
    def layer_types(self) -> tuple:
        return tuple("full_attention" if self.is_full_attention(i)
                     else "linear_attention" for i in range(self.n_layers))

    def gdn_dims(self) -> _gdn.GatedDeltaDims:
        return _gdn.GatedDeltaDims(
            k_heads=self.lin_k_heads, v_heads=self.lin_v_heads,
            k_dim=self.lin_k_dim, v_dim=self.lin_v_dim,
            conv_kernel=self.conv_kernel, chunk=self.chunk,
            norm_eps=self.norm_eps,
            beta_scale=2.0 if self.allow_neg_eigval else 1.0)


def tiny(**kw) -> OlmoHybridConfig:
    """One period at test size: key width != value width, as many key heads
    as value heads, a head of 16."""
    base = dict(vocab_size=256, d_model=64, n_layers=4, n_heads=4, d_ff=96,
                lin_k_heads=4, lin_v_heads=4, lin_k_dim=12, lin_v_dim=24,
                dtype=jnp.float32, use_flash=False)
    base.update(kw)
    return OlmoHybridConfig(**base)


def olmo_hybrid_7b() -> OlmoHybridConfig:
    """The published sizes."""
    return OlmoHybridConfig()


# ------------------------------------------------------------------- params
def init_params(cfg: OlmoHybridConfig, key) -> Dict:
    d, dt = cfg.d_model, cfg.dtype
    keys = iter(jax.random.split(key, 2 + 9 * cfg.n_layers))

    def dense(fan_in, shape):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                / np.sqrt(fan_in)).astype(dt)

    def attn():
        return {"wq": dense(d, (d, d)), "wk": dense(d, (d, d)),
                "wv": dense(d, (d, d)), "q_norm": jnp.ones((d,), dt),
                "k_norm": jnp.ones((d,), dt), "wo": dense(d, (d, d))}

    layers = []
    for i in range(cfg.n_layers):
        full = cfg.is_full_attention(i)
        layers.append({
            "attn" if full else "gdn":
                attn() if full else _gdn.init_params(cfg.gdn_dims(), d, dt,
                                                     keys),
            "mixer_norm": jnp.ones((d,), dt),
            "mlp": {"w_gate": dense(d, (d, cfg.d_ff)),
                    "w_up": dense(d, (d, cfg.d_ff)),
                    "w_down": dense(cfg.d_ff, (cfg.d_ff, d))},
            "mlp_norm": jnp.ones((d,), dt)})
    return {"embed": dense(d, (cfg.vocab_size, d)), "layers": layers,
            "final_norm": jnp.ones((d,), dt),
            "lm_head": dense(d, (d, cfg.vocab_size))}


# ------------------------------------------------------------------ forward
_rmsnorm = _blocks.rmsnorm


def _full_attention(x, p, cfg: OlmoHybridConfig):
    from ..ops.flash_attention import flash_attention, resolve_flash
    B, T, _ = x.shape
    h, hd = cfg.n_heads, cfg.head_dim
    with jax.named_scope("attn/full"):
        q = _rmsnorm(x @ p["wq"], p["q_norm"], cfg.norm_eps)
        k = _rmsnorm(x @ p["wk"], p["k_norm"], cfg.norm_eps)
        v = x @ p["wv"]
        attend = (flash_attention if resolve_flash(cfg.use_flash, seq=T,
                                                   causal=True)
                  else local_flash_attention)
        # no rotary: the heads see no position but the causal mask
        o = attend(*(y.reshape(B, T, h, hd) for y in (q, k, v)), causal=True)
        return o.reshape(B, T, h * hd) @ p["wo"]


def _mixer_block(p, x, cfg: OlmoHybridConfig):
    y = (_full_attention(x, p["attn"], cfg) if "attn" in p
         else _gdn.gated_delta_net(
             x, p["gdn"], cfg.gdn_dims(),
             rule=_gdn.by_head_groups(_gdn.chunked_gated_delta_rule,
                                      RULE_TOKEN_HEADS)))
    return x + _rmsnorm(y, p["mixer_norm"], cfg.norm_eps)


def _mlp_block(p, x, cfg: OlmoHybridConfig):
    with jax.named_scope("mlp"):
        w = p["mlp"]
        y = (jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])) @ w["w_down"]
        return x + _rmsnorm(y, p["mlp_norm"], cfg.norm_eps)


def forward(params, tokens, cfg: OlmoHybridConfig):
    """Logits ``[B, T, vocab]`` in float32."""
    x = params["embed"][tokens]
    # Each mixer and each MLP is recomputed in the backward pass, as two
    # regions a layer, so that the backward pass never holds a mixer's and
    # an MLP's intermediates together (qwen3_next._forward's reason).  A
    # grouped delta rule's result is saved (189 MB a layer at 16 k tokens):
    # its groups recompute themselves, so the mixer's recomputation need
    # not run the rule forward a third time.  (The kernel pair's result
    # carries no such name: the recomputation runs its forward kernel, which
    # saves what its backward kernel starts from.)
    mixer = jax.checkpoint(
        _mixer_block, static_argnums=(2,),
        policy=jax.checkpoint_policies.save_only_these_names(
            _gdn.RULE_OUTPUT))
    mlp = jax.checkpoint(_mlp_block, static_argnums=(2,))
    for p in params["layers"]:
        x = mlp(p, mixer(p, x, cfg), cfg)
    with jax.named_scope("head"):
        x = _rmsnorm(x, params["final_norm"], cfg.norm_eps)
        return jnp.einsum("btd,dv->btv", x, params["lm_head"],
                          preferred_element_type=jnp.float32)


def beta_stats(params, tokens, cfg: OlmoHybridConfig):
    """``(share, largest)``, each ``[Gated DeltaNet layers]`` float32: the
    share of (token, head) pairs of a batch whose beta exceeds 1 (where
    ``I - beta k k^T`` has a negative eigenvalue) and the largest beta, by
    layer.  A counter for set-up, not for a step: the layers run forward
    once more."""
    x = params["embed"][tokens]
    dims, share, largest = cfg.gdn_dims(), [], []
    for p in params["layers"]:
        if "gdn" in p:
            ba = jnp.einsum("btd,de->bte", x, p["gdn"]["w_ba"],
                            preferred_element_type=jnp.float32)
            beta, _ = _gdn.gate_inputs(ba, p["gdn"], dims)
            share.append(jnp.mean((beta > 1.0).astype(jnp.float32)))
            largest.append(jnp.max(beta))
        x = _mlp_block(p, _mixer_block(p, x, cfg), cfg)
    return jnp.stack(share), jnp.stack(largest)


def loss_fn(params, tokens, targets, cfg: OlmoHybridConfig):
    """Mean next-token cross-entropy over this rank's tokens."""
    return _blocks.next_token_loss(forward(params, tokens, cfg), targets)


# --------------------------------------------------------------- train step
def make_train_step(cfg: OlmoHybridConfig, optimizer):
    """:func:`blocks.train_step` of this module's ``loss_fn``, looked up
    when the step runs."""
    return _blocks.train_step(
        lambda p, tokens, targets: loss_fn(p, tokens, targets, cfg),
        optimizer)
