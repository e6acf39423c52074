"""Jamba-style hybrid decoder: Mamba-1 state-space layers with an attention
layer once a period, a dense SwiGLU after every mixer, a head tied to the
embedding.

The published ``jamba`` model (dense members of the family: ``num_experts``
1) as a training step on the normal path: ``make_train_step(cfg,
optimizer)`` has the shape of ``llama.make_train_step`` and the other
hybrids' and runs inside ``shard_map`` over ``hvd.mesh()`` with an in-graph
``hvd.DistributedOptimizer`` (the gradient exchange is the optimizer's; the
loss here is this rank's own mean).

Layer ``i`` is an attention layer where ``i % attn_layer_period ==
attn_layer_offset`` and a Mamba layer elsewhere; every layer is ``x <- x +
mixer(RMSNorm(x))``, then ``x <- x + MLP(RMSNorm(x))``; a final RMSNorm,
then the head, which is the embedding matrix read the other way: one leaf,
whose gradient is the lookup's scatter plus the head's product, and the
optimizer holds it once.  Parameters are a list of per-layer dicts, each
holding ``mixer_norm``, ``ssm`` or ``attn``, ``mlp_norm`` and ``mlp``.

- **Mamba** (``models/mamba.py``): ``d_inner = mamba_expand * d_model``
  channels, a state of ``mamba_d_state``, a step through rank
  ``mamba_dt_rank``, the family's RMSNorms on ``dt_r``, ``B`` and ``C``;
  the recurrence is ``ops/selective_scan.py``'s.
- **Attention**: ``n_heads`` query heads on ``n_kv_heads`` key and value
  heads, no bias, causal, scale ``head_dim ** -0.5``, **no rotary** and no
  other position signal (positions reach it through the Mamba layers); the
  Pallas flash kernel on a TPU.
- **MLP**: ``W_down (SiLU(x W_gate) * x W_up)``, no bias.  The family's
  expert models put experts where ``i % expert_layer_period ==
  expert_layer_offset``; with ``num_experts`` 1 that layer is the same
  dense MLP, and a config with more experts is refused.

What the published ``config.json`` does not settle, and what is assumed
here (``benchmark/configs/jamba2-3b-14l.json`` lists the same under
``assumed``): the order of the layer types follows from period and offset
alone; the three inner norms and where they sit; no rotary; ``head_dim =
d_model / n_heads``; the fused ``W_in``'s columns are ``[x | z]``.

Each mixer and each MLP is recomputed in the backward pass as its own
region, and the head :data:`HEAD_TOKENS` tokens at a time, recomputed too:
logits over 65536 rows are 268 MB of float32 a thousand tokens.

The parts of a step carry ``jax.named_scope`` names a device trace shows:
``ssm/proj``, ``ssm/conv``, ``ssm/scan``, ``ssm/out`` (the Mamba mixer),
``attn/full``, ``mlp`` (the SwiGLU and its norm), ``head`` (the final norm,
the logits and each token's loss).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import blocks as _blocks
from . import mamba as _ssm

# tokens of a sequence whose logits over the whole vocabulary are held
# together, in the forward pass and again in the backward pass
HEAD_TOKENS = 1024


@dataclasses.dataclass(frozen=True)
class JambaConfig:
    vocab_size: int = 65536
    d_model: int = 2560
    n_layers: int = 28
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    expert_layer_period: int = 2
    expert_layer_offset: int = 1
    num_experts: int = 1
    d_ff: int = 8192
    # Mamba-1
    mamba_expand: int = 2
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_dt_rank: int = 160
    # attention
    n_heads: int = 20
    n_kv_heads: int = 1
    head_dim: int = 128
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    # Pallas flash attention: True/False, or None = on a TPU (see
    # ops/flash_attention.resolve_flash).
    use_flash: Optional[bool] = None

    def __post_init__(self):
        if self.num_experts != 1:
            raise ValueError(
                f"num_experts {self.num_experts}: the family's expert "
                f"models (experts where i % {self.expert_layer_period} == "
                f"{self.expert_layer_offset}) are not built; num_experts "
                f"must be 1")
        if not 0 <= self.attn_layer_offset < self.attn_layer_period:
            raise ValueError("attn_layer_offset must lie inside the period")

    def is_attention(self, layer: int) -> bool:
        return layer % self.attn_layer_period == self.attn_layer_offset

    @property
    def pattern(self) -> str:
        """A layer a character: ``*`` attention, ``M`` Mamba."""
        return "".join("*" if self.is_attention(i) else "M"
                       for i in range(self.n_layers))

    def ssm_dims(self) -> _ssm.MambaDims:
        return _ssm.MambaDims(
            d_inner=self.mamba_expand * self.d_model,
            state=self.mamba_d_state, dt_rank=self.mamba_dt_rank,
            conv_kernel=self.mamba_d_conv, norm_eps=self.norm_eps)


def tiny(**kw) -> JambaConfig:
    """One period of four at test size: three Mamba layers and an
    attention layer of four heads on one key-value head."""
    base = dict(vocab_size=256, d_model=64, n_layers=4, attn_layer_period=4,
                attn_layer_offset=2, d_ff=96, mamba_d_state=8,
                mamba_dt_rank=8, n_heads=4, n_kv_heads=1, head_dim=16,
                dtype=jnp.float32, use_flash=False)
    base.update(kw)
    return JambaConfig(**base)


def jamba2_3b() -> JambaConfig:
    """The published sizes."""
    return JambaConfig()


# ------------------------------------------------------------------- params
def init_params(cfg: JambaConfig, key) -> Dict:
    d, dt = cfg.d_model, cfg.dtype
    keys = iter(jax.random.split(key, 1 + 9 * cfg.n_layers))

    def dense(fan_in, shape):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                / np.sqrt(fan_in)).astype(dt)

    def attn():
        h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        return {"wq": dense(d, (d, h * hd)), "wk": dense(d, (d, kv * hd)),
                "wv": dense(d, (d, kv * hd)),
                "wo": dense(h * hd, (h * hd, d))}

    layers = []
    for i in range(cfg.n_layers):
        mixer = ({"attn": attn()} if cfg.is_attention(i) else
                 {"ssm": _ssm.init_params(cfg.ssm_dims(), d, dt, keys)})
        layers.append({
            "mixer_norm": jnp.ones((d,), dt), **mixer,
            "mlp_norm": jnp.ones((d,), dt),
            "mlp": {"w_gate": dense(d, (d, cfg.d_ff)),
                    "w_up": dense(d, (d, cfg.d_ff)),
                    "w_down": dense(cfg.d_ff, (cfg.d_ff, d))}})
    return {"embed": dense(d, (cfg.vocab_size, d)), "layers": layers,
            "final_norm": jnp.ones((d,), dt)}


# ------------------------------------------------------------------ forward
_rmsnorm = _blocks.rmsnorm


def _mixer_block(p, x, cfg: JambaConfig):
    if "attn" in p:
        with jax.named_scope("attn/full"):
            u = _rmsnorm(x, p["mixer_norm"], cfg.norm_eps)
        return x + _blocks.grouped_attention(u, p["attn"], cfg)
    with jax.named_scope("ssm/proj"):
        u = _rmsnorm(x, p["mixer_norm"], cfg.norm_eps)
    return x + _ssm.mamba(u, p["ssm"], cfg.ssm_dims())


def _mlp_block(p, x, cfg: JambaConfig):
    with jax.named_scope("mlp"):
        u, w = _rmsnorm(x, p["mlp_norm"], cfg.norm_eps), p["mlp"]
        return x + (jax.nn.silu(u @ w["w_gate"]) * (u @ w["w_up"])
                    ) @ w["w_down"]


def hidden(params, tokens, cfg: JambaConfig):
    """The last layer's output ``[B, T, d_model]``, before the final norm."""
    x = params["embed"][tokens]
    # Each mixer and each MLP is recomputed in the backward pass, as two
    # regions a layer, so that the backward pass never holds a mixer's and
    # an MLP's intermediates together (a Mamba layer's step alone is 168 MB
    # of float32 at 8192 tokens, the MLP's three products 403 MB).
    mixer = jax.checkpoint(_mixer_block, static_argnums=(2,))
    mlp = jax.checkpoint(_mlp_block, static_argnums=(2,))
    for p in params["layers"]:
        x = mlp(p, mixer(p, x, cfg), cfg)
    return x


def _logits(params, x, cfg: JambaConfig):
    """Float32 logits of the final norm's output through the tied head."""
    return jnp.einsum("btd,vd->btv",
                      _rmsnorm(x, params["final_norm"], cfg.norm_eps),
                      params["embed"], preferred_element_type=jnp.float32)


def forward(params, tokens, cfg: JambaConfig):
    """Logits ``[B, T, vocab]`` in float32, whole: for tests' sizes."""
    with jax.named_scope("head"):
        return _logits(params, hidden(params, tokens, cfg), cfg)


def loss_fn(params, tokens, targets, cfg: JambaConfig):
    """Mean next-token cross-entropy over this rank's tokens, the head
    :data:`HEAD_TOKENS` tokens at a time, each block recomputed in the
    backward pass."""
    return _blocks.next_token_loss_in_blocks(
        hidden(params, tokens, cfg), targets,
        lambda x: _logits(params, x, cfg), HEAD_TOKENS)


def decay_stats(params, tokens, cfg: JambaConfig):
    """``(share, least, most)``, each ``[Mamba layers]`` float32: the share
    of (token, channel, state) triples of a batch whose decay over one
    token, ``exp(delta A)``, is under 0.5 (state that forgets), and the
    smallest and largest step ``delta``, by layer.  A counter for set-up,
    not for a step: the layers run forward once more."""
    from .gated_delta import causal_conv_silu
    x = params["embed"][tokens]
    dims, share, least, most = cfg.ssm_dims(), [], [], []
    for p in params["layers"]:
        if "ssm" in p:
            w = p["ssm"]
            u = _rmsnorm(x, p["mixer_norm"], cfg.norm_eps)
            delta, _, _ = _ssm.step_and_projections(causal_conv_silu(
                u @ w["w_in"][:, :dims.d_inner], w["conv"], w["conv_bias"]),
                w, dims)
            # exp(delta A) < 0.5  <=>  delta > log 2 / exp(A_log)
            a = jnp.exp(w["A_log"].astype(jnp.float32))          # [d, n]
            share.append(jnp.mean(
                (delta[..., None] * a > np.log(2.0)).astype(jnp.float32)))
            least.append(jnp.min(delta))
            most.append(jnp.max(delta))
        x = _mlp_block(p, _mixer_block(p, x, cfg), cfg)
    return jnp.stack(share), jnp.stack(least), jnp.stack(most)


# --------------------------------------------------------------- train step
def make_train_step(cfg: JambaConfig, optimizer):
    """:func:`blocks.train_step` of this module's ``loss_fn``, looked up
    when the step runs."""
    return _blocks.train_step(
        lambda p, tokens, targets: loss_fn(p, tokens, targets, cfg),
        optimizer)
