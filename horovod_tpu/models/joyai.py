"""JoyAI-LLM-Flash-style expert decoder (DeepSeek-V3's equations): latent
attention, a leading dense layer before the expert layers, sigmoid-routed
experts whose selection bias is moved by the load, and a multi-token
prediction module that reads the embedding and the head a second time.

The published ``joyai_llm_flash`` model as a training step on the normal
path: ``make_train_step(cfg, optimizer)`` has the shape of
``laguna.make_train_step`` and runs inside ``shard_map`` over ``hvd.mesh()``
with an in-graph ``hvd.DistributedOptimizer`` (the gradient exchange is the
optimizer's; the loss here is this rank's own mean).

Every layer is ``x <- x + Attn(RMSNorm(x))``, then ``x <- x +
MLP(RMSNorm(x))``, no bias anywhere; the stack is a Python loop over a list
of per-layer dicts (``attn_norm``, ``attn``, ``mlp_norm`` and ``mlp`` or
``moe``), as ``models/laguna.py``'s is.

- **Attention**: ``models/latent_attention.py``'s block, every layer alike
  (queries through a rank-``q_rank`` bottleneck, keys and values from a
  rank-``kv_rank`` latent, one rotary key for all heads, scores over
  ``d_nope + d_rope`` numbers and values of ``d_v``); the Pallas flash
  kernels on a TPU.
- **MLP**: the first ``first_dense`` layers are ``W_down (SiLU(x W_gate) *
  x W_up)`` at ``d_ff``; the others ``models/moe.py``'s
  ``dropless_moe_ffn`` told sigmoid scoring: the ``top_k`` largest ``s +
  b`` chosen (``b`` the layer's ``router_bias``, float32, which no gradient
  reaches), weighed by ``routed_scale * s / sum_chosen s``, SwiGLU experts
  and an ungated shared expert.  It routes over all published experts and
  computes the part of the experts ``first_expert .. first_expert +
  experts_held``.
- A final RMSNorm ``g = RMSNorm(x)`` and the untied head give the main
  logits; ``L_main`` is the mean next-token cross-entropy.
- **The prediction module** (``mtp_modules`` 1), at position ``i``: ``z_i =
  [RMSNorm(Emb[t_{i+1}]; embed_norm) ; RMSNorm(g_i; hidden_norm)] W_eh``
  (``2 d_model -> d_model``), ``u = Block(z)`` (one more layer of the sparse
  kind with weights of its own, over all ``T`` positions), ``logits' =
  Head(RMSNorm(u; final_norm'))`` through **the main model's head**,
  ``Emb`` **the main model's embedding**; held to ``t_{i+2}``, so the last
  position has no target and is masked out of the mean.  ``L = L_main +
  mtp_weight L_mtp``; the gradients of ``embed`` and ``lm_head`` are the
  sums of their two uses.
- **The bias**, after the optimizer's update, inside the compiled step:
  ``b_e += bias_speed * sign(mean_e'(c_e') - c_e)``, ``c_e`` the
  assignments expert ``e`` got in this step's batch in that layer, over
  ALL experts (summed over the replicas of ``axis_name`` where the step is
  told one).  Adam sees a zero gradient on the leaf and leaves it.

What the published ``config.json`` does not settle is assumed as
``benchmark/configs/joyai-llm-flash-5l.json`` lists under ``assumed``:
``mtp_weight`` 0.3 and ``bias_speed`` 0.001 (the DeepSeek-V3 report's),
``W_eh`` reads the embedding's half first, ``g`` is taken after the final
norm, the module's block is of the sparse kind, no auxiliary loss, pre-norm
blocks.

Each attention block, each dense MLP and each expert layer is recomputed in
the backward pass as its own region, the module's parts too, and the head
:data:`HEAD_TOKENS` tokens at a time, recomputed too.  ``trace.attention``
counts, once a traced call site, which path an attention took:
``latent_flash`` or ``latent_plain``.

The parts of a step carry ``jax.named_scope`` names a device trace shows:
``attn/latent`` (a layer's norm, projections, rotary, kernels and ``W_o``;
the two low-rank paths up to ``q``, ``k``, ``v`` under ``attn/latent/proj``
beneath it), ``mlp``, ``moe/route``, ``moe/dispatch``, ``moe/experts``,
``moe/shared``, ``moe/combine``, ``head``, and everything of the module
under ``mtp`` (``mtp/attn/latent``, ``mtp/moe/experts``, ``mtp/head``...).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import blocks as _blocks
from . import latent_attention as _latent
from . import moe as _moe
from .. import trace

# tokens of a sequence whose logits over the vocabulary's rows are held
# together, in the forward pass and again in the backward pass
HEAD_TOKENS = 2048


@dataclasses.dataclass(frozen=True)
class JoyAIConfig:
    vocab_size: int = 129280
    n_layers: int = 40
    first_dense: int = 1                # the published first_k_dense_replace
    attn: _latent.LatentDims = _latent.LatentDims(
        d_model=2048, n_heads=32, q_rank=1536, kv_rank=512, d_nope=128,
        d_rope=64, d_v=128, rope_theta=32000000.0, norm_eps=1e-6)
    # the dense layers' SwiGLU
    d_ff: int = 7168
    # expert layers: the router's width, and the share held here
    n_experts: int = 256
    top_k: int = 8
    routed_scale: float = 2.5
    d_expert: int = 768
    d_shared: int = 768
    first_expert: int = 0
    experts_held: Optional[int] = None      # None = all of them
    # the prediction module and its loss term's weight; the bias's step
    mtp_modules: int = 1
    mtp_weight: float = 0.3
    bias_speed: float = 0.001
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    # Pallas flash attention: True/False, or None = on a TPU (see
    # ops/flash_attention.resolve_flash).
    use_flash: Optional[bool] = None

    def __post_init__(self):
        if not 0 <= self.first_dense <= self.n_layers:
            raise ValueError(f"first_dense {self.first_dense} of "
                             f"{self.n_layers} layers")
        if self.mtp_modules not in (0, 1):
            raise ValueError(f"mtp_modules {self.mtp_modules}: none or one "
                             f"prediction module")

    @property
    def d_model(self) -> int:
        return self.attn.d_model

    def is_sparse(self, layer: int) -> bool:
        return layer >= self.first_dense

    def moe_cfg(self) -> _moe.DroplessMoEConfig:
        return _moe.DroplessMoEConfig(
            d_model=self.d_model, d_ff=self.d_expert,
            n_experts=self.n_experts, top_k=self.top_k,
            first_expert=self.first_expert, experts_held=self.experts_held,
            d_shared=self.d_shared, dtype=self.dtype, scoring="sigmoid",
            routed_scale=self.routed_scale, shared_gate=False)


def tiny(**kw) -> JoyAIConfig:
    """Test size: a dense layer and two expert layers of 16 experts of which
    4 are held, top-3, and the module; 4 heads whose keys (16 + 8) and
    values (16) differ in width, ranks 24 and 16."""
    base = dict(
        vocab_size=256, n_layers=3,
        attn=_latent.LatentDims(
            d_model=64, n_heads=4, q_rank=24, kv_rank=16, d_nope=16,
            d_rope=8, d_v=16, rope_theta=32000000.0),
        d_ff=96, n_experts=16, top_k=3, d_expert=32, d_shared=32,
        experts_held=4, dtype=jnp.float32, use_flash=False)
    base.update(kw)
    return JoyAIConfig(**base)


def joyai_llm_flash() -> JoyAIConfig:
    """The published sizes, every expert held."""
    return JoyAIConfig()


# ------------------------------------------------------------------- params
def _init_layer(cfg: JoyAIConfig, sparse: bool, keys, dense) -> Dict:
    d, dt = cfg.d_model, cfg.dtype
    layer = {"attn_norm": jnp.ones((d,), dt),
             "attn": _latent.init_params(cfg.attn, dt, keys),
             "mlp_norm": jnp.ones((d,), dt)}
    if sparse:
        layer["moe"] = _moe.dropless_init_params(cfg.moe_cfg(), next(keys))
        # moved by steps of bias_speed, which bfloat16 would round
        layer["moe"]["router_bias"] = jnp.zeros((cfg.n_experts,),
                                                jnp.float32)
    else:
        layer["mlp"] = {"w_gate": dense(d, (d, cfg.d_ff)),
                        "w_up": dense(d, (d, cfg.d_ff)),
                        "w_down": dense(cfg.d_ff, (cfg.d_ff, d))}
    return layer


def init_params(cfg: JoyAIConfig, key) -> Dict:
    d, dt = cfg.d_model, cfg.dtype
    keys = iter(jax.random.split(key, 3 + 9 * (cfg.n_layers + 1)))

    def dense(fan_in, shape):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                / np.sqrt(fan_in)).astype(dt)

    params = {"embed": dense(d, (cfg.vocab_size, d)),
              "layers": [_init_layer(cfg, cfg.is_sparse(i), keys, dense)
                         for i in range(cfg.n_layers)],
              "final_norm": jnp.ones((d,), dt),
              "lm_head": dense(d, (d, cfg.vocab_size))}
    if cfg.mtp_modules:
        params["mtp"] = {"embed_norm": jnp.ones((d,), dt),
                         "hidden_norm": jnp.ones((d,), dt),
                         "proj": dense(2 * d, (2 * d, d)),
                         "block": _init_layer(cfg, True, keys, dense),
                         "final_norm": jnp.ones((d,), dt)}
    return params


def _over_blocks(params, fn) -> Dict:
    """``params`` with ``fn`` applied to every layer's dict, the main
    stack's in order and then the module's block."""
    out = {**params, "layers": [fn(p) for p in params["layers"]]}
    if "mtp" in params:
        out["mtp"] = {**params["mtp"], "block": fn(params["mtp"]["block"])}
    return out


def from_published(params, cfg: JoyAIConfig) -> Dict:
    """Parameters whose attention blocks are in the published column order
    (``latent_attention.from_published``) as this module holds them."""
    return _over_blocks(params, lambda p: {
        **p, "attn": _latent.from_published(p["attn"], cfg.attn)})


# ------------------------------------------------------------------ forward
_rmsnorm = _blocks.rmsnorm


def _attention_block(p, x, cfg: JoyAIConfig, flash: bool):
    with jax.named_scope("attn/latent"):
        return x + _latent.latent_attention(
            p["attn"], _rmsnorm(x, p["attn_norm"], cfg.norm_eps), cfg.attn,
            flash)


def _attention(p, x, cfg: JoyAIConfig):
    """A layer's attention block, recomputed in the backward pass as a
    region of its own; the call site is counted here, outside the region
    (JAX keeps a traced region by its arguments' shapes, and the expert
    layers' blocks have the same)."""
    from ..ops.flash_attention import resolve_flash
    flash = resolve_flash(cfg.use_flash, seq=x.shape[1], causal=True)
    trace.attention[f"latent_{'flash' if flash else 'plain'}"] += 1
    return jax.checkpoint(_attention_block, static_argnums=(2, 3))(
        p, x, cfg, flash)


def _mlp_block(p, x, cfg: JoyAIConfig):
    """A dense layer's SwiGLU behind the second norm."""
    with jax.named_scope("mlp"):
        u, w = _rmsnorm(x, p["mlp_norm"], cfg.norm_eps), p["mlp"]
        return x + (jax.nn.silu(u @ w["w_gate"]) * (u @ w["w_up"])
                    ) @ w["w_down"]


def _expert_block(p, x, cfg: JoyAIConfig):
    """``(x, counts [n_experts], held_counts [experts_held])``: a sparse
    layer's expert layer behind the second norm, with the assignments each
    of ALL experts got (what moves the bias) and each held one."""
    B, T, D = x.shape
    mcfg, w = cfg.moe_cfg(), p["moe"]
    u = _rmsnorm(x, p["mlp_norm"], cfg.norm_eps).reshape(B * T, D)
    with jax.named_scope("moe/route"):
        ids, weights = _moe.dropless_route(u, w["router"], mcfg,
                                           w["router_bias"])
        counts = jnp.sum(
            ids.reshape(-1, 1) == jnp.arange(cfg.n_experts, dtype=ids.dtype),
            axis=0, dtype=jnp.int32)
    y, held = _moe.dropless_moe_ffn(u, w, mcfg, routed=(ids, weights))
    return x + y.reshape(B, T, D), counts, held


def _sparse_layer(p, x, cfg: JoyAIConfig):
    """An attention block and an expert layer, each recomputed in the
    backward pass as a region of its own (at 16 k tokens ``q`` and ``k``
    are 201 MB each, ``W_kvb``'s output 268 MB; a block's input is 67
    MB)."""
    return jax.checkpoint(_expert_block, static_argnums=(2,))(
        p, _attention(p, x, cfg), cfg)


def _hidden(params, tokens, cfg: JoyAIConfig):
    """``(the last layer's output [B, T, d_model], counts, held_counts)``,
    before the final norm; the counts a list, an expert layer an entry."""
    x = params["embed"][tokens]
    counts, held = [], []
    for p in params["layers"]:
        if "moe" in p:
            x, c, h = _sparse_layer(p, x, cfg)
            counts.append(c)
            held.append(h)
        else:
            x = jax.checkpoint(_mlp_block, static_argnums=(2,))(
                p, _attention(p, x, cfg), cfg)
    return x, counts, held


def _final(params, x, cfg: JoyAIConfig):
    """``g``: the main stack's output behind the final norm, which the head
    and the prediction module both read."""
    with jax.named_scope("head"):
        return _rmsnorm(x, params["final_norm"], cfg.norm_eps)


def _head(params, g):
    """Float32 logits of normed rows through the untied head."""
    return jnp.einsum("btd,dv->btv", g, params["lm_head"],
                      preferred_element_type=jnp.float32)


def _mtp_input(m, rows, g, cfg: JoyAIConfig):
    """``z``: the next tokens' embedding rows and ``g``, each behind a norm
    of its own, the embedding's half first, through ``W_eh``."""
    return jnp.concatenate(
        [_rmsnorm(rows, m["embed_norm"], cfg.norm_eps),
         _rmsnorm(g, m["hidden_norm"], cfg.norm_eps)], axis=-1) @ m["proj"]


def _mtp_hidden(params, g, targets, cfg: JoyAIConfig):
    """``(the module's block's output [B, T, d_model], counts,
    held_counts)`` for the main stack's ``g`` and the next tokens."""
    m = params["mtp"]
    z = jax.checkpoint(_mtp_input, static_argnums=(3,))(
        m, params["embed"][targets], g, cfg)
    return _sparse_layer(m["block"], z, cfg)


def forward(params, tokens, cfg: JoyAIConfig):
    """The main logits ``[B, T, vocab]`` in float32, whole: for tests'
    sizes."""
    g = _final(params, _hidden(params, tokens, cfg)[0], cfg)
    with jax.named_scope("head"):
        return _head(params, g)


def mtp_forward(params, tokens, targets, cfg: JoyAIConfig):
    """The module's logits ``[B, T, vocab]`` in float32 (position ``i``
    predicts ``t_{i+2}``), whole: for tests' sizes."""
    g = _final(params, _hidden(params, tokens, cfg)[0], cfg)
    with jax.named_scope("mtp"):
        u = _mtp_hidden(params, g, targets, cfg)[0]
        return _head(params, _rmsnorm(u, params["mtp"]["final_norm"],
                                      cfg.norm_eps))


def losses(params, tokens, targets, cfg: JoyAIConfig):
    """``((L_main, L_mtp), counts [expert layers (+ 1), n_experts],
    held_counts [.., experts_held])``: both terms as this rank's own means,
    the head :data:`HEAD_TOKENS` tokens at a time, and the load of every
    expert layer, the module's last."""
    x, counts, held = _hidden(params, tokens, cfg)
    g = _final(params, x, cfg)
    main = _blocks.next_token_loss_in_blocks(
        g, targets, lambda y: _head(params, y), HEAD_TOKENS)
    mtp = jnp.zeros((), jnp.float32)
    if cfg.mtp_modules:
        with jax.named_scope("mtp"):
            u, c, h = _mtp_hidden(params, g, targets, cfg)
            counts, held = counts + [c], held + [h]
            # position i is held to t_{i+2}: the next position's target
            after = jnp.roll(targets, -1, axis=1)
            keep = jnp.arange(targets.shape[1]) < targets.shape[1] - 1
            mtp = _blocks.next_token_loss_in_blocks(
                u, after, lambda y: _head(params, _rmsnorm(
                    y, params["mtp"]["final_norm"], cfg.norm_eps)),
                HEAD_TOKENS, mask=jnp.broadcast_to(keep, targets.shape))
    stack = lambda rows, width: (jnp.stack(rows) if rows else
                                 jnp.zeros((0, width), jnp.int32))
    return ((main, mtp), stack(counts, cfg.n_experts),
            stack(held, cfg.moe_cfg().held))


def loss_fn(params, tokens, targets, cfg: JoyAIConfig):
    """``(L_main + mtp_weight L_mtp, counts)``: the loss and what moves the
    bias."""
    (main, mtp), counts, _ = losses(params, tokens, targets, cfg)
    return main + cfg.mtp_weight * mtp, counts


def expert_load(params, tokens, targets, cfg: JoyAIConfig):
    """Assignments that land on each held expert, ``[expert layers (the
    module's last), experts_held]`` int32, for a batch: the counter the
    benchmark reads in set-up.  ``tokens.size * top_k`` assignments are
    made in each expert layer."""
    return losses(params, tokens, targets, cfg)[2]


# ----------------------------------------------------------- the bias, step
def moved_bias(bias, counts, speed):
    """A layer's selection bias after a step in which its experts got
    ``counts`` assignments: up by ``speed`` where an expert got fewer than
    the mean, down where more."""
    counts = counts.astype(jnp.float32)
    return bias + speed * jnp.sign(jnp.mean(counts) - counts).astype(
        bias.dtype)


def update_bias(params, counts, cfg: JoyAIConfig):
    """``params`` with every expert layer's ``router_bias`` moved by its row
    of ``counts`` (the main stack's expert layers in order, then the
    module's)."""
    rows = iter(counts)

    def moved(layer):
        if "moe" not in layer:
            return layer
        moe = layer["moe"]
        return {**layer, "moe": {**moe, "router_bias": moved_bias(
            moe["router_bias"], next(rows), cfg.bias_speed)}}

    return _over_blocks(params, moved)


def make_train_step(cfg: JoyAIConfig, optimizer, axis_name=None):
    """:func:`blocks.train_step` of this module's ``loss_fn``, the bias
    moved after the optimizer's update by the step's counts, summed over
    the replicas of ``axis_name`` where given (they hold one bias)."""
    def after_update(params, counts):
        if axis_name is not None:
            counts = lax.psum(counts, axis_name)
        return update_bias(params, counts, cfg)

    return _blocks.train_step(
        lambda p, tokens, targets: loss_fn(p, tokens, targets, cfg),
        optimizer, after_update)
