"""Llama-family decoder with explicit dp/tp/sp parallelism (flagship model).

Role in the rebuild: BASELINE config #4 ("Llama-3 8B pure-DP with Adasum /
hierarchical allreduce on torus") plus the long-context requirement the
reference lacks (SURVEY.md §5): ring attention over the ``sp`` axis, Megatron
tensor parallelism over ``tp``, gradient allreduce over ``dp`` — all written
as explicit SPMD for ``shard_map``, the TPU-native analogue of the
reference's explicit-collective style (vs. letting GSPMD guess).

Parameters are plain pytrees (dict of dicts of jnp arrays) with a parallel
tree of ``PartitionSpec``s (``param_specs``) describing how each leaf is
sharded over the mesh; activations: batch over ``dp``, sequence over ``sp``,
heads/ffn over ``tp``.

TP convention (Megatron): wq/wk/wv/w1/w3 column-sharded, wo/w2 row-sharded
with a psum after; norms/embeddings replicated (their grads are psum'd over
``tp`` in the train step — the f/g-operator pair).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P
from ..compat import axis_size as compat_axis_size

from ..parallel.ring_attention import (NEG_INF, local_flash_attention,
                                       ring_attention)


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    d_ff: int = 14336
    max_seq: int = 8192
    rope_theta: float = 500000.0
    dtype: Any = jnp.bfloat16
    # mesh axis names (set to None to disable an axis)
    dp_axis: Optional[str] = "dp"
    tp_axis: Optional[str] = "tp"
    sp_axis: Optional[str] = "sp"
    # Sequence-parallel engine: "ring" (K/V rotate — any head count,
    # O(T/sp) memory) or "ulysses" (two alltoalls to head-sharded layout —
    # needs q AND kv heads per tp shard divisible by sp; wins when ICI
    # alltoall bandwidth is plentiful).
    sp_impl: str = "ring"
    # Pipeline parallelism (beyond-ref, SURVEY.md §2c PP row): stage =
    # contiguous layer slab.  When set, ``init_params``/``param_specs``
    # emit the layer stack as STACKED arrays [n_layers, ...] sharded over
    # ``pp_axis`` (shard_map hands each stage its slab in layer order) and
    # ``forward`` runs the GPipe schedule from parallel/pipeline.py.
    # Composes with dp (data split) / tp (params within a layer) / sp
    # (sequence within attention).
    pp_axis: Optional[str] = None
    # Microbatches for the pipeline fill/drain (bubble = (pp-1)/(pp+M-1));
    # the per-shard batch must divide by it.  Ignored without pp_axis.
    n_microbatches: int = 2
    # Rematerialize each pipeline stage's forward in the backward scan
    # (jax.checkpoint): activation memory stops scaling with stage depth —
    # the 1F1B memory dividend, XLA-style (see parallel/pipeline.py).
    remat_stages: bool = False
    # Rematerialize each transformer layer in the NON-pipelined forward:
    # activation memory per layer collapses to the layer input, at ~1/3
    # extra forward FLOPs.  A memory lever for steps that do not otherwise
    # fit; what it costs a step on the chip is not measured.
    remat_layers: bool = False
    # Where the LM loss is computed under pp (docs/parallelism.md):
    # "broadcast"  — psum the [M, mb, T, D] pipeline output to every
    #                stage; each computes final-norm+head+nll redundantly
    #                (1/pp-scaled).  Simple; costs one activation psum
    #                (~M·mb·T·D bytes/step over the pp axis) plus
    #                redundant [B,T,vocab] matmuls.
    # "last_stage" — no activation broadcast: only the final stage's
    #                output is real (zeros elsewhere); every stage still
    #                runs the head matmul in lockstep (SPMD — no wall
    #                saving there) but only the last stage's nll counts
    #                and ONLY the scalar loss rides the psum.  At 8B
    #                geometry the avoided broadcast is ~B·T·4096·2 bytes
    #                per step per pp hop.  forward()/logits are then only
    #                valid on the last stage.
    pp_loss: str = "broadcast"
    # Mixture-of-Experts MLP (models/moe.py): n_experts > 0 replaces the
    # dense w1/w3/w2 MLP with Switch-routed experts; ``ep_axis`` shards
    # them (a DATA axis for everything else — tokens split over dp×ep, so
    # shard the batch over ("dp", "ep")).  Composes with tp (attention
    # stays tp-sharded; experts are not additionally tp-split), sp, and
    # pp (the router aux loss rides the pipeline carry as per-stage
    # partials).
    n_experts: int = 0
    ep_axis: Optional[str] = None
    capacity_factor: float = 1.25
    aux_weight: float = 0.01           # router load-balance loss weight
    router_mode: str = "tokens"        # "tokens" | "expert_choice"
    router_top_k: int = 1              # 1 = Switch, >=2 = GShard top-k
    router_z_weight: float = 0.0       # ST-MoE z-loss weight (0 = off)
    router_noise: float = 0.0          # router jitter std (needs rng=)
    moe_gated: bool = False            # SwiGLU experts (Mixtral shape)
    # Pallas flash attention: True/False, or None = resolve from the
    # HVD_TPU_FLASH env var at TRACE time (auto: on TPU for sequences at
    # or past the measured crossover HVD_TPU_FLASH_MIN_SEQ — causal
    # default 512; below it XLA's fused attention is faster, see
    # ops/flash_attention.flash_min_seq).  The env vars are not part of
    # any jit cache key — to toggle after a step has compiled, change
    # this config field (it IS traced).
    use_flash: Optional[bool] = None
    # Sliding-window (Mistral-style) causal attention: each position
    # attends its last ``sliding_window`` positions only.  The flash
    # kernel skips whole out-of-window blocks (O(T·W) compute); local
    # attention only for now — sp (ring/Ulysses) rejects it at trace
    # time (ring-step skipping is the natural extension).
    sliding_window: Optional[int] = None
    # Rolling KV cache for windowed decode: the cache becomes a ring of
    # ``sliding_window + rolling_slack`` slots (position p lives at slot
    # p mod R) instead of max_seq — O(W) serving memory and UNBOUNDED
    # generation length.  The slack keeps a chunked write (decode_chunk,
    # speculative verify) from overwriting slots its own earlier rows
    # still attend: any chunk up to ``rolling_slack`` tokens is safe.
    rolling_cache: bool = False
    rolling_slack: int = 8
    # RMSNorm epsilon — checkpoint-dependent (Llama-3: 1e-5; several
    # families use 1e-6); models/convert.py parity depends on matching
    # the source checkpoint's value.
    norm_eps: float = 1e-5

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    def __post_init__(self):
        if self.sp_impl not in ("ring", "ulysses"):
            raise ValueError(
                f"sp_impl must be 'ring' or 'ulysses', got "
                f"{self.sp_impl!r}")
        if self.pp_loss not in ("broadcast", "last_stage"):
            raise ValueError(
                f"pp_loss must be 'broadcast' or 'last_stage', got "
                f"{self.pp_loss!r}")
        if self.sliding_window is not None and self.sliding_window < 1:
            raise ValueError(
                f"sliding_window must be >= 1 (or None to disable), got "
                f"{self.sliding_window!r}")
        if self.rolling_cache:
            if not self.sliding_window:
                raise ValueError("rolling_cache requires sliding_window "
                                 "(a full-attention model needs every "
                                 "past position)")
            if self.rolling_slack < 1:
                raise ValueError("rolling_slack must be >= 1")

    @property
    def all_axes(self):
        """Every mesh axis this model can touch — THE axis list for loss
        scaling and loss psums (one place to extend, three consumers)."""
        return (self.dp_axis, self.sp_axis, self.tp_axis, self.pp_axis,
                self.ep_axis)

    @property
    def spec_gated_axes(self):
        """Axes whose gradient psum is per-leaf spec-gated: leaves SHARDED
        over the axis carry exact shard gradients (no psum); replicated
        leaves' partials are summed.  tp/pp = redundant compute; ep = a
        data axis whose expert slabs already aggregated every rank's
        tokens through the all_to_all transpose."""
        return (self.tp_axis, self.pp_axis, self.ep_axis)

    def moe_cfg(self):
        """The models.moe config for this model's MoE MLP (single source
        of truth: init/specs/forward all derive from moe.py through it)."""
        from . import moe as _moe
        return _moe.MoEConfig(
            d_model=self.d_model, d_ff=self.d_ff,
            n_experts=self.n_experts, capacity_factor=self.capacity_factor,
            ep_axis=self.ep_axis, router_mode=self.router_mode,
            router_top_k=self.router_top_k,
            router_z_weight=self.router_z_weight,
            router_noise=self.router_noise, gated=self.moe_gated,
            dtype=self.dtype)


def tiny(vocab_size: int = 256, d_model: int = 64, n_layers: int = 2,
         n_heads: int = 4, n_kv_heads: int = 2, d_ff: int = 128,
         max_seq: int = 128, **kw) -> LlamaConfig:
    """Small config for tests / dryruns."""
    return LlamaConfig(vocab_size=vocab_size, d_model=d_model,
                       n_layers=n_layers, n_heads=n_heads,
                       n_kv_heads=n_kv_heads, d_ff=d_ff, max_seq=max_seq, **kw)


def llama3_8b() -> LlamaConfig:
    return LlamaConfig()  # defaults above are the 8B geometry


def mixtral_8x7b() -> LlamaConfig:
    """Mixtral-8x7B geometry: Mistral attention + 8 SwiGLU experts with
    normalized top-2 routing (models/moe.py gated experts).

    ``capacity_factor=4.0`` (= n_experts / top_k) gives every expert
    worst-case capacity, so NO token is ever capacity-dropped and a
    converted checkpoint reproduces HF logits exactly (Mixtral itself
    has no capacity drops).  Training at scale usually wants a tighter
    factor (1.25–2.0) — override ``capacity_factor`` for that; drops
    then fall back to the residual path."""
    return LlamaConfig(vocab_size=32000, d_model=4096, n_layers=32,
                       n_heads=32, n_kv_heads=8, d_ff=14336,
                       max_seq=32768, rope_theta=1e6,
                       n_experts=8, router_top_k=2, moe_gated=True,
                       capacity_factor=4.0, ep_axis="ep")


def mistral_7b() -> LlamaConfig:
    """Mistral-7B geometry: the Llama architecture + sliding-window
    attention (the flash kernel skips whole out-of-window blocks)."""
    return LlamaConfig(vocab_size=32000, d_model=4096, n_layers=32,
                       n_heads=32, n_kv_heads=8, d_ff=14336,
                       max_seq=32768, rope_theta=10000.0,
                       sliding_window=4096)


# ------------------------------------------------------------------- params
def init_params(cfg: LlamaConfig, key) -> Dict:
    """Initialize the full (unsharded) parameter pytree."""
    k = iter(jax.random.split(key, 4 + 7 * cfg.n_layers))
    D, H, K, Hd, F = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                      cfg.head_dim, cfg.d_ff)
    dt = cfg.dtype

    def dense(key, fan_in, shape):
        return (jax.random.normal(key, shape, jnp.float32)
                * (1.0 / np.sqrt(fan_in))).astype(dt)

    layers = []
    for _ in range(cfg.n_layers):
        layer = {
            "attn_norm": jnp.ones((D,), dt),
            "wq": dense(next(k), D, (D, H * Hd)),
            "wk": dense(next(k), D, (D, K * Hd)),
            "wv": dense(next(k), D, (D, K * Hd)),
            "wo": dense(next(k), H * Hd, (H * Hd, D)),
            "mlp_norm": jnp.ones((D,), dt),
        }
        if cfg.n_experts:
            from . import moe as _moe
            layer["moe"] = _moe.init_params(cfg.moe_cfg(), next(k))
        else:
            layer |= {
                "w1": dense(next(k), D, (D, F)),
                "w3": dense(next(k), D, (D, F)),
                "w2": dense(next(k), F, (F, D)),
            }
        layers.append(layer)
    if cfg.pp_axis:
        # Stacked layout [n_layers, ...]: shard_map slices axis 0 over the
        # pp axis in order, so stage i holds the contiguous layer slab
        # [i*L/pp, (i+1)*L/pp).  tree_map so nested subtrees (MoE) stack.
        layers = jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *layers)
    return {
        "embed": dense(next(k), D, (cfg.vocab_size, D)),
        "layers": layers,
        "final_norm": jnp.ones((D,), dt),
        "lm_head": dense(next(k), D, (D, cfg.vocab_size)),
    }


def param_specs(cfg: LlamaConfig) -> Dict:
    """PartitionSpec tree matching ``init_params`` (tp shards within a
    layer, pp shards the stacked layer axis; params are replicated over
    dp/sp)."""
    tp = cfg.tp_axis
    layer = {
        "attn_norm": P(),
        "wq": P(None, tp),
        "wk": P(None, tp),
        "wv": P(None, tp),
        "wo": P(tp, None),
        "mlp_norm": P(),
    }
    if cfg.n_experts:
        from . import moe as _moe
        layer["moe"] = _moe.param_specs(cfg.moe_cfg())
    else:
        layer |= {
            "w1": P(None, tp),
            "w3": P(None, tp),
            "w2": P(tp, None),
        }
    if cfg.pp_axis:
        layers = jax.tree_util.tree_map(
            lambda spec: P(cfg.pp_axis, *spec), layer,
            is_leaf=lambda x: isinstance(x, P))
    else:
        layers = [jax.tree_util.tree_map(
            lambda s: s, layer, is_leaf=lambda x: isinstance(x, P))
            for _ in range(cfg.n_layers)]
    return {
        "embed": P(),
        "layers": layers,
        "final_norm": P(),
        "lm_head": P(),
    }


# ------------------------------------------------------------------ forward
def _rmsnorm(x, w, eps=1e-5):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) * lax.rsqrt(var + eps)).astype(x.dtype) * w


def _rope(x, positions, theta):
    """Rotary embeddings; x: [B, T, H, Hd], positions: [T]."""
    B, T, H, Hd = x.shape
    half = Hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = positions[:, None].astype(jnp.float32) * freqs[None, :]  # [T, half]
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1).astype(x.dtype)


def _use_pallas_flash(cfg: "LlamaConfig", seq: Optional[int] = None) -> bool:
    """Pallas flash attention on TPU by default for sequences past the
    measured crossover (the [Tq,Tk] scores never touch HBM —
    ops/flash_attention.py; below it XLA's fused attention is faster,
    see flash_min_seq).  ``cfg.use_flash`` decides when set; otherwise
    HVD_TPU_FLASH=1/0 forces it on (interpret mode off-TPU, for tests)
    or off — read at TRACE time only (see LlamaConfig)."""
    from ..ops.flash_attention import resolve_flash
    return resolve_flash(cfg.use_flash, seq=seq, causal=True)


def _qkv(x, p, cfg: LlamaConfig, positions):
    """Project + rope this rank's head shard — THE qkv contract, shared
    by training attention, blockwise prefill and decode_step so the
    three paths cannot drift (tp head split, rope on q and k)."""
    B, T, _ = x.shape
    tp = compat_axis_size(cfg.tp_axis) if cfg.tp_axis else 1
    if cfg.n_heads % tp or cfg.n_kv_heads % tp:
        raise ValueError(f"n_heads={cfg.n_heads}/n_kv_heads={cfg.n_kv_heads} "
                         f"must be divisible by tp={tp}")
    H, K, Hd = cfg.n_heads // tp, cfg.n_kv_heads // tp, cfg.head_dim
    q = (x @ p["wq"]).reshape(B, T, H, Hd)
    k = (x @ p["wk"]).reshape(B, T, K, Hd)
    v = (x @ p["wv"]).reshape(B, T, K, Hd)
    return (_rope(q, positions, cfg.rope_theta),
            _rope(k, positions, cfg.rope_theta), v)


def _wo_project(out, p, cfg: LlamaConfig):
    """Row-parallel output projection (+psum over tp) — shared epilogue
    of every attention path."""
    B, T = out.shape[:2]
    o = out.reshape(B, T, -1) @ p["wo"]
    if cfg.tp_axis:
        o = lax.psum(o, cfg.tp_axis)
    return o


def _local_attend(q, k, v, cfg: LlamaConfig):
    """Causal local attention through the same flash routing as every
    path (Pallas kernel on TPU, jnp fallback otherwise); sliding window
    when the config asks for it."""
    if _use_pallas_flash(cfg, seq=q.shape[1]):
        from ..ops.flash_attention import flash_attention
        return flash_attention(q, k, v, causal=True,
                               window=cfg.sliding_window)
    return local_flash_attention(q, k, v, causal=True,
                                 window=cfg.sliding_window)


def _attention(x, p, cfg: LlamaConfig, positions):
    """Self-attention on the local tp shard of heads; sp-ring over sequence."""
    q, kk, v = _qkv(x, p, cfg, positions)

    sp = compat_axis_size(cfg.sp_axis) if cfg.sp_axis else 1
    if sp > 1 and cfg.sliding_window:
        raise ValueError(
            "sliding_window composes with dp/tp/pp/ep but not (yet) with "
            "sequence parallelism — disable sp_axis or the window")
    if sp > 1 and cfg.sp_impl == "ulysses":
        # Head exchange instead of kv rotation (docs/parallelism.md for
        # the tradeoff); GQA kv travels un-repeated through the alltoall.
        from ..ops.flash_attention import flash_attention
        from ..parallel.ulysses import ulysses_attention
        # Ulysses attends the FULL gathered sequence on local heads.
        attn = (flash_attention if _use_pallas_flash(cfg, seq=q.shape[1] * sp)
                else local_flash_attention)   # same routing as every path
        out = ulysses_attention(q, kk, v, attn_fn=attn,
                                axis_name=cfg.sp_axis, causal=True)
    elif sp > 1:
        # GQA passes through un-repeated: the ring handles it on both
        # engines (pallas reads shared kv heads through block index maps —
        # H/K× less ring traffic; the jnp fallback repeats internally).
        out = ring_attention(q, kk, v, axis_name=cfg.sp_axis, causal=True,
                             use_flash=cfg.use_flash)
    else:
        out = _local_attend(q, kk, v, cfg)
    return _wo_project(out, p, cfg)


def _mlp(x, p, cfg: LlamaConfig, rng=None):
    """Dense SwiGLU MLP, or top-k-routed MoE when cfg.n_experts > 0.

    Returns ``(y, router_losses [2])`` — ``[aux, z_loss]`` stacked so ONE
    scalar-shaped carrier threads both through scans/pipeline carries;
    dense returns zeros.  The MoE path is NOT tp-split (experts shard
    over ep; every tp rank computes the same routing/experts redundantly
    — acceptable at the tp degrees attention wants, and it keeps the
    exchange one all_to_all instead of a tp×ep lattice; the arithmetic
    is written down in docs/moe.md)."""
    if cfg.n_experts:
        from . import moe as _moe
        B, T, D = x.shape
        y, aux, zl = _moe.moe_ffn(x.reshape(B * T, D), p["moe"],
                                  cfg.moe_cfg(), rng=rng)
        return y.reshape(B, T, D), jnp.stack([aux, zl])
    h = jax.nn.silu(x @ p["w1"]) * (x @ p["w3"])
    out = h @ p["w2"]
    if cfg.tp_axis:
        out = lax.psum(out, cfg.tp_axis)
    return out, jnp.zeros((2,), jnp.float32)


def _layer_apply(p, x, cfg: LlamaConfig, positions, rng=None):
    x = x + _attention(_rmsnorm(x, p["attn_norm"], cfg.norm_eps), p, cfg,
                       positions)
    y, aux = _mlp(_rmsnorm(x, p["mlp_norm"], cfg.norm_eps), p, cfg,
                  rng=rng)
    return x + y, aux


def forward(params, tokens, cfg: LlamaConfig, rng=None):
    """Logits for local token shard (public surface; see _forward)."""
    return _forward(params, tokens, cfg, rng=rng)[0]


def _forward(params, tokens, cfg: LlamaConfig, rng=None):
    """(logits, router_losses [2]) for local token shard [B_loc, T_loc]
    (call inside shard_map, or directly when all axes are disabled/
    size-1).  ``router_losses`` stacks the summed MoE load-balance aux
    and router z-loss (zeros for dense models).

    ``rng`` (router jitter) is folded once with every DATA axis index
    (dp/ep/sp — each rank draws independent noise over its own token
    shard; tp/pp ranks computing the same routing redundantly share the
    draw) and then per layer.  Under pp, microbatches within a stage
    share a layer's draw — jitter is a regularizer, not a statistical
    contract, so the correlation is accepted.

    With ``pp_axis`` set, ``params["layers"]`` is this stage's slab of the
    stacked layer arrays and the blocks run under the GPipe microbatch
    schedule; embedding and the LM head are computed replicated on every
    stage (cheap next to the blocks), with the head reading the last
    stage's pipeline output broadcast via the zero-sum psum trick."""
    B, T = tokens.shape
    if cfg.sp_axis:
        sp_idx = lax.axis_index(cfg.sp_axis)
        positions = sp_idx * T + jnp.arange(T)
    else:
        positions = jnp.arange(T)
    if rng is not None:
        for ax in (cfg.dp_axis, cfg.ep_axis, cfg.sp_axis):
            if ax:
                rng = jax.random.fold_in(rng, lax.axis_index(ax))
    x = params["embed"][tokens]
    aux_total = jnp.zeros((2,), jnp.float32)
    if cfg.pp_axis:
        from ..parallel.pipeline import microbatch, pipeline_apply
        M = cfg.n_microbatches
        micro_x = microbatch(x, M)           # [M, B/M, T, D]

        def stage_fn(slab, xm):
            lps = jax.tree_util.tree_leaves(slab)[0].shape[0]
            base = (lax.axis_index(cfg.pp_axis) * lps
                    if rng is not None else 0)

            def body(carry, p):
                h, aux, j = carry
                lrng = (jax.random.fold_in(rng, base + j)
                        if rng is not None else None)
                h, a = _layer_apply(p, h, cfg, positions, rng=lrng)
                return (h, aux + a, j + 1), None
            (h, aux, _), _ = lax.scan(
                body, (xm, jnp.zeros((2,), jnp.float32),
                       jnp.zeros((), jnp.int32)), slab)
            return h, aux

        x, aux_total = pipeline_apply(
            stage_fn, params["layers"], micro_x, axis_name=cfg.pp_axis,
            broadcast_out=(cfg.pp_loss == "broadcast"),
            remat=cfg.remat_stages, with_aux=True,
            aux_init=aux_total)
        # moe aux/z are per-token MEANs (batch-size invariant); the
        # pipeline accumulated one per microbatch, so average — otherwise
        # the scheduling knob n_microbatches would scale the training
        # objective.
        aux_total = aux_total / M
        x = x.reshape((B, T, -1))
    else:
        def _apply(p, h, positions, lrng):
            return _layer_apply(p, h, cfg, positions, rng=lrng)
        if cfg.remat_layers:
            _apply = jax.checkpoint(_apply)
        for i, p in enumerate(params["layers"]):
            lrng = (jax.random.fold_in(rng, i)
                    if rng is not None else None)
            x, aux = _apply(p, x, positions, lrng)
            aux_total = aux_total + aux
    x = _rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return x @ params["lm_head"], aux_total


def loss_fn(params, tokens, targets, cfg: LlamaConfig, rng=None):
    """PARTIAL next-token cross-entropy: this rank's contribution to the
    global mean.  ``rng`` threads router jitter (cfg.router_noise > 0
    requires it; see _forward for the fold-in contract).

    Written for shard_map's sum-semantics autodiff (the transpose of an
    in-graph psum is psum): the differentiated function contains NO loss
    psum; instead per-rank partial losses are scaled so they sum to the true
    global mean across every mesh axis — 1/(global_count) for the dp/sp data
    split and 1/tp for the redundant tensor-parallel compute.  ``sync_grads``
    then turns per-rank partial grads into the exact mean gradient, and
    ``psum_loss`` recovers the scalar for logging.
    """
    logits, router = _forward(params, tokens, cfg, rng=rng)
    logits = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    # dp/sp/ep factors extend the local count to the global token count
    # (ep is a data axis when MoE is on); the tp/pp factors split the
    # redundantly-computed loss across ranks (every tp rank computes the
    # full head; every pp stage computes the loss from the broadcast
    # pipeline output).
    denom = float(nll.size)
    axes_denom = 1.0
    for ax in cfg.all_axes:
        if ax:
            axes_denom = axes_denom * compat_axis_size(ax)
    nll_sum = jnp.sum(nll)
    if cfg.pp_axis and cfg.pp_loss == "last_stage":
        # Only the final stage's pipeline output is real (no activation
        # broadcast); mask the garbage nll elsewhere and undo pp's share
        # of the redundancy factor — the loss is no longer computed pp×
        # redundantly, it exists once.
        pp_n = compat_axis_size(cfg.pp_axis)
        is_last = (lax.axis_index(cfg.pp_axis) == pp_n - 1)
        nll_sum = jnp.where(is_last, nll_sum, 0.0) * pp_n
    total = nll_sum / (denom * axes_denom)
    if cfg.n_experts:
        # Per-rank mean router losses (mean over layers), scaled so the
        # psum over every axis yields the cross-rank mean.  Unlike the
        # nll (redundant over pp via the broadcast output), they are
        # PARTITIONED over pp — each stage computed only its own slab's
        # routers — so pp's factor must not divide them.
        aux_denom = axes_denom
        if cfg.pp_axis:
            aux_denom = aux_denom / compat_axis_size(cfg.pp_axis)
        router_losses = (cfg.aux_weight * router[0]
                         + cfg.router_z_weight * router[1])
        total = total + (router_losses / cfg.n_layers) / aux_denom
    return total


def psum_loss(loss_partial, cfg: LlamaConfig):
    """Sum per-rank partial losses into the true global mean loss."""
    for ax in cfg.all_axes:
        if ax:
            loss_partial = lax.psum(loss_partial, ax)
    return loss_partial


# --------------------------------------------------------------- train step
def sync_grads(grads, cfg: LlamaConfig, specs=None):
    """Cross-rank gradient synchronization for the explicit-SPMD step.

    Under sum-semantics autodiff each rank's grad is its partial
    contribution, so:

    - ALL params: psum over dp (the Horovod allreduce) and sp (each sp rank
      saw a different sequence chunk).
    - tp-replicated params only (norms, embed, lm_head): additionally psum
      over tp to combine the per-shard contributions; tp-SHARDED params'
      grads are already exact for their shard (the cotangent arriving
      through the row-parallel psum's transpose is the full one).
    - pp-replicated params (embed/lm_head/final_norm): psum over pp — the
      embed grad is nonzero only on stage 0 (the pipeline consumes input
      there) and the head grad is 1/pp-scaled on every stage, so the psum
      reassembles both.  pp-SHARDED slabs are exact per stage, like tp.
    - ep (MoE): a data axis — non-expert leaves saw only this rank's
      token shard (psum over ep like dp/sp), while ep-SHARDED expert
      slabs already aggregated every ep rank's tokens through the
      all_to_all transpose (exact, no psum).
    The 1/(count·tp·pp·ep) scaling inside ``loss_fn`` makes these psums
    land on the exact global-mean gradient.
    """
    specs = specs or param_specs(cfg)
    gated = cfg.spec_gated_axes

    def leaf_sync(g, spec):
        for ax in (cfg.dp_axis, cfg.sp_axis):
            if ax:
                g = lax.psum(g, ax)
        for ax in gated:
            if ax and all(s != ax for s in spec):
                g = lax.psum(g, ax)
        return g

    return jax.tree_util.tree_map(leaf_sync, grads, specs,
                                  is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------- inference
def init_cache(cfg: LlamaConfig, batch: int, max_seq: Optional[int] = None,
               sharded: Optional[bool] = None):
    """Per-layer KV cache ``[B, max_seq, n_kv_heads, head_dim]`` (zeros).

    Beyond-reference: Horovod ships no inference path at all; this is the
    decode half of the flagship model.  Static shape — the cache is a
    fixed ring of ``max_seq`` slots written via dynamic_update_slice, so
    one compiled decode step serves every position.
    """
    if cfg.rolling_cache:
        # Ring of W + slack slots (position p -> slot p mod R): O(W)
        # memory, unbounded generation.  max_seq is irrelevant here.
        T = cfg.sliding_window + cfg.rolling_slack
    else:
        T = max_seq or cfg.max_seq
    K = cfg.n_kv_heads
    if cfg.tp_axis:
        # Inside shard_map (tp decode) each rank holds its K/tp kv-head
        # shard; outside, the cache is global — shard it with
        # ``cache_specs``.  ``sharded`` overrides the auto-detection
        # (which keys on the axis name being bound at trace time).
        if sharded is None:
            try:
                tp = compat_axis_size(cfg.tp_axis)
            except NameError:       # axis unbound → outside shard_map
                tp = 1
        else:
            tp = compat_axis_size(cfg.tp_axis) if sharded else 1
        if cfg.n_kv_heads % tp:
            raise ValueError(f"n_kv_heads={cfg.n_kv_heads} must divide "
                             f"by tp={tp} for the sharded cache")
        K //= tp
    shape = (batch, T, K, cfg.head_dim)
    return [{"k": jnp.zeros(shape, cfg.dtype),
             "v": jnp.zeros(shape, cfg.dtype)}
            for _ in range(cfg.n_layers)]


def _check_cache_budget(t_final: int, cache_t: int,
                        cfg: Optional[LlamaConfig] = None):
    """Every position is static at trace time — refuse to decode past the
    cache instead of letting dynamic_update_slice clamp writes onto the
    last slot (which silently corrupts every later token).  A rolling
    cache has no length budget (positions wrap)."""
    if cfg is not None and cfg.rolling_cache:
        return
    if t_final > cache_t:
        raise ValueError(
            f"decode would write position {t_final - 1} but the KV cache "
            f"has only {cache_t} slots; raise max_seq (init_cache) or "
            f"generate fewer tokens")


def _decode_axes_check(cfg: LlamaConfig, what: str):
    """Decode supports tp (heads split, psum at wo — same Megatron
    contract as training) and rejects the training-only axes: dp is just
    batching (run more replicas), sp/pp restructure the sequence/depth in
    ways a token-at-a-time cache does not, ep would need the alltoall
    lattice per generated token."""
    bad = [ax for ax in (cfg.dp_axis, cfg.sp_axis, cfg.pp_axis,
                         cfg.ep_axis) if ax]
    if bad:
        raise ValueError(
            f"{what} supports tp only; disable {bad} "
            f"(dp/sp/pp/ep = None) in the decode config")


def decode_step(params, cache, tokens, pos, cfg: LlamaConfig):
    """One decode step: ``tokens [B]`` at position ``pos`` (traced
    scalar) -> (logits [B, vocab], updated cache).

    Runs single-device, or tp-sharded inside ``shard_map`` with the
    training param specs (wq/wk/wv column-split → this rank holds
    H/tp q heads and K/tp kv heads; wo row-split with a psum — the same
    f/g pair as ``_attention``) and the cache sharded over its head axis
    (``cache_specs``).  The Tq=1 case of ``decode_chunk`` — one
    implementation, two entry points.  Attention over the cache is a
    plain masked einsum: at Tq=1 there is no score matrix to tile, so
    flash buys nothing.
    """
    logits, cache = decode_chunk(params, cache, tokens[:, None], pos, cfg)
    return logits[:, 0, :], cache


def decode_chunk(params, cache, tokens, pos, cfg: LlamaConfig):
    """Cached forward over a SHORT chunk ``tokens [B, Tq]`` starting at
    position ``pos`` (traced scalar) -> (logits [B, Tq, vocab], cache).

    The multi-token generalization of ``decode_step`` (which is the
    Tq=1 case): chunk kv is written into the cache at [pos, pos+Tq) and
    each chunk row i attends the cache prefix ``<= pos + i`` — the
    verify pass of speculative decoding, and the building block for any
    multi-token stepping.  tp-sharded like decode_step.
    """
    _decode_axes_check(cfg, "decode_chunk")
    B, Tq = tokens.shape
    x = params["embed"][tokens]                      # [B, Tq, D]
    positions = pos + jnp.arange(Tq)
    new_cache = []
    T = cache[0]["k"].shape[1]
    if cfg.rolling_cache:
        if Tq > cfg.rolling_slack:
            raise ValueError(
                f"decode_chunk of {Tq} tokens exceeds rolling_slack="
                f"{cfg.rolling_slack}: earlier chunk rows would attend "
                f"slots the later writes just overwrote; raise "
                f"rolling_slack")
        # Slot j holds position p_j = the largest p ≤ (chunk end) with
        # p ≡ j (mod R); row i attends p_j in (pos+i-W, pos+i].  The
        # explicit p_j >= 0 term masks never-written slots — without it
        # a context SHORTER than the window would attend zero-filled
        # slots (their derived p_j is negative, but so is qpos-W then).
        R = T
        end = pos + Tq - 1
        j = jnp.arange(R)[None, :]
        p_j = end - ((end - j) % R)                  # [1, R]
        qpos = (pos + jnp.arange(Tq))[:, None]       # [Tq, 1]
        valid = (p_j >= 0) & (p_j <= qpos) \
            & (p_j > qpos - cfg.sliding_window)
        write_slots = (pos + jnp.arange(Tq)) % R     # [Tq]
    else:
        # valid[i, t]: chunk row i sees cache positions t <= pos + i
        # (and, with a sliding window, only the last W of them).
        valid = (jnp.arange(T)[None, :]
                 <= (pos + jnp.arange(Tq))[:, None])     # [Tq, T]
        if cfg.sliding_window:
            valid = jnp.logical_and(
                valid, jnp.arange(T)[None, :]
                > (pos + jnp.arange(Tq))[:, None] - cfg.sliding_window)
    valid = valid[None, None, None, :, :]            # [1,1,1,Tq,T]
    for p, c in zip(params["layers"], cache):
        h = _rmsnorm(x, p["attn_norm"], cfg.norm_eps)
        q, k_new, v_new = _qkv(h, p, cfg, positions)  # local head shard
        H, K, Hd = q.shape[2], k_new.shape[2], q.shape[3]
        if cfg.rolling_cache:
            if Tq == 1:
                # Hot decode loop: a single position is always a
                # contiguous write — dynamic_update_slice at pos % R
                # avoids scatter lowering per layer per token.
                ck = lax.dynamic_update_slice(
                    c["k"], k_new.astype(c["k"].dtype),
                    (0, pos % T, 0, 0))
                cv = lax.dynamic_update_slice(
                    c["v"], v_new.astype(c["v"].dtype),
                    (0, pos % T, 0, 0))
            else:
                ck = c["k"].at[:, write_slots].set(
                    k_new.astype(c["k"].dtype))
                cv = c["v"].at[:, write_slots].set(
                    v_new.astype(c["v"].dtype))
        else:
            ck = lax.dynamic_update_slice(
                c["k"], k_new.astype(c["k"].dtype), (0, pos, 0, 0))
            cv = lax.dynamic_update_slice(
                c["v"], v_new.astype(c["v"].dtype), (0, pos, 0, 0))
        new_cache.append({"k": ck, "v": cv})
        # GQA groups against the shared kv, one extra chunk axis q.
        qg = q.reshape(B, Tq, K, H // K, Hd)
        s = jnp.einsum("bqkrd,btkd->bkrqt", qg, ck,
                       preferred_element_type=jnp.float32)
        s = s / np.sqrt(Hd)
        s = jnp.where(valid, s, NEG_INF)
        w = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkrqt,btkd->bqkrd", w.astype(cv.dtype), cv,
                       preferred_element_type=jnp.float32)
        x = x + _wo_project(o.reshape(B, Tq, H, Hd).astype(x.dtype),
                            p, cfg)
        y, _ = _mlp(_rmsnorm(x, p["mlp_norm"], cfg.norm_eps), p, cfg)
        x = x + y
    x = _rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return (x @ params["lm_head"]).astype(jnp.float32), new_cache


def cache_specs(cfg: LlamaConfig):
    """PartitionSpecs for ``init_cache``'s pytree under tp decode: the
    kv-head axis shards over tp, matching the column-split wk/wv."""
    spec = {"k": P(None, None, cfg.tp_axis, None),
            "v": P(None, None, cfg.tp_axis, None)}
    return [spec for _ in range(cfg.n_layers)]


def prefill(params, cache, tokens, cfg: LlamaConfig):
    """Batched prefill: fill the cache from a prompt ``[B, T0]`` in ONE
    pass over the layers; returns (last logits, cache).

    Each layer projects q/k/v for the WHOLE prompt, writes its kv block
    into the cache at positions [0, T0), and attends causally through
    the same flash routing as training (Pallas kernel on TPU, tiled
    [Tq, Tk] scores that never materialize in HBM) — matmul-shaped MXU
    work, linear in prompt blocks.  The previous implementation scanned
    ``decode_step`` token-by-token: T0 sequential steps each attending
    over the full cache, O(T0·cache_T) with no batching (VERDICT r4
    weak #1).  tp-sharded like decode_step.
    """
    _decode_axes_check(cfg, "prefill")
    B, T0 = tokens.shape
    _check_cache_budget(T0, cache[0]["k"].shape[1], cfg)
    positions = jnp.arange(T0)
    x = params["embed"][tokens]                      # [B, T0, D]
    new_cache = []
    for p, c in zip(params["layers"], cache):
        h = _rmsnorm(x, p["attn_norm"], cfg.norm_eps)
        q, k, v = _qkv(h, p, cfg, positions)         # local head shard
        if cfg.rolling_cache:
            # Only the last min(T0, R) prompt positions can ever be
            # attended again — write just those, at their ring slots
            # (static indices: T0 and R are trace-time constants).
            R = c["k"].shape[1]
            keep = min(T0, R)
            slots = np.arange(T0 - keep, T0) % R
            ck = c["k"].at[:, slots].set(
                k[:, T0 - keep:].astype(c["k"].dtype))
            cv = c["v"].at[:, slots].set(
                v[:, T0 - keep:].astype(c["v"].dtype))
        else:
            ck = lax.dynamic_update_slice(c["k"], k.astype(c["k"].dtype),
                                          (0, 0, 0, 0))
            cv = lax.dynamic_update_slice(c["v"], v.astype(c["v"].dtype),
                                          (0, 0, 0, 0))
        new_cache.append({"k": ck, "v": cv})
        x = x + _wo_project(_local_attend(q, k, v, cfg), p, cfg)
        y, _ = _mlp(_rmsnorm(x, p["mlp_norm"], cfg.norm_eps), p, cfg)
        x = x + y
    x = _rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return ((x[:, -1, :] @ params["lm_head"]).astype(jnp.float32),
            new_cache)


def sample_logits(logits, rng, temperature: float = 0.0,
                  top_p: float = 1.0, top_k: int = 0):
    """Pick next tokens from ``logits [B, vocab]``.

    temperature == 0 → greedy argmax (rng unused).  Otherwise scale by
    1/temperature, optionally keep only the ``top_k`` largest logits,
    optionally apply nucleus filtering (smallest set of tokens whose
    probability mass ≥ ``top_p``), then draw categorically.  All masks
    are static-shape (sort + where) — jit/scan friendly.
    """
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits.astype(jnp.float32) / temperature
    if top_k > 0:
        kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
        logits = jnp.where(logits < kth, NEG_INF, logits)
    if top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # Keep every token strictly inside the nucleus plus the first one
        # past the boundary (standard nucleus semantics: the smallest set
        # reaching top_p).
        keep_sorted = cum - probs < top_p
        cutoff = jnp.min(jnp.where(keep_sorted, sorted_logits,
                                   jnp.inf), axis=-1)[:, None]
        logits = jnp.where(logits < cutoff, NEG_INF, logits)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


def generate(params, prompt, n_tokens: int, cfg: LlamaConfig,
             max_seq: Optional[int] = None,
             temperature: float = 0.0, top_p: float = 1.0,
             top_k: int = 0, rng=None):
    """Generation: ``prompt [B, T0]`` -> ``[B, n_tokens]``.

    Greedy by default; ``temperature > 0`` samples (with optional
    ``top_k`` / nucleus ``top_p`` filtering; ``rng`` required, folded
    per position).  jit-compatible end to end (scan over a static token
    budget); tp-sharded like decode_step — every tp rank holds the full
    psum'd logits, so sampling stays deterministic across the group as
    long as the caller passes the same rng to every rank."""
    B, T0 = prompt.shape
    if n_tokens < 1:
        return jnp.zeros((B, 0), jnp.int32)
    if temperature > 0.0 and rng is None:
        raise ValueError("temperature > 0 requires rng=")
    cache = init_cache(cfg, B, max_seq)
    # The last generated token's own kv is never written back, hence -1.
    _check_cache_budget(T0 + n_tokens - 1, cache[0]["k"].shape[1], cfg)
    logits, cache = prefill(params, cache, prompt, cfg)

    def pick(logits, t):
        step_rng = (jax.random.fold_in(rng, t)
                    if rng is not None else None)
        return sample_logits(logits, step_rng, temperature, top_p, top_k)

    def body(carry, t):
        tok, cache = carry
        logits, cache = decode_step(params, cache, tok, t, cfg)
        nxt = pick(logits, t)
        return (nxt, cache), nxt

    first = pick(logits, T0 - 1)
    (_, _), rest = lax.scan(body, (first, cache),
                            jnp.arange(T0, T0 + n_tokens - 1))
    return jnp.concatenate([first[:, None], rest.T], axis=1)


def speculative_generate(params, draft_params, prompt, n_tokens: int,
                         cfg: LlamaConfig,
                         draft_cfg: Optional[LlamaConfig] = None,
                         n_draft: int = 4,
                         max_seq: Optional[int] = None):
    """Greedy speculative decoding: a cheap draft model proposes
    ``n_draft`` tokens per round; the target model verifies them in ONE
    ``decode_chunk`` forward and emits every leading match plus the
    target's own correction token.

    EXACT by construction: the output equals greedy
    ``generate(params, prompt, n_tokens, cfg)`` token for token — the
    draft only changes how many sequential target forwards are needed
    (1 + n_accepted tokens per target forward instead of 1).  Batched:
    acceptance is the MINIMUM leading-match length across rows, so every
    row stays exact (for rows that matched further, the correction token
    IS their draft token); peak speedup needs agreeing rows.

    ``draft_cfg`` defaults to ``cfg`` (self-speculation layout); it must
    share the vocabulary.  jit-compatible end to end (``while_loop``
    over a static token budget; caches sized ``T0 + n_tokens + n_draft``
    so the last round's chunk always fits).
    """
    draft_cfg = draft_cfg or cfg
    _decode_axes_check(cfg, "speculative_generate")
    _decode_axes_check(draft_cfg, "speculative_generate (draft)")
    B, T0 = prompt.shape
    if n_tokens < 1:
        return jnp.zeros((B, 0), jnp.int32)
    k = int(n_draft)
    if k < 1:
        raise ValueError("n_draft must be >= 1")
    budget = max_seq or (T0 + n_tokens + k)
    cache_t = init_cache(cfg, B, budget)
    cache_d = init_cache(draft_cfg, B, budget)
    # Both caches have budgets of their own: a rolling target does not
    # exempt a fixed-length draft cache (whose clamped writes would
    # silently corrupt the draft and erode acceptance).
    _check_cache_budget(T0 + n_tokens + k, cache_t[0]["k"].shape[1], cfg)
    _check_cache_budget(T0 + n_tokens + k, cache_d[0]["k"].shape[1],
                        draft_cfg)

    logits_t, cache_t = prefill(params, cache_t, prompt, cfg)
    _, cache_d = prefill(draft_params, cache_d, prompt, draft_cfg)
    first = jnp.argmax(logits_t, axis=-1).astype(jnp.int32)   # [B]

    PAD = n_tokens + k + 1      # rounds overwrite their garbage tail
    out0 = jnp.zeros((B, PAD), jnp.int32)
    out0 = lax.dynamic_update_slice(out0, first[:, None], (0, 0))

    def cond(carry):
        return carry[1] < n_tokens

    def body(carry):
        out, n_done, last, cache_t, cache_d = carry
        p0 = T0 + n_done - 1    # position of `last`'s (unwritten) kv

        # Draft k tokens sequentially on the cheap model.  k+1 steps, not
        # k: the extra step writes d_k's own kv into the draft cache —
        # without it a fully-accepted round leaves a zero hole at
        # position p0+k that every later draft step would attend,
        # silently eroding the acceptance rate (output stays exact — the
        # target verifies — but the speedup decays).  Its proposed token
        # is discarded.
        def dstep(c, i):
            cache_d, tok = c
            logits, cache_d = decode_step(draft_params, cache_d, tok,
                                          p0 + i, draft_cfg)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (cache_d, nxt), nxt

        (cache_d, _), drafts = lax.scan(dstep, (cache_d, last),
                                        jnp.arange(k + 1))
        drafts = drafts.T[:, :k]                            # [B, k]

        # Verify in one target forward over [last, d_1..d_k]: logits row
        # i is the target's next-token distribution after position p0+i,
        # so t_i aligns with draft d_{i+1}.
        chunk = jnp.concatenate([last[:, None], drafts], axis=1)
        logits, cache_t = decode_chunk(params, cache_t, chunk, p0, cfg)
        targets = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B,k+1]

        m = (drafts == targets[:, :k])                      # [B, k]
        a_row = jnp.sum(jnp.cumprod(m.astype(jnp.int32), axis=1), axis=1)
        a = jnp.min(a_row)                                  # scalar 0..k
        correction = lax.dynamic_index_in_dim(targets, a, axis=1,
                                              keepdims=False)   # [B]
        padded = jnp.concatenate(
            [drafts, jnp.zeros((B, 1), jnp.int32)], axis=1)  # [B, k+1]
        emit = jnp.where(jnp.arange(k + 1)[None, :] < a, padded,
                         correction[:, None])
        out = lax.dynamic_update_slice(out, emit, (0, n_done))
        return out, n_done + a + 1, correction, cache_t, cache_d

    out, _, _, _, _ = lax.while_loop(
        cond, body, (out0, jnp.asarray(1, jnp.int32), first,
                     cache_t, cache_d))
    return out[:, :n_tokens]


def make_train_step(cfg: LlamaConfig, optimizer, with_rng: bool = False):
    """Returns ``step(params, opt_state, tokens, targets) -> (params,
    opt_state, loss)`` for use inside shard_map over (dp, sp, tp).
    ``with_rng=True`` adds a trailing ``rng`` argument threading router
    jitter (required when cfg.router_noise > 0)."""
    import optax

    # The four parts of the step under ``jax.named_scope`` (metadata only:
    # what a device trace shows an operation to belong to);
    # ``value_and_grad`` split into its halves so that each has its name.
    def _step(params, opt_state, tokens, targets, rng):
        with jax.named_scope("forward"):
            loss_partial, backward = jax.vjp(
                lambda p: loss_fn(p, tokens, targets, cfg, rng), params)
        with jax.named_scope("backward"):
            grads, = backward(jnp.ones_like(loss_partial))
        with jax.named_scope("gradient_exchange"):
            grads = sync_grads(grads, cfg)
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return params, opt_state, psum_loss(loss_partial, cfg)

    if with_rng:
        return _step

    def step(params, opt_state, tokens, targets):
        return _step(params, opt_state, tokens, targets, None)

    return step
