"""Nemotron-H-style hybrid decoder: Mamba-2 state-space layers, a LatentMoE
expert layer with sigmoid routing and ``relu^2`` experts, and position-free
grouped attention, each layer ONE mixer behind a pre-norm residual.

The published ``nemotron_h`` model as a training step on the normal path:
``make_train_step(cfg, optimizer)`` has the shape of
``llama.make_train_step`` and the other hybrids' and runs inside
``shard_map`` over ``hvd.mesh()`` with an in-graph
``hvd.DistributedOptimizer`` (the gradient exchange is the optimizer's; the
loss here is this rank's own mean).

Blocks are composed from the pattern string (the published
``hybrid_override_pattern``): character ``i`` is layer ``i``'s mixer, ``M``
Mamba-2, ``E`` expert layer, ``*`` attention.  Every layer is ``x <- x +
mixer_i(RMSNorm(x))`` (a plain weight, eps 1e-5); a final RMSNorm, then the
untied head.  Parameters are a list of per-layer dicts, each holding
``norm`` beside ``ssm``, ``moe`` or ``attn``.

- **Mamba-2** (``M``): ``models/mamba2.py``'s mixer told this config's
  sizes — 128 heads of 64, 8 groups of ``B`` and ``C``, a 128-wide state,
  chunks of 128 — a group of ``B``/``C`` at a time where the sequence is
  long (``mamba2.by_state_groups``).
- **Attention** (``*``): ``n_heads`` query heads on ``n_kv_heads`` key and
  value heads, no bias, causal, scale ``head_dim ** -0.5``, **no rotary**
  and no other position signal (positions reach it through the Mamba
  layers); the Pallas flash kernel on a TPU.
- **LatentMoE** (``E``): ``models/moe.py``'s ``dropless_moe_ffn`` told
  sigmoid scoring with a selection bias, the chosen renormalised and scaled
  by ``routed_scale``, ``relu^2`` experts of two matrices in a
  ``d_latent``-wide latent, and a shared expert without a gate.  It routes
  over all published experts and computes the part of the experts
  ``first_expert .. first_expert + experts_held``.

What the published ``config.json`` does not settle, and what is assumed
here (``benchmark/configs/nemotron3-super-120b-a12b-11l.json`` lists the
same under ``assumed``): the router reads the ``d_model``-wide state, not
the latent; no router auxiliary loss; the selection bias is a constant
buffer; no clamp on the step ``delta``; ``rope_theta`` and
``partial_rotary_factor`` are unused; the fused ``W_in``'s columns are
``[z | x | B | C | dt]``.  Left out: multi-token prediction
(``num_nextn_predict_layers``) — the loss is the mean next-token
cross-entropy alone.

Each layer is recomputed in the backward pass as its own region.

The parts of a step carry ``jax.named_scope`` names a device trace shows:
``ssm/proj``, ``ssm/conv``, ``ssm/scan``, ``ssm/out`` (the Mamba-2 mixer),
``attn/full``, ``moe/route``, ``moe/latent``, ``moe/dispatch``,
``moe/experts``, ``moe/shared``, ``moe/combine``, ``head``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import blocks as _blocks
from . import mamba2 as _ssm
from . import moe as _moe

PUBLISHED_PATTERN = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
                     "EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
KINDS = {"M": "ssm", "E": "moe", "*": "attn"}
# (token, head) pairs of a sequence whose chunked recurrence is computed
# together (mamba2.by_state_groups): one group of 16 heads at 8192 tokens,
# every head at once up to 1024 tokens
SCAN_TOKEN_HEADS = 1 << 17


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072
    d_model: int = 4096
    pattern: str = PUBLISHED_PATTERN    # a layer a character: M, E or *
    # Mamba-2
    ssm_heads: int = 128
    ssm_head_dim: int = 64
    ssm_groups: int = 8
    ssm_state: int = 128
    conv_kernel: int = 4
    chunk: int = 128
    step_min: float = 1e-3
    step_max: float = 0.1
    step_floor: float = 1e-4
    # attention
    n_heads: int = 32
    n_kv_heads: int = 2
    head_dim: int = 128
    # LatentMoE: the router's width, and the share held here
    n_experts: int = 512
    top_k: int = 22
    routed_scale: float = 5.0
    d_latent: int = 1024
    d_expert: int = 2688
    d_shared: int = 5376
    first_expert: int = 0
    experts_held: Optional[int] = None      # None = all of them
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # Pallas flash attention: True/False, or None = on a TPU (see
    # ops/flash_attention.resolve_flash).
    use_flash: Optional[bool] = None

    def __post_init__(self):
        if not self.pattern or set(self.pattern) - set(KINDS):
            raise ValueError(f"pattern {self.pattern!r}: a string of "
                             f"{sorted(KINDS)}")

    @property
    def n_layers(self) -> int:
        return len(self.pattern)

    def count(self, kind: str) -> int:
        return self.pattern.count(kind)

    def ssm_dims(self) -> _ssm.Mamba2Dims:
        return _ssm.Mamba2Dims(
            heads=self.ssm_heads, head_dim=self.ssm_head_dim,
            groups=self.ssm_groups, state=self.ssm_state,
            conv_kernel=self.conv_kernel, chunk=self.chunk,
            norm_eps=self.norm_eps, step_min=self.step_min,
            step_max=self.step_max, step_floor=self.step_floor)

    def moe_cfg(self) -> _moe.DroplessMoEConfig:
        return _moe.DroplessMoEConfig(
            d_model=self.d_model, d_ff=self.d_expert,
            n_experts=self.n_experts, top_k=self.top_k,
            first_expert=self.first_expert, experts_held=self.experts_held,
            d_shared=self.d_shared, dtype=self.dtype, scoring="sigmoid",
            routed_scale=self.routed_scale, expert_form="relu2",
            d_latent=self.d_latent, shared_gate=False)


def tiny(**kw) -> NemotronHConfig:
    """One period at test size: 16 experts of which 4 are held, top-3, a
    latent narrower than the model, 8 Mamba heads in 2 groups."""
    base = dict(vocab_size=256, d_model=64, pattern="MEMEMEM*EME",
                ssm_heads=8, ssm_head_dim=8, ssm_groups=2, ssm_state=16,
                chunk=32, n_heads=4, n_kv_heads=2, head_dim=16,
                n_experts=16, top_k=3, d_latent=32, d_expert=48, d_shared=96,
                experts_held=4, dtype=jnp.float32, use_flash=False)
    base.update(kw)
    return NemotronHConfig(**base)


def nemotron3_super_120b_a12b() -> NemotronHConfig:
    """The published sizes, every expert held."""
    return NemotronHConfig()


# ------------------------------------------------------------------- params
def init_params(cfg: NemotronHConfig, key) -> Dict:
    d, dt = cfg.d_model, cfg.dtype
    keys = iter(jax.random.split(key, 2 + 6 * cfg.n_layers))

    def dense(fan_in, shape):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                / np.sqrt(fan_in)).astype(dt)

    def attn():
        h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        return {"wq": dense(d, (d, h * hd)), "wk": dense(d, (d, kv * hd)),
                "wv": dense(d, (d, kv * hd)),
                "wo": dense(h * hd, (h * hd, d))}

    mixers = {"ssm": lambda: _ssm.init_params(cfg.ssm_dims(), d, dt, keys),
              "moe": lambda: _moe.dropless_init_params(cfg.moe_cfg(),
                                                       next(keys)),
              "attn": attn}
    layers = [{"norm": jnp.ones((d,), dt), KINDS[c]: mixers[KINDS[c]]()}
              for c in cfg.pattern]
    return {"embed": dense(d, (cfg.vocab_size, d)), "layers": layers,
            "final_norm": jnp.ones((d,), dt),
            "lm_head": dense(d, (d, cfg.vocab_size))}


# ------------------------------------------------------------------ forward
_rmsnorm = _blocks.rmsnorm


def _mamba(x, p, cfg: NemotronHConfig):
    return _ssm.mamba2(x, p, cfg.ssm_dims(), scan=_ssm.by_state_groups(
        _ssm.chunked_ssd, SCAN_TOKEN_HEADS))


def _layer(p, x, cfg: NemotronHConfig):
    """``(x, held_counts or None)``: one mixer behind its pre-norm
    residual."""
    h = _rmsnorm(x, p["norm"], cfg.norm_eps)
    if "moe" in p:
        B, T, D = x.shape
        y, counts = _moe.dropless_moe_ffn(h.reshape(B * T, D), p["moe"],
                                          cfg.moe_cfg())
        return x + y.reshape(B, T, D), counts
    return x + (_mamba(h, p["ssm"], cfg) if "ssm" in p
                else _blocks.grouped_attention(h, p["attn"], cfg)), None


def _forward(params, tokens, cfg: NemotronHConfig):
    """``(logits float32 [B, T, V], held_counts [expert layers,
    experts_held])``."""
    x = params["embed"][tokens]
    # Each layer is recomputed in the backward pass (a Mamba layer's
    # projection at 8192 tokens is 304 MB, its input 67 MB).
    layer = jax.checkpoint(_layer, static_argnums=(2,))
    counts = []
    for p in params["layers"]:
        x, c = layer(p, x, cfg)
        if c is not None:
            counts.append(c)
    with jax.named_scope("head"):
        x = _rmsnorm(x, params["final_norm"], cfg.norm_eps)
        logits = jnp.einsum("btd,dv->btv", x, params["lm_head"],
                            preferred_element_type=jnp.float32)
    return logits, jnp.stack(counts)


def forward(params, tokens, cfg: NemotronHConfig):
    """Logits ``[B, T, vocab]`` in float32."""
    return _forward(params, tokens, cfg)[0]


def expert_load(params, tokens, cfg: NemotronHConfig):
    """Assignments that land on each held expert, ``[expert layers,
    experts_held]`` int32, for a batch of tokens: the counter the benchmark
    reads in set-up.  ``tokens.size * top_k`` assignments are made in each
    expert layer."""
    return _forward(params, tokens, cfg)[1]


def decay_stats(params, tokens, cfg: NemotronHConfig):
    """``(share, least, most)``, each ``[Mamba layers]`` float32: the share
    of (chunk, head) pairs of a batch whose decay across the whole chunk,
    ``exp(sum log a)``, exceeds 0.01 (state that a chunk hands on), and the
    smallest and largest per-token ``a``, by layer.  A counter for set-up,
    not for a step: the layers run forward once more."""
    x = params["embed"][tokens]
    dims, share, least, most = cfg.ssm_dims(), [], [], []
    for p in params["layers"]:
        if "ssm" in p:
            dt = jnp.einsum(
                "btd,dh->bth", _rmsnorm(x, p["norm"], cfg.norm_eps),
                p["ssm"]["w_in"][:, -dims.heads:],
                preferred_element_type=jnp.float32)
            _, log_a = _ssm.step_and_decay(dt, p["ssm"])
            b, t, h = log_a.shape
            pad = (-t) % dims.chunk
            whole = jnp.pad(log_a, ((0, 0), (0, pad), (0, 0))).reshape(
                b, -1, dims.chunk, h).sum(axis=2)
            share.append(jnp.mean((whole > np.log(0.01)).astype(
                jnp.float32)))
            least.append(jnp.exp(jnp.min(log_a)))
            most.append(jnp.exp(jnp.max(log_a)))
        x = _layer(p, x, cfg)[0]
    return jnp.stack(share), jnp.stack(least), jnp.stack(most)


def loss_fn(params, tokens, targets, cfg: NemotronHConfig):
    """Mean next-token cross-entropy over this rank's tokens."""
    return _blocks.next_token_loss(forward(params, tokens, cfg), targets)


# --------------------------------------------------------------- train step
def make_train_step(cfg: NemotronHConfig, optimizer):
    """:func:`blocks.train_step` of this module's ``loss_fn``, looked up
    when the step runs."""
    return _blocks.train_step(
        lambda p, tokens, targets: loss_fn(p, tokens, targets, cfg),
        optimizer)
