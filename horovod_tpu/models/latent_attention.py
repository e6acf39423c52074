"""Multi-head latent attention (DeepSeek-V2/V3's), as a training block: one
function, told its sizes by the caller's config (:class:`LatentDims`),
never which model it serves (``models/gated_delta.py``'s arrangement).

For the normed input ``h [B, T, d_model]``:

- queries through a bottleneck with a norm of its own: ``c_q =
  RMSNorm(h W_qa; q_norm)`` (``d_model -> q_rank``), ``q = c_q W_qb``
  (``q_rank -> heads x (d_rope + d_nope)``);
- keys and values from one latent: ``[c_kv | k_r] = h W_kva`` (``d_model ->
  kv_rank + d_rope``), ``c_kv = RMSNorm(c_kv; kv_norm)``, ``[k_nope | v] =
  c_kv W_kvb`` (``kv_rank -> heads x (d_nope + d_v)``);
- the rotary turns every head's ``q_rope`` and the **one** ``k_r`` that all
  heads share, plain frequencies ``theta ** (-2j / d_rope)``;
- head ``n``'s score of query ``t`` on key ``j <= t`` is ``(q_nope[t, n] .
  k_nope[j, n] + q_rope[t, n] . k_r[j]) / sqrt(d_nope + d_rope)``, softmax
  over ``j``, ``o[t, n] = sum_j p v[j, n]`` (``d_v`` wide), then ``W_o``
  (``heads x d_v -> d_model``).

A head here is ``[rope | nope]``, the rotary part first and its pairs
``(i, i + d_rope / 2)``: ``blocks.rotary`` turns the leading numbers of a
head in that pairing, so one call assembles ``q``.  The published layout is
``[nope | rope]`` with the pairs ``(2j, 2j + 1)`` (``rope_interleave``);
the scores are the same under a permutation of a head's columns that ``q``
and ``k`` share, and :func:`from_published` is that permutation of
``W_qb``'s and ``W_kva``'s columns.

``k_r`` is laid beside ``k_nope`` for every head (a broadcast: at 16 k
tokens and 32 heads 67 MB a layer), and the kernels of
``ops/flash_attention`` take keys of ``d_rope + d_nope`` and values of
``d_v``: ``v`` is never padded to the keys' width.  This is the form
training wants (every head's keys are needed for the backward pass anyway);
the absorbed form, in which ``W_kvb`` is folded into ``q`` and ``o`` and
the cache holds ``c_kv`` and ``k_r`` alone, is serving's and is not here.

Scopes: the caller names the block (``attn/latent``); the two low-rank
paths up to the assembled ``q``, ``k`` and ``v`` are under ``proj`` beneath
it.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from . import blocks as _blocks
from ..parallel.ring_attention import local_flash_attention


@dataclasses.dataclass(frozen=True)
class LatentDims:
    """What the block is told: the model's width, the heads, the two ranks,
    a head's three widths (the keys' part without position, the rotary
    part, a value), the rotary's theta and the inner norms' eps."""
    d_model: int
    n_heads: int
    q_rank: int
    kv_rank: int
    d_nope: int
    d_rope: int
    d_v: int
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6

    @property
    def d_qk(self) -> int:
        return self.d_nope + self.d_rope

    @property
    def rotary(self) -> _blocks.Rotary:
        return _blocks.Rotary(width=self.d_rope, theta=self.rope_theta)


def init_params(dims: LatentDims, dtype, keys):
    """A block's parameters; ``keys`` is an iterator of PRNG keys (five are
    taken)."""
    d, h = dims.d_model, dims.n_heads

    def dense(fan_in, shape):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                / np.sqrt(fan_in)).astype(dtype)

    return {"wq_a": dense(d, (d, dims.q_rank)),
            "q_norm": jnp.ones((dims.q_rank,), dtype),
            "wq_b": dense(dims.q_rank, (dims.q_rank, h * dims.d_qk)),
            "wkv_a": dense(d, (d, dims.kv_rank + dims.d_rope)),
            "kv_norm": jnp.ones((dims.kv_rank,), dtype),
            "wkv_b": dense(dims.kv_rank,
                           (dims.kv_rank, h * (dims.d_nope + dims.d_v))),
            "wo": dense(h * dims.d_v, (h * dims.d_v, d))}


def from_published(p, dims: LatentDims):
    """A block's parameters in the published column order (a head of
    ``W_qb`` ``[nope | rope]``, the rotary's pairs ``(2j, 2j + 1)`` there
    and in ``W_kva``'s last ``d_rope`` columns) as this module holds them
    (``[rope | nope]``, pairs ``(i, i + d_rope / 2)``)."""
    halves = np.r_[np.arange(0, dims.d_rope, 2), np.arange(1, dims.d_rope, 2)]
    head = np.r_[dims.d_nope + halves, np.arange(dims.d_nope)]
    q_cols = (np.arange(dims.n_heads)[:, None] * dims.d_qk + head).reshape(-1)
    kv_cols = np.r_[np.arange(dims.kv_rank), dims.kv_rank + halves]
    return {**p, "wq_b": p["wq_b"][:, q_cols], "wkv_a": p["wkv_a"][:, kv_cols]}


def qkv(p, h, dims: LatentDims):
    """``(q [B, T, heads, d_qk], k [B, T, heads, d_qk], v [B, T, heads,
    d_v])`` of the normed input ``h``, rotary applied."""
    B, T, _ = h.shape
    H, rot = dims.n_heads, dims.rotary
    c_q = _blocks.rmsnorm(h @ p["wq_a"], p["q_norm"], dims.norm_eps)
    q = _blocks.rotary((c_q @ p["wq_b"]).reshape(B, T, H, dims.d_qk), rot)
    kv_a = h @ p["wkv_a"]
    c_kv = _blocks.rmsnorm(kv_a[..., :dims.kv_rank], p["kv_norm"],
                           dims.norm_eps)
    k_r = _blocks.rotary(kv_a[..., dims.kv_rank:].reshape(
        B, T, 1, dims.d_rope), rot)
    kv = (c_kv @ p["wkv_b"]).reshape(B, T, H, dims.d_nope + dims.d_v)
    k = jnp.concatenate([jnp.broadcast_to(k_r, (B, T, H, dims.d_rope)),
                         kv[..., :dims.d_nope]], axis=-1)
    return q, k, kv[..., dims.d_nope:]


def latent_attention(p, h, dims: LatentDims, flash: bool):
    """``Attn(h) [B, T, d_model]`` of the normed input ``h``, causal;
    ``flash`` takes the Pallas kernels (``ops/flash_attention``), else
    XLA's own code."""
    from ..ops.flash_attention import flash_attention
    B, T, _ = h.shape
    with jax.named_scope("proj"):
        q, k, v = qkv(p, h, dims)
    attend = flash_attention if flash else local_flash_attention
    o = attend(q, k, v, causal=True)       # the scale is d_qk ** -0.5
    return o.reshape(B, T, dims.n_heads * dims.d_v) @ p["wo"]
