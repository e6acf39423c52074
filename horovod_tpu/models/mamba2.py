"""The Mamba-2 mixer (Dao and Gu, "Transformers are SSMs"): a selective
state-space layer with one scalar decay a head, told its sizes by the
caller's config (:class:`Mamba2Dims`), never which model it serves.

``[z | xBC | dt] = u W_in``; a causal depthwise convolution with bias +
SiLU over ``xBC``; ``x [T, H, P]``, ``B, C [T, G, N]`` (head ``h`` reads
group ``h // (H / G)``); ``delta = softplus(dt + dt_bias)``, ``a =
exp(-delta * exp(A_log))``; per head ``S_t = a_t S_{t-1} + delta_t x_t
B_t^T`` (``P x N``, float32), ``y_t = S_t C_t + D x_t``; ``y <-
RMSNorm_groups(y * SiLU(z)) * w`` with the mean square over each of the
``G`` groups of ``H P / G`` channels (the gate first, then the norm);
``W_out``.

The recurrence is computed in its **chunked** form (state-space duality,
:func:`chunked_ssd`): inside a chunk ``(L * C B^T) (delta x)`` with
``L_ts = exp(sum_{s<r<=t} log a_r)``, between chunks a scan over the chunk
states.  It has no delta-rule solve (``gated_delta.chunked_gated_delta_rule``
has one) and its keys and queries are a group's, not a head's.

The parts carry ``jax.named_scope`` names a device trace shows:
``ssm/proj``, ``ssm/conv``, ``ssm/scan``, ``ssm/out``.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .gated_delta import causal_conv_silu


@dataclasses.dataclass(frozen=True)
class Mamba2Dims:
    """What the mixer is told: ``heads`` of ``head_dim`` (``heads`` a
    multiple of ``groups``), the ``groups`` that share ``B`` and ``C`` (and
    the output norm's mean square), the state's width, the convolution's
    taps, the chunk, the output norm's eps and the range the published
    initialisation draws the step from."""
    heads: int
    head_dim: int
    groups: int
    state: int
    conv_kernel: int = 4
    chunk: int = 128
    norm_eps: float = 1e-5
    step_min: float = 1e-3
    step_max: float = 0.1
    step_floor: float = 1e-4

    @property
    def d_inner(self) -> int:
        return self.heads * self.head_dim

    @property
    def conv_width(self) -> int:
        return self.d_inner + 2 * self.groups * self.state


def init_params(dims: Mamba2Dims, d_model, dtype, keys):
    """A layer's parameters; ``keys`` is an iterator of PRNG keys (five are
    taken).  The published draw: ``A`` uniform in (1, 16), the step
    log-uniform in ``step_min .. step_max`` floored at ``step_floor``
    (``dt_bias`` its inverse softplus), ``D = 1``."""
    h, width = dims.heads, dims.conv_width

    def dense(fan_in, shape):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                / np.sqrt(fan_in)).astype(dtype)

    a = jax.random.uniform(next(keys), (h,), jnp.float32, 1.0, 16.0)
    step = jnp.maximum(jnp.exp(jax.random.uniform(
        next(keys), (h,), jnp.float32, np.log(dims.step_min),
        np.log(dims.step_max))), dims.step_floor)
    return {"w_in": dense(d_model, (d_model, dims.d_inner + width + h)),
            "conv": dense(dims.conv_kernel, (dims.conv_kernel, width)),
            "conv_bias": jnp.zeros((width,), dtype),
            "A_log": jnp.log(a).astype(dtype),
            "D": jnp.ones((h,), dtype),
            "dt_bias": jnp.log(jnp.expm1(step)).astype(dtype),
            "norm": jnp.ones((dims.d_inner,), dtype),
            "w_out": dense(dims.d_inner, (dims.d_inner, d_model))}


def chunked_ssd(x, delta, log_a, B, C, chunk=128):
    """``S_t = a_t S_{t-1} + delta_t x_t B_t^T``, ``y_t = S_t C_t`` with
    ``S_0 = 0``, a chunk of tokens at a time.

    x ``[b, T, H, P]``, delta and log_a (``log a <= 0``) ``[b, T, H]``
    float32, B and C ``[b, T, G, N]`` (``H`` a multiple of ``G``; head ``h``
    reads group ``h // (H / G)``) -> y ``[b, T, H, P]`` in x's type.  Inside
    a chunk of ``Q`` tokens the outputs are ``(L * C B^T) (delta x)``, all
    chunks at once, ``C B^T`` once a group; each chunk's own state is
    ``sum_s exp(A_Q - A_s) delta_s x_s B_s^T`` (``A`` the running sum of
    ``log a`` inside the chunk); a ``lax.scan`` carries the ``P x N`` state
    over the chunks, and ``C_t S exp(A_t)`` adds what the chunks before
    give.  The sums of ``log a``, the decays and the state are float32; the
    matrix products take their operands in x's type and accumulate in
    float32; only differences ``<= 0`` are exponentiated.  Plain JAX
    operations: the backward pass is autodiff's.  ``T`` need not be a
    multiple of ``chunk``."""
    b, T, H, P = x.shape
    G, N = B.shape[2:]
    R, Q, dt, f32 = H // G, chunk, x.dtype, jnp.float32
    pad = (-T) % Q
    n = (T + pad) // Q

    def chunks(v):          # [b, T, ...] -> [b, n, Q, ...]
        v = jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
        return v.reshape((b, n, Q) + v.shape[2:])

    # padding: delta = 0 writes nothing, log a = 0 decays nothing
    x, delta, log_a, B, C = (chunks(v) for v in (x, delta, log_a, B, C))
    mm = lambda spec, p, q: jnp.einsum(spec, p.astype(dt), q.astype(dt),
                                       preferred_element_type=f32)
    grouped = lambda v: v.reshape(v.shape[:3] + (G, R) + v.shape[4:])
    A = jnp.cumsum(log_a.astype(f32), axis=2)               # [b,n,Q,H]
    total = A[:, :, -1]                                     # [b,n,H]
    xd = grouped(x.astype(f32) * delta[..., None])          # [b,n,Q,G,R,P]
    # ---- inside a chunk: heads lead, the chunk's (t, s) are the minor axes
    lower = jnp.tril(jnp.ones((Q, Q), bool))
    Ah = jnp.moveaxis(A, 2, 3).reshape(b, n, G, R, Q)
    L = jnp.where(lower, jnp.exp(jnp.where(
        lower, Ah[..., :, None] - Ah[..., None, :], 0.0)), 0.0)
    scores = mm("bntgk,bnsgk->bngts", C, B)[:, :, :, None] * L
    y = mm("bngrts,bnsgrp->bntgrp", scores, xd)
    # ---- each chunk's own state, and the scan over them
    tail = jnp.exp(total[:, :, None] - A)                   # [b,n,Q,H]
    own = mm("bnsgk,bnsgrp->bngrpk", B, xd * grouped(tail)[..., None])
    decay = jnp.exp(total).reshape(b, n, G, R)

    def carry(S, of_chunk):     # S [b,G,R,P,N]: what a chunk starts from
        own_n, decay_n = of_chunk
        return S * decay_n[..., None, None] + own_n, S

    _, S = lax.scan(carry, jnp.zeros((b, G, R, P, N), f32),
                    (jnp.moveaxis(own, 1, 0), jnp.moveaxis(decay, 1, 0)))
    y = y + mm("bntgk,nbgrpk->bntgrp", C, S) * grouped(jnp.exp(A))[..., None]
    return y.reshape(b, n * Q, H, P)[:, :T].astype(dt)


def by_state_groups(scan, token_heads):
    """``scan`` (:func:`chunked_ssd`'s signature) run some of the ``G``
    groups at a time: as many whole groups as keep ``b * T * heads`` (token,
    head) pairs within ``token_heads``, and at least one; all at once where
    they fit.  Heads are independent and a group's ``B`` and ``C`` go with
    its heads, so the result is ``scan``'s own.  A ``lax.map`` over the
    parts, each recomputed in the backward pass: the float32 working set of
    the chunk algebra (``L``, the scores, every chunk's state, and their
    cotangents) is then a part's (``gated_delta.by_head_groups``'
    reason)."""
    def grouped(x, delta, log_a, B, C, chunk):
        b, T, H, P = x.shape
        G = B.shape[2]
        fit = max(1, token_heads // (b * T * (H // G)))
        at_once = max(g for g in range(1, G + 1) if G % g == 0 and g <= fit)
        if at_once == G:
            return scan(x, delta, log_a, B, C, chunk)
        parts = G // at_once

        def split(v):       # [b, T, H or G, ...] -> [parts, b, T, ., ...]
            v = v.reshape((b, T, parts, v.shape[2] // parts) + v.shape[3:])
            return jnp.moveaxis(v, 2, 0)

        one = jax.checkpoint(lambda *a: scan(*a, chunk))
        y = lax.map(lambda a: one(*a),
                    tuple(split(v) for v in (x, delta, log_a, B, C)))
        return jnp.moveaxis(y, 0, 2).reshape(b, T, H, P)
    return grouped


def step_and_decay(dt, p):
    """``(delta, log a)`` float32 ``[b, T, H]`` from the ``dt`` columns of
    the projection (float32) and the layer's ``dt_bias`` and ``A_log``."""
    f32 = jnp.float32
    delta = jax.nn.softplus(dt + p["dt_bias"].astype(f32))
    return delta, -delta * jnp.exp(p["A_log"].astype(f32))


def gated_group_norm(y, z, w, groups, eps):
    """``RMSNorm(y * SiLU(z)) * w`` with the mean square taken over each of
    ``groups`` equal runs of the last axis; float32 inside."""
    f32 = jnp.float32
    g = y.astype(f32) * jax.nn.silu(z.astype(f32))
    parts = g.reshape(g.shape[:-1] + (groups, -1))
    var = jnp.mean(jnp.square(parts), axis=-1, keepdims=True)
    g = (parts * lax.rsqrt(var + eps)).reshape(g.shape)
    return (g * w.astype(f32)).astype(y.dtype)


def mamba2(u, p, dims: Mamba2Dims, scan=None):
    """The mixer: u ``[b, T, d_model]`` -> ``[b, T, d_model]``.  ``scan`` is
    the recurrence's implementation, :func:`chunked_ssd` by default (the
    same signature: a caller may hand in its own)."""
    scan = scan or chunked_ssd
    b, T, _ = u.shape
    H, P, G, N = dims.heads, dims.head_dim, dims.groups, dims.state
    d_inner, f32 = dims.d_inner, jnp.float32
    with jax.named_scope("ssm/proj"):
        # a product a consumer: the convolution's kernels read ``xBC`` as
        # an array of its own, where a slice of ``[z|xBC]`` would be copied
        z = u @ p["w_in"][:, :d_inner]
        xBC = u @ p["w_in"][:, d_inner:d_inner + dims.conv_width]
        dt = jnp.einsum("btd,dh->bth", u, p["w_in"][:, -H:],
                        preferred_element_type=f32)
    with jax.named_scope("ssm/conv"):
        xBC = causal_conv_silu(xBC, p["conv"], p["conv_bias"])
    with jax.named_scope("ssm/scan"):
        x = xBC[..., :d_inner].reshape(b, T, H, P)
        B = xBC[..., d_inner:d_inner + G * N].reshape(b, T, G, N)
        C = xBC[..., d_inner + G * N:].reshape(b, T, G, N)
        delta, log_a = step_and_decay(dt, p)
        y = scan(x, delta, log_a, B, C, dims.chunk)
        y = (y.astype(f32) + p["D"].astype(f32)[:, None] * x.astype(f32)
             ).astype(u.dtype)
    with jax.named_scope("ssm/out"):
        y = gated_group_norm(y.reshape(b, T, d_inner), z, p["norm"], G,
                             dims.norm_eps)
        return y @ p["w_out"]
