"""The Mamba-1 mixer (Gu and Dao, "Mamba: Linear-Time Sequence Modeling with
Selective State Spaces"), told its sizes by the caller's config
(:class:`MambaDims`), never which model it serves.

``[x | z] = u W_in`` (no bias); a causal depthwise convolution with bias +
SiLU over ``x``; ``[dt_r | B | C] = x W_x`` (``dt_rank + 2 state``
columns); an RMSNorm with a learned weight on each of ``dt_r``, ``B`` and
``C`` (the Jamba family's addition to Mamba-1); ``delta = softplus(dt_r
W_dt + b_dt)``, a step of its own for every channel through a projection
of rank ``dt_rank``; ``A = -exp(A_log)``, ``[d_inner, state]``: a decay
for every (channel, state) pair; the selective scan
(``ops/selective_scan.py``: ``h_t = exp(delta_t A) h_{t-1} + delta_t B_t
x_t``, ``y_t = h_t C_t + D x_t``, float32 state); ``y <- y * SiLU(z)``;
``W_out``.

It has no heads and no groups, and ``exp(delta_t[c] A[c, s])`` is no scalar
a head: ``mamba2.chunked_ssd`` cannot compute it (``models/mamba2.py`` is
Mamba-2: one decay a head, ``B`` and ``C`` a group's, the recurrence as
chunked matrix products).

The parts carry ``jax.named_scope`` names a device trace shows, the same
four as ``mamba2``'s: ``ssm/proj`` (``W_in``, ``W_x``, the three norms,
``W_dt``), ``ssm/conv``, ``ssm/scan`` (the scan and the skip ``D x``),
``ssm/out`` (the gate and ``W_out``).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops.selective_scan import selective_scan
from .gated_delta import causal_conv_silu

# the range the published Mamba-1 initialisation draws the step from
STEP_MIN, STEP_MAX = 1e-3, 0.1


@dataclasses.dataclass(frozen=True)
class MambaDims:
    """What the mixer is told: the channels of the recurrence, its state's
    width, the rank of the step's projection, the convolution's taps and
    the inner norms' eps."""
    d_inner: int
    state: int = 16
    dt_rank: int = 160
    conv_kernel: int = 4
    norm_eps: float = 1e-6

    @property
    def x_width(self) -> int:
        return self.dt_rank + 2 * self.state


def init_params(dims: MambaDims, d_model, dtype, keys):
    """A layer's parameters; ``keys`` is an iterator of PRNG keys (six are
    taken).  The published Mamba-1 draw: ``A_log = log(1 .. state)`` for
    every channel, ``D = 1``, the step log-uniform in ``STEP_MIN ..
    STEP_MAX`` (``dt_bias`` its inverse softplus)."""
    di, n, r = dims.d_inner, dims.state, dims.dt_rank

    def dense(fan_in, shape):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                / np.sqrt(fan_in)).astype(dtype)

    step = jnp.exp(jax.random.uniform(
        next(keys), (di,), jnp.float32, np.log(STEP_MIN), np.log(STEP_MAX)))
    return {"w_in": dense(d_model, (d_model, 2 * di)),
            "conv": dense(dims.conv_kernel, (dims.conv_kernel, di)),
            "conv_bias": jnp.zeros((di,), dtype),
            "w_x": dense(di, (di, dims.x_width)),
            "w_dt": dense(r, (r, di)),
            "dt_bias": jnp.log(jnp.expm1(step)).astype(dtype),
            "A_log": jnp.broadcast_to(jnp.log(jnp.arange(
                1, n + 1, dtype=jnp.float32)), (di, n)).astype(dtype),
            "D": jnp.ones((di,), dtype),
            "w_out": dense(di, (di, d_model)),
            "dt_norm": jnp.ones((r,), dtype),
            "b_norm": jnp.ones((n,), dtype),
            "c_norm": jnp.ones((n,), dtype)}


def _rmsnorm(x, w, eps):
    """``x / rms(x) * w`` over the last axis; x is float32."""
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * w.astype(jnp.float32)


def step_and_projections(x, p, dims: MambaDims):
    """``(delta, B, C)`` from the convolution's output x ``[b, T,
    d_inner]``: ``delta`` float32 ``[b, T, d_inner]``, ``B`` and ``C``
    ``[b, T, state]`` in x's type."""
    f32, r, n = jnp.float32, dims.dt_rank, dims.state
    dbc = jnp.einsum("bte,ef->btf", x, p["w_x"], preferred_element_type=f32)
    dt_r, B, C = dbc[..., :r], dbc[..., r:r + n], dbc[..., r + n:]
    dt_r = _rmsnorm(dt_r, p["dt_norm"], dims.norm_eps)
    B = _rmsnorm(B, p["b_norm"], dims.norm_eps)
    C = _rmsnorm(C, p["c_norm"], dims.norm_eps)
    dt = jnp.einsum("btr,re->bte", dt_r.astype(x.dtype), p["w_dt"],
                    preferred_element_type=f32)
    delta = jax.nn.softplus(dt + p["dt_bias"].astype(f32))
    return delta, B.astype(x.dtype), C.astype(x.dtype)


def mamba(u, p, dims: MambaDims):
    """The mixer: u ``[b, T, d_model]`` -> ``[b, T, d_model]``."""
    di, f32 = dims.d_inner, jnp.float32
    with jax.named_scope("ssm/proj"):
        # a product a consumer: the convolution's kernels read ``x`` as an
        # array of its own, where a slice of ``[x|z]`` would be copied
        x = u @ p["w_in"][:, :di]
        z = u @ p["w_in"][:, di:]
    with jax.named_scope("ssm/conv"):
        x = causal_conv_silu(x, p["conv"], p["conv_bias"])
    with jax.named_scope("ssm/proj"):
        delta, B, C = step_and_projections(x, p, dims)
    with jax.named_scope("ssm/scan"):
        y = selective_scan(x, delta, -jnp.exp(p["A_log"].astype(f32)), B, C,
                           p["D"])
    with jax.named_scope("ssm/out"):
        y = (y.astype(f32) * jax.nn.silu(z.astype(f32))).astype(u.dtype)
        return y @ p["w_out"]
