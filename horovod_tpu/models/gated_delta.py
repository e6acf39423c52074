"""The Gated DeltaNet mixer (Yang et al., "Gated Delta Networks"), shared
by the model families that have linear-attention layers of this kind
(``qwen3_next``, ``olmo_hybrid``): one function, told its sizes by the
caller's config (:class:`GatedDeltaDims`), never which model it serves.

``[q|k|v|z] = x W_qkvz``, ``[b|a] = x W_ba``; a causal depthwise
convolution + SiLU over ``[q|k|v]``; q and k repeated to the value heads
and L2-normalised a head, q times ``k_dim ** -0.5``; ``beta = beta_scale *
sigmoid(b)``, ``g = -exp(A_log) * softplus(a + dt_bias)``; per head ``S <-
exp(g_t) S + k_t (beta_t (v_t - S^T k_t))^T``, ``o_t = S^T q_t`` with ``S``
``k_dim x v_dim``, computed in the **chunked** form
(:func:`chunked_gated_delta_rule`, or on a TPU the kernel pair of
``ops/delta_rule.py``, at widths rounded up to whole lanes); ``o <-
RMSNorm(o; w_out[v_dim]) * SiLU(z)``; ``W_o``.

``beta_scale`` 1 keeps beta in (0, 1): ``I - beta k k^T`` then only shrinks
a state component along k.  ``beta_scale`` 2 (the published
``allow_neg_eigval``) lets beta reach 2, where that factor has the
eigenvalue -1 and a component can change sign.

The parts carry ``jax.named_scope`` names a device trace shows:
``gdn/proj``, ``gdn/conv``, ``gdn/scan``, ``gdn/out``.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from .. import trace
from ..ops import causal_conv, delta_rule

# the checkpoint name of a grouped delta rule's result (by_head_groups)
RULE_OUTPUT = "gdn_rule_out"
# added to a head's squared length before the root, so that a zero vector
# normalises to zero (the published layers' l2norm has the same term)
L2_NORM_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class GatedDeltaDims:
    """What the mixer is told: head counts (``v_heads`` a multiple of
    ``k_heads``), a key's and a value's width (they may differ and need be
    no multiple of anything), the convolution's taps, the chunk of the
    delta rule, the output norm's eps and the scale of beta."""
    k_heads: int
    v_heads: int
    k_dim: int
    v_dim: int
    conv_kernel: int = 4
    chunk: int = 64
    norm_eps: float = 1e-6
    beta_scale: float = 1.0

    @property
    def qkv_width(self) -> int:
        return 2 * self.k_heads * self.k_dim + self.v_heads * self.v_dim


def init_params(dims: GatedDeltaDims, d_model, dtype, keys):
    """A layer's parameters; ``keys`` is an iterator of PRNG keys (five are
    taken).  HF's draw: A uniform in (0, 16), dt log-uniform in (1e-3,
    0.1)."""
    hv, dv = dims.v_heads, dims.v_dim

    def dense(fan_in, shape):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                / np.sqrt(fan_in)).astype(dtype)

    a = jax.random.uniform(next(keys), (hv,), jnp.float32, 1e-3, 16.0)
    step = jnp.exp(jax.random.uniform(
        next(keys), (hv,), jnp.float32, np.log(1e-3), np.log(0.1)))
    return {"w_qkvz": dense(d_model, (d_model, dims.qkv_width + hv * dv)),
            "w_ba": dense(d_model, (d_model, 2 * hv)),
            "conv": dense(dims.conv_kernel,
                          (dims.conv_kernel, dims.qkv_width)),
            "A_log": jnp.log(a).astype(dtype),
            "dt_bias": jnp.log(jnp.expm1(step)).astype(dtype),
            "out_norm": jnp.ones((dv,), dtype),
            "wo": dense(hv * dv, (hv * dv, d_model))}


def chunked_gated_delta_rule(q, k, v, g, beta, chunk=64):
    """The gated delta rule ``S <- exp(g_t) S + k_t (beta_t (v_t - S^T
    k_t))^T``, ``o_t = S^T q_t`` with ``S_0 = 0``, a chunk of tokens at a
    time (Yang et al., "Gated Delta Networks").

    q, k ``[B, T, H, dk]``, v ``[B, T, H, dv]`` (``dk`` and ``dv`` may
    differ), g and beta ``[B, T, H]`` float32 -> o ``[B, T, H, dv]`` in v's
    type.  What the inputs have to satisfy: k of unit length (or zero), g
    (the log decay) ``<= 0``, and beta in ``[0, 2]`` — the range over which
    every step's factor ``I - beta k k^T`` has eigenvalues in ``[-1, 1]``
    and the state stays bounded; the tests hold this form to the
    token-by-token recurrence with beta drawn over (0, 2) and with every
    beta at 1.999.  Within a chunk the ``C`` rank-one updates are one
    unit-lower-triangular solve ``(I + tril(diag(beta) K K^T * D, -1)) [U |
    W] = diag(beta) [V | K * exp(G)]`` (``D_ij = exp(G_i - G_j)``, ``G`` the
    running sum of g inside the chunk), all chunks at once; a ``lax.scan``
    then carries the ``dk x dv`` state over the chunks, and the outputs
    follow from the states, again all chunks at once.  g, its sums, the
    solve and the state are float32; the matrix products take their
    operands in the inputs' type and accumulate in float32.  Plain JAX
    operations: the backward pass is autodiff's.  ``T`` need not be a
    multiple of ``chunk``.

    This is the **plain branch** of the rule: :func:`gated_delta_net` takes
    it wherever the kernel pair of ``ops/delta_rule.py`` does not engage —
    any backend but a TPU (the CPU tests and rehearsals), and on a TPU a
    key or value width under 32 (96 and 192, say, run in the kernels at
    128 and 256 on zero-padded heads) or a chunk its tiles do not take —
    and it is the yardstick the kernels' tests hold them to.  Every float32
    intermediate here is an array of all chunks at once that goes to HBM
    and comes back; the kernels keep a chunk's in VMEM."""
    B, T, H, dk = q.shape
    dv, dt, C = v.shape[-1], v.dtype, chunk
    pad = (-T) % C
    N = (T + pad) // C

    def chunks(x):          # [B, T, H, ...] -> [B, H, N, C, ...]
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape((B, N, C) + x.shape[2:])
        return jnp.moveaxis(x, 3, 1)

    # padding: k = 0 and beta = 0 write nothing, g = 0 decays nothing
    q, k, v, g, beta = (chunks(x) for x in (q, k, v, g, beta))
    f32 = jnp.float32
    mm = lambda spec, a, b: jnp.einsum(spec, a.astype(dt), b.astype(dt),
                                       preferred_element_type=f32)
    G = jnp.cumsum(g.astype(f32), axis=-1)                  # [B,H,N,C]
    lower = jnp.tril(jnp.ones((C, C), bool))
    # exp only of differences that are <= 0
    decay = jnp.where(lower, jnp.exp(jnp.where(
        lower, G[..., :, None] - G[..., None, :], 0.0)), 0.0)
    k_beta = k.astype(f32) * beta[..., None]
    A = jnp.where(jnp.tril(jnp.ones((C, C), bool), -1),
                  mm("bhnik,bhnjk->bhnij", k_beta, k) * decay, 0.0)
    rhs = jnp.concatenate([v.astype(f32) * beta[..., None],
                           k_beta * jnp.exp(G)[..., None]], axis=-1)
    solved = jax.scipy.linalg.solve_triangular(
        A + jnp.eye(C, dtype=f32), rhs, lower=True, unit_diagonal=True)
    U, W = solved[..., :dv], solved[..., dv:]               # [B,H,N,C,·]
    total = G[..., -1]                                      # [B,H,N]
    k_tail = k.astype(f32) * jnp.exp(total[..., None] - G)[..., None]

    def carry(S, x):        # S [B,H,dk,dv]: the state a chunk starts from
        U_n, W_n, k_n, decay_n = x
        v_new = U_n - mm("bhck,bhkv->bhcv", W_n, S)
        S_next = S * decay_n[..., None, None] + mm("bhck,bhcv->bhkv",
                                                   k_n, v_new)
        return S_next, (S, v_new)

    time_first = lambda x: jnp.moveaxis(x, 2, 0)
    _, (S, v_new) = lax.scan(
        carry, jnp.zeros((B, H, dk, dv), f32),
        tuple(time_first(x) for x in (U, W, k_tail, jnp.exp(total))))
    S, v_new = jnp.moveaxis(S, 0, 2), jnp.moveaxis(v_new, 0, 2)
    o = mm("bhnck,bhnkv->bhncv", q.astype(f32) * jnp.exp(G)[..., None], S)
    o = o + mm("bhnij,bhnjv->bhniv",
               mm("bhnik,bhnjk->bhnij", q, k) * decay, v_new)
    o = jnp.moveaxis(o, 1, 3).reshape(B, N * C, H, dv)[:, :T]
    return o.astype(dt)


def by_head_groups(rule, token_heads):
    """``rule`` run a group of heads at a time: the largest group that
    divides the head count and keeps ``B * T * group`` (token, head) pairs
    within ``token_heads``; all heads at once where they fit.  Heads are
    independent, so the result is ``rule``'s own.  A ``lax.map`` over the
    groups, each group recomputed in the backward pass: the float32 working
    set of the chunk algebra — the solve's two sides, every chunk's state,
    and their cotangents — is then a group's instead of all heads' (at 16 k
    tokens and 30 heads of 96 x 192: 7.4 GB of a step's temporaries in
    place of 12.9).  The grouped result carries the checkpoint name
    :data:`RULE_OUTPUT`: a caller that recomputes the whole mixer in the
    backward pass saves it by that name, and the rule then runs forward
    twice a step (once to recompute a group for its backward pass), as it
    does ungrouped, not three times.

    Part of the **plain branch**: the split exists because
    :func:`chunked_gated_delta_rule`'s working set is all chunks' at once.
    Where :func:`gated_delta_net` takes the kernel pair — on a TPU, the
    shape this was written for included — the working set is a chunk's,
    and the ``rule`` a caller built with this is not called."""
    def grouped(q, k, v, g, beta, chunk):
        B, T, H = q.shape[:3]
        fit = max(1, token_heads // (B * T))
        heads = max(h for h in range(1, H + 1) if H % h == 0 and h <= fit)
        if heads == H:
            return rule(q, k, v, g, beta, chunk)

        def split(x):       # [B, T, H, ...] -> [H / heads, B, T, heads, ...]
            x = x.reshape((B, T, H // heads, heads) + x.shape[3:])
            return jnp.moveaxis(x, 2, 0)

        one = jax.checkpoint(lambda *a: rule(*a, chunk))
        o = lax.map(lambda a: one(*a),
                    tuple(split(x) for x in (q, k, v, g, beta)))
        o = jnp.moveaxis(o, 0, 2)
        return checkpoint_name(o.reshape((B, T, H) + o.shape[4:]),
                               RULE_OUTPUT)
    return grouped


def causal_conv_silu(x, kernel, bias=None):
    """``SiLU(conv(x) + bias)`` for x ``[B, T, channels]`` and a causal
    depthwise ``kernel [taps, channels]``: tap ``j`` weighs the input
    ``taps - 1 - j`` back.  Float32 inside, x's type out.  The recurrent
    mixers' (this one's, without a bias, and ``mamba2``'s).

    One algorithm, two implementations chosen at trace time from what can
    be observed: on a TPU, where the shape fits its tiles
    (``ops/causal_conv.py`` ``tiles``), the kernel pair that reads x once
    a direction; everywhere else the plain formulation below.
    ``trace.causal_conv`` counts the call sites of each."""
    taps, T = kernel.shape[0], x.shape[1]
    if causal_conv.kernel_enabled() and causal_conv.tiles(x.shape, taps,
                                                          x.dtype):
        trace.causal_conv["kernel"] += 1        # Python: once a trace
        return causal_conv.causal_conv_silu(x, kernel, bias)
    trace.causal_conv["plain"] += 1
    f32 = jnp.float32
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0))).astype(f32)
    conv = kernel.astype(f32)
    y = sum(conv[j] * padded[:, j:j + T] for j in range(taps))
    if bias is not None:
        y = y + bias.astype(f32)
    return jax.nn.silu(y).astype(x.dtype)


def gate_inputs(ba, p, dims: GatedDeltaDims):
    """``(beta, g)`` float32 ``[B, T, v_heads]`` from the ``[b|a]``
    projection (float32) and the layer's ``A_log`` and ``dt_bias``."""
    hv, f32 = dims.v_heads, jnp.float32
    beta = jax.nn.sigmoid(ba[..., :hv])
    if dims.beta_scale != 1.0:
        beta = dims.beta_scale * beta
    g = -jnp.exp(p["A_log"].astype(f32)) * jax.nn.softplus(
        ba[..., hv:] + p["dt_bias"].astype(f32))
    return beta, g


def gated_delta_net(x, p, dims: GatedDeltaDims, rule=None):
    """The mixer: x ``[B, T, d_model]`` -> ``[B, T, d_model]``.  The delta
    rule is one algorithm with two implementations, chosen here at trace
    time from what can be observed: on a TPU, where the shape fits
    (``ops/delta_rule.py`` ``tiles``: key and value widths of 32 or more,
    run at ``widths``' whole lanes on zero-padded heads — 96 x 192 at 128
    x 256), the Pallas kernel pair, which holds a chunk's algebra in VMEM
    and needs no split by heads; everywhere else ``rule``, the plain
    branch (:func:`chunked_gated_delta_rule` by default; a caller whose
    shape wants it hands in :func:`by_head_groups` of it).
    ``trace.delta_rule`` counts the call sites of each, and of the
    kernel's those that run at padded widths."""
    rule = rule or chunked_gated_delta_rule
    B, T, _ = x.shape
    hk, hv, dk, dv = dims.k_heads, dims.v_heads, dims.k_dim, dims.v_dim
    f32 = jnp.float32
    with jax.named_scope("gdn/proj"):
        # a product a consumer: the convolution's kernels read ``qkv`` as
        # an array of its own, where a slice of ``[q|k|v|z]`` would be copied
        qkv = x @ p["w_qkvz"][:, :dims.qkv_width]
        z = x @ p["w_qkvz"][:, -hv * dv:]
        ba = jnp.einsum("btd,de->bte", x, p["w_ba"],
                        preferred_element_type=f32)
    with jax.named_scope("gdn/conv"):
        qkv = causal_conv_silu(qkv, p["conv"])
    with jax.named_scope("gdn/scan"):
        # one algorithm, two implementations chosen at trace time from the
        # backend and the shape: the kernel pair reads q and k at the key
        # heads, the plain branch a copy a value head
        kernel = delta_rule.kernel_enabled() and delta_rule.tiles(
            (B, T, hk, dk), (B, T, hv, dv), dims.chunk, x.dtype)
        trace.delta_rule["kernel" if kernel else "plain"] += 1   # a trace
        if kernel and delta_rule.widths(dk, dv) != (dk, dv):
            trace.delta_rule["padded"] += 1

        def heads(y, n, dim, repeat=1):
            y = y.reshape(B, T, n, dim).astype(f32)
            y = y * lax.rsqrt(jnp.sum(jnp.square(y), axis=-1,
                                      keepdims=True) + L2_NORM_EPS)
            return jnp.repeat(y, repeat, axis=2)

        copies = 1 if kernel else hv // hk
        q = (heads(qkv[..., :hk * dk], hk, dk, copies)
             / np.sqrt(dk)).astype(x.dtype)
        k = heads(qkv[..., hk * dk:2 * hk * dk], hk, dk,
                  copies).astype(x.dtype)
        v = qkv[..., 2 * hk * dk:].reshape(B, T, hv, dv)
        beta, g = gate_inputs(ba, p, dims)
        o = (delta_rule.gated_delta_rule if kernel else rule)(
            q, k, v, g, beta, dims.chunk)
    with jax.named_scope("gdn/out"):
        of = o.astype(f32)
        var = jnp.mean(jnp.square(of), axis=-1, keepdims=True)
        o = (p["out_norm"].astype(f32) * (of * lax.rsqrt(var + dims.norm_eps))
             * jax.nn.silu(z.reshape(B, T, hv, dv).astype(f32)))
        return o.astype(x.dtype).reshape(B, T, hv * dv) @ p["wo"]
