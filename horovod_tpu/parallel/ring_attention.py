"""Ring attention: exact attention over sequence shards via ICI neighbor
exchange.

No reference analogue (Horovod predates sequence parallelism — SURVEY.md §5
explicitly: "ABSENT in the reference"); built on the same primitive class the
reference exposes (point-to-point ring = ``lax.ppermute`` over ICI, the
substrate XLA already provides on the torus).  Algorithm: blockwise/flash
attention with an online-softmax accumulator; K/V blocks rotate around the
``sp`` ring, so each rank sees every block once, overlapping compute with the
neighbor transfer.  Memory per chip stays O(T/sp · T/sp) and the full
sequence is never materialized — the long-context workhorse.

Use inside ``shard_map`` with the sequence dimension sharded over ``sp``:

    out = ring_attention(q, k, v, axis_name="sp", causal=True)

Shapes: q, k, v are the local shards ``[batch, seq_local, heads, head_dim]``.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from ..compat import axis_size as compat_axis_size

NEG_INF = -1e30


def _block_attn(q, k, v, bias, scale):
    """One q-block × k-block attention with f32 accumulation.

    Returns (unnormalized out, row max, row sumexp) for online-softmax
    merging.  q: [B,Tq,H,D], k/v: [B,Tk,H,D], bias: [Tq,Tk] or None.
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if bias is not None:
        s = s + bias[None, None, :, :]
    m = jnp.max(s, axis=-1)                       # [B,H,Tq]
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)                       # [B,H,Tq]
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o, m, l


def ring_attention(q, k, v, axis_name: str = "sp", causal: bool = False,
                   scale: Optional[float] = None,
                   use_flash: Optional[bool] = None,
                   block_q: Optional[int] = None,
                   block_k: Optional[int] = None,
                   interpret: Optional[bool] = None):
    """Exact (flash-equivalent) attention over an ``sp``-sharded sequence.

    q: ``[B, T_loc, H, D]``; k, v: ``[B, T_loc, K, D]`` with ``H % K == 0``
    — GQA is supported on both paths (the pallas path reads shared kv heads
    natively, so the ring rotates ``H/K``× less data than a materialized
    repeat would).

    Two inner engines, same numerics:

    - **Pallas flash** (default on TPU; forced by ``use_flash=True`` or
      ``HVD_TPU_FLASH=1`` — interpret mode off-TPU): every per-block
      (o, lse) pair comes from the flash kernels in
      ``ops/flash_attention.py``; ring steps merge the normalized pairs by
      logsumexp weighting, and a custom VJP runs the backward ring over the
      flash backward kernels with the GLOBAL lse (dq rides the rotating
      tuple back to its owner; dk/dv accumulate where the kv shard lives).
    - **jnp blockwise** (fallback): the original online-softmax ring.
    """
    from ..ops.flash_attention import (resolve_flash, _interpret_default,
                                       resolve_blocks)
    # No seq threshold here: the alternative to the pallas ring engine is
    # the jnp blockwise ring below (full per-step [B,H,Tq,Tk] scores in
    # HBM + a materialized GQA repeat), NOT XLA's fused single-device
    # attention — so the single-device crossover (flash_min_seq) does not
    # apply and TPU auto mode always takes the flash engine.
    if resolve_flash(use_flash):
        if interpret is None:
            interpret = _interpret_default()
        block_q, block_k = resolve_blocks(block_q, block_k)
        return _ring_flash_bthd(q, k, v, axis_name, causal, scale,
                                block_q, block_k, interpret)
    if k.shape[2] != q.shape[2]:
        # jnp path's accumulator is head-aligned: materialize the GQA
        # repeat (the pallas path above avoids this).
        rep = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    n = compat_axis_size(axis_name)
    my = lax.axis_index(axis_name)
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    scale = scale if scale is not None else 1.0 / (D ** 0.5)

    # Online-softmax accumulators (f32).
    o_acc = jnp.zeros((B, Tq, H, D), jnp.float32)
    m_acc = jnp.full((B, H, Tq), NEG_INF, jnp.float32)
    l_acc = jnp.zeros((B, H, Tq), jnp.float32)

    shift = [(i, (i + 1) % n) for i in range(n)]

    def merge(carry, block):
        o_acc, m_acc, l_acc = carry
        o, m, l = block
        m_new = jnp.maximum(m_acc, m)
        a = jnp.exp(m_acc - m_new)
        b = jnp.exp(m - m_new)
        l_new = l_acc * a + l * b
        # broadcast [B,H,Tq] -> [B,Tq,H,1]
        a_ = jnp.transpose(a, (0, 2, 1))[..., None]
        b_ = jnp.transpose(b, (0, 2, 1))[..., None]
        o_new = o_acc * a_ + o.astype(jnp.float32) * b_
        return o_new, m_new, l_new

    kv = (k, v)
    for step in range(n):
        src = (my - step) % n          # which rank's K/V block we now hold
        k_cur, v_cur = kv
        if causal:
            def compute(args):
                q_, k_, v_ = args
                q_pos = my * Tq + jnp.arange(Tq)
                k_pos = src * Tk + jnp.arange(Tk)
                bias = jnp.where(q_pos[:, None] >= k_pos[None, :],
                                 0.0, NEG_INF)
                return _block_attn(q_, k_, v_, bias, scale)

            def masked(args):
                # Identity element of the online-softmax merge.
                return (jnp.zeros((B, Tq, H, D), jnp.float32),
                        jnp.full((B, H, Tq), NEG_INF, jnp.float32),
                        jnp.zeros((B, H, Tq), jnp.float32))

            # src = (my-step)%n > my  ⇔  my < step: this rank's queries are
            # entirely BEFORE the held block — skip the whole block's
            # compute (≈ halves the causal ring's FLOPs at large sp).
            o, m, l = lax.cond(my < step, masked, compute, (q, k_cur, v_cur))
        else:
            o, m, l = _block_attn(q, k_cur, v_cur, None, scale)
        o_acc, m_acc, l_acc = merge((o_acc, m_acc, l_acc), (o, m, l))
        if step != n - 1:
            # Rotate K/V to the next rank; XLA overlaps this with compute.
            kv = (lax.ppermute(k_cur, axis_name, perm=shift),
                  lax.ppermute(v_cur, axis_name, perm=shift))

    l_ = jnp.transpose(l_acc, (0, 2, 1))[..., None]        # [B,Tq,H,1]
    out = o_acc / jnp.maximum(l_, 1e-30)
    return out.astype(q.dtype)


# ------------------------------------------------- pallas-flash ring engine
def _ring_flash_bthd(q, k, v, axis_name, causal, scale, block_q, block_k,
                     interpret):
    """[B, T, H, D] wrapper: flatten heads into the batch dim ([BH, T, D],
    the flash kernels' layout), run the flash ring core, restore."""
    B, Tq, H, D = q.shape
    K = k.shape[2]
    if v.shape[2] != K or (K != H and H % K):
        raise ValueError(f"GQA heads mismatch: q={H} k={K} v={v.shape[2]}")
    rep = H // K
    scale = scale if scale is not None else 1.0 / (D ** 0.5)

    def to_bh(x):
        h = x.shape[2]
        return x.transpose(0, 2, 1, 3).reshape(B * h, x.shape[1], D)

    o = _ring_flash_core(to_bh(q), to_bh(k), to_bh(v), axis_name, causal,
                         scale, block_q, block_k, interpret, rep)
    return o.reshape(B, H, Tq, D).transpose(0, 2, 1, 3)


def _ring_flash_forward(qb, kb, vb, axis_name, causal, scale, block_q,
                        block_k, interpret, rep):
    """Forward ring over the flash forward kernel.  Per step the held kv
    block is one of three STATIC cases (step is a Python int, so the kernel
    config stays static): step 0 = the causal diagonal; step > 0 = full
    block when this rank's queries are after the held kv (my >= step),
    identity otherwise.  Normalized per-block (o, lse) pairs merge by
    logsumexp weighting.  Returns (o [BH, Tq, D] in q dtype, global lse)."""
    from ..ops.flash_attention import _fwd_impl
    n = compat_axis_size(axis_name)
    my = lax.axis_index(axis_name)
    BH, Tq, D = qb.shape
    o_acc = jnp.zeros((BH, Tq, D), jnp.float32)
    lse_acc = jnp.full((BH, Tq), NEG_INF, jnp.float32)
    shift = [(i, (i + 1) % n) for i in range(n)]

    kv = (kb, vb)
    for step in range(n):
        k_cur, v_cur = kv
        if step == 0:
            o_i, lse_i = _fwd_impl(qb, k_cur, v_cur, scale, causal,
                                   block_q, block_k, interpret, rep)
            o_i = o_i.astype(jnp.float32)
        elif causal:
            def compute(args):
                q_, k_, v_ = args
                o_c, l_c = _fwd_impl(q_, k_, v_, scale, False,
                                     block_q, block_k, interpret, rep)
                return o_c.astype(jnp.float32), l_c

            def masked(args):
                # Identity of the (o, lse) merge.
                return (jnp.zeros((BH, Tq, D), jnp.float32),
                        jnp.full((BH, Tq), NEG_INF, jnp.float32))

            o_i, lse_i = lax.cond(my < step, masked, compute,
                                  (qb, k_cur, v_cur))
        else:
            o_i, lse_i = _fwd_impl(qb, k_cur, v_cur, scale, False,
                                   block_q, block_k, interpret, rep)
            o_i = o_i.astype(jnp.float32)
        lse_new = jnp.logaddexp(lse_acc, lse_i)
        a = jnp.exp(lse_acc - lse_new)[..., None]
        b = jnp.exp(lse_i - lse_new)[..., None]
        o_acc = o_acc * a + o_i * b
        lse_acc = lse_new
        if step != n - 1:
            kv = (lax.ppermute(k_cur, axis_name, perm=shift),
                  lax.ppermute(v_cur, axis_name, perm=shift))
    return o_acc.astype(qb.dtype), lse_acc


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _ring_flash_core(qb, kb, vb, axis_name, causal, scale, block_q, block_k,
                     interpret, rep):
    o, _ = _ring_flash_forward(qb, kb, vb, axis_name, causal, scale,
                               block_q, block_k, interpret, rep)
    return o


def _ring_flash_fwd_rule(qb, kb, vb, axis_name, causal, scale, block_q,
                         block_k, interpret, rep):
    o, lse = _ring_flash_forward(qb, kb, vb, axis_name, causal, scale,
                                 block_q, block_k, interpret, rep)
    return o, (qb, kb, vb, o, lse)


def _ring_flash_bwd_rule(axis_name, causal, scale, block_q, block_k,
                         interpret, rep, res, do):
    """Backward ring: kv (and its dk/dv accumulators) stay put; the tuple
    (q, do, lse, delta, dq) rotates.  At step t the held q belongs to rank
    ``(my - t) % n``; with causal masking it attends this rank's kv iff
    my < t (plus the t = 0 diagonal).  Every step uses the flash backward
    kernels with the GLOBAL lse/delta, so per-pair contributions are exact;
    after n rotations the dq accumulator arrives back at its owner."""
    from ..ops.flash_attention import _bwd_impl
    qb, kb, vb, o, lse = res
    n = compat_axis_size(axis_name)
    my = lax.axis_index(axis_name)
    BH, Tq, D = qb.shape
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    shift = [(i, (i + 1) % n) for i in range(n)]

    dk_acc = jnp.zeros(kb.shape, jnp.float32)
    dv_acc = jnp.zeros(vb.shape, jnp.float32)
    rot = (qb, do, lse, delta, jnp.zeros((BH, Tq, D), jnp.float32))
    for t in range(n):
        q_t, do_t, lse_t, delta_t, dq_t = rot
        if t == 0:
            dq_i, dk_i, dv_i = _bwd_impl(
                q_t, kb, vb, do_t, lse_t, delta_t, scale=scale,
                causal=causal, block_q=block_q, block_k=block_k,
                interpret=interpret, rep=rep)
        elif causal:
            def compute(args):
                q_, do_, lse_, delta_ = args
                return _bwd_impl(q_, kb, vb, do_, lse_, delta_, scale=scale,
                                 causal=False, block_q=block_q,
                                 block_k=block_k, interpret=interpret,
                                 rep=rep)

            def skip(args):
                return (jnp.zeros((BH, Tq, D), qb.dtype),
                        jnp.zeros(kb.shape, kb.dtype),
                        jnp.zeros(vb.shape, vb.dtype))

            dq_i, dk_i, dv_i = lax.cond(my < t, compute, skip,
                                        (q_t, do_t, lse_t, delta_t))
        else:
            dq_i, dk_i, dv_i = _bwd_impl(
                q_t, kb, vb, do_t, lse_t, delta_t, scale=scale,
                causal=False, block_q=block_q, block_k=block_k,
                interpret=interpret, rep=rep)
        dk_acc = dk_acc + dk_i.astype(jnp.float32)
        dv_acc = dv_acc + dv_i.astype(jnp.float32)
        rot = (q_t, do_t, lse_t, delta_t, dq_t + dq_i.astype(jnp.float32))
        # Rotate every step (including the last) so each tuple lands back
        # on its owner after n hops.
        rot = tuple(lax.ppermute(x, axis_name, perm=shift) for x in rot)
    dq_home = rot[4]
    return (dq_home.astype(qb.dtype), dk_acc.astype(kb.dtype),
            dv_acc.astype(vb.dtype))


_ring_flash_core.defvjp(_ring_flash_fwd_rule, _ring_flash_bwd_rule)


def _causal_mask(Tq, Tk, window: Optional[int]):
    m = jnp.arange(Tq)[:, None] >= jnp.arange(Tk)[None, :]
    if window:
        m = jnp.logical_and(
            m, (jnp.arange(Tq)[:, None] - jnp.arange(Tk)[None, :])
            < window)
    return m


def local_flash_attention(q, k, v, causal: bool = False,
                          scale: Optional[float] = None,
                          window: Optional[int] = None):
    """Single-device reference attention (same math, no ring) for tests and
    for the sp=1 fast path.  GQA is native: kv may have ``K = H / rep``
    heads — a grouped einsum, no HBM repeat.  ``window`` = sliding-window
    (Mistral-style) causal attention over the last ``window`` positions.
    ``v``'s heads may be another width than ``q``'s and ``k``'s: the
    output has theirs."""
    B, Tq, H, D = q.shape
    K = k.shape[2]
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    if window is not None and not causal:
        raise ValueError("window requires causal=True")
    if K != H:
        if v.shape[2] != K or H % K:
            raise ValueError(f"GQA heads mismatch: q={H} k={K} v={v.shape[2]}")
        qg = q.reshape(B, Tq, K, H // K, D)
        s = jnp.einsum("bqkrd,bskd->bkrqs", qg, k,
                       preferred_element_type=jnp.float32) * scale
        if causal:
            mask = _causal_mask(Tq, k.shape[1], window)
            s = jnp.where(mask[None, None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bkrqs,bskd->bqkrd", p.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
        return out.reshape(B, Tq, H, v.shape[-1]).astype(q.dtype)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        mask = _causal_mask(Tq, k.shape[1], window)
        s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)
