"""Pallas TPU flash attention (forward + backward kernels).

The hot op of the flagship Llama path (SURVEY.md §7 "pallas kernels for the
hot ops"; no reference analogue — Horovod ships no model math).  Standard
flash attention: the [Tq, Tk] score matrix is never materialized in HBM;
each (batch·head, q-block) streams k/v blocks through VMEM with an
online-softmax accumulator whose row statistics stay lane-replicated, in
the layout the score block's reductions leave them.  The backward pass
recomputes probabilities blockwise from the saved logsumexp — two kernels
(dq; dk/dv) so every accumulator lives in VMEM scratch across the inner
grid dimension.

Layout: ``[B, T, H, D]`` (the llama layout).  GQA is native: pass kv with
``K = H / rep`` heads and each q-head group reads its shared kv head
through the kernels' block index maps — the repeat never touches HBM.

On non-TPU backends the kernels run in Pallas interpret mode (tests), so
the same code path is exercised everywhere; ``models/llama`` routes to
this kernel on TPU and keeps the jnp reference elsewhere.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..compat import tpu_compiler_params

NEG_INF = -1e30


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def resolve_flash(override: Optional[bool] = None,
                  seq: Optional[int] = None,
                  causal: bool = False) -> bool:
    """Config-first flash routing: a model config's ``use_flash`` field
    (traced, so toggling it recompiles) wins; ``None`` falls back to
    :func:`flash_enabled` with the caller's sequence length and
    causality."""
    return flash_enabled(seq, causal) if override is None else override


def _env_int(name: str, dflt: int, valid=lambda v: True) -> int:
    """Env-tunable integer knob: bad, unparseable, or out-of-contract
    values keep the default instead of dying at trace time."""
    import os
    try:
        v = int(os.environ.get(name, str(dflt)))
        return v if valid(v) else dflt
    except ValueError:
        return dflt


def flash_min_seq(causal: bool = False) -> int:
    """Auto-mode crossover.  The defaults are unmeasured on the current
    machine (they come from an earlier installation's in-model A/Bs; the
    sweep that closes ROADMAP queue 1 item 4a re-measures them, and every
    benchmark cell runs at 4096 tokens or more, far above either):

    - **causal** (llama family): 512 — whole-block causal skipping halves
      the work, so flash is expected to win early.
    - **non-causal** (bert): 1024 — no blocks to skip, so below it flash's
      rescaling machinery is expected to be pure overhead against XLA's
      fused attention.

    ``HVD_TPU_FLASH_MIN_SEQ`` overrides BOTH; ``tools/flash_sweep.py
    --xla --seqs ...`` measures the crossover per chip."""
    return _env_int("HVD_TPU_FLASH_MIN_SEQ", 512 if causal else 1024,
                    lambda v: v >= 0)


def flash_enabled(seq: Optional[int] = None,
                  causal: bool = False) -> bool:
    """Shared routing default for attention call sites (llama, bert,
    Ulysses, ring): pallas flash on TPU for sequences past the measured
    crossover (:func:`flash_min_seq` — causality-aware), jnp reference
    elsewhere; ``HVD_TPU_FLASH=1/0`` forces it globally — all read at
    TRACE time only (not part of any jit cache key)."""
    import os
    v = os.environ.get("HVD_TPU_FLASH", "auto").lower()
    if v in ("1", "true", "on"):
        return True
    if v in ("0", "false", "off"):
        return False
    if jax.default_backend() != "tpu":
        return False
    return seq is None or seq >= flash_min_seq(causal)


# ----------------------------------------------------------------- forward
LANES = 128     # of a vector register: the row statistics' scratch width


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, scale, causal, block_q, block_k,
                n_k, tk_valid, window):
    """Online softmax over the k-blocks of one (head, q-block).  The running
    max ``m`` and sum ``l`` live as ``[block_q, LANES]`` scratch, a row's
    value in every lane: that is the layout a reduction along the lanes of
    the ``[block_q, block_k]`` scores leaves behind, so nothing between the
    two products changes orientation (as ``(block_q,)`` vectors each block
    paid four relayouts through VMEM, 2.9 us a live block where this form
    takes 1.1: PERF.md section 6, PR 41)."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    head_dim = acc_ref.shape[1]

    @pl.when(ki == 0)
    def _():
        # Half of the mask's NEG_INF: a live block that masks a row whole
        # then leaves it p = exp(NEG_INF - m) = 0, l = 0 (from NEG_INF
        # itself p would be exp(0) = 1 and the row would average v).
        m_ref[:] = jnp.full_like(m_ref, NEG_INF / 2)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def across(stat, width):
        """A ``[block_q, LANES]`` statistic against a block ``width`` wide."""
        if width == LANES:
            return stat
        if width % LANES == 0:
            return pltpu.repeat(stat, width // LANES, axis=1)
        return stat[:, :1]

    q_start = qi * block_q
    k_start = ki * block_k
    # Causal: skip k-blocks strictly above the diagonal band; a sliding
    # window additionally skips blocks entirely BELOW the band (the
    # Mistral-style O(T·W) compute shape — whole blocks outside
    # [r-window+1, r] never touch the MXU).
    live = (not causal) or (k_start <= q_start + block_q - 1)
    if window:
        live = jnp.logical_and(live,
                               k_start + block_k > q_start - window)

    @pl.when(live)
    def _():
        # Dots take the RAW input dtype (bf16 in training) with an f32
        # accumulator: bf16×bf16 products are exact in f32 accumulation,
        # so this matches the old cast-to-f32-first numerics while running
        # the MXU at full bf16 rate instead of the slower f32 path.
        q = q_ref[0]                                # [bq, D]
        k = k_ref[0]                                # [bk, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [bq, bk]
        cols = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = cols < tk_valid
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            mask = jnp.logical_and(mask, rows >= cols)
            if window:
                mask = jnp.logical_and(mask, rows - cols < window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - across(m_new, block_k))
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        # p is quantized to the value dtype for the second MXU pass (the
        # standard TPU flash formulation; exact when inputs are f32).
        acc_ref[:] = (acc_ref[:] * across(alpha, head_dim)
                      + jax.lax.dot_general(
                          p.astype(v_ref.dtype), v_ref[0],
                          (((1,), (0,)), ((), ())),
                          preferred_element_type=jnp.float32))
        m_ref[:] = m_new

    @pl.when(ki == n_k - 1)
    def _():
        l = l_ref[:]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / across(safe_l, head_dim)
                    ).astype(o_ref.dtype)
        # Empty rows (fully masked) store lse=0, NOT -inf: the backward
        # computes p = exp(s - lse) with s = NEG_INF on masked entries, and
        # exp(NEG_INF - 0) = 0 zeroes their contribution, while -inf would
        # turn it into exp(0) = 1 and poison dk/dv.
        lse = jnp.where(l == 0.0, 0.0, m_ref[:] + jnp.log(safe_l))
        lse_ref[0] = lse[:, :1]


# ---------------------------------------------------------------- backward
def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc_ref, *, scale, causal, block_q, block_k, n_k,
               tq_valid, tk_valid, window):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = ki * block_k
    live = (not causal) or (k_start <= q_start + block_q - 1)
    if window:
        live = jnp.logical_and(live,
                               k_start + block_k > q_start - window)

    @pl.when(live)
    def _():
        # Raw-dtype MXU operands + f32 accumulators (see _fwd_kernel): the
        # f32 intermediates p/ds are quantized back to the operand dtype
        # for their second matmuls.
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        cols = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        rows = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        mask = jnp.logical_and(cols < tk_valid, rows < tq_valid)
        if causal:
            mask = jnp.logical_and(mask, rows >= cols)
            if window:
                mask = jnp.logical_and(mask, rows - cols < window)
        s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse_ref[0, :, :1])        # [bq, bk]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[0, :, :1]) * scale).astype(k.dtype)
        acc_ref[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _():
        dq_ref[0] = acc_ref[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal,
                block_q, block_k, n_q, n_t, tq_valid, tk_valid, window):
    ki = pl.program_id(1)
    t = pl.program_id(2)      # = r * n_q + qi over the rep q-heads (GQA)
    qi = t % n_q

    @pl.when(t == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q_start = qi * block_q
    k_start = ki * block_k
    live = (not causal) or (k_start <= q_start + block_q - 1)
    if window:
        live = jnp.logical_and(live,
                               k_start + block_k > q_start - window)

    @pl.when(live)
    def _():
        # Raw-dtype MXU operands + f32 accumulators (see _fwd_kernel).
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        cols = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        rows = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        mask = jnp.logical_and(cols < tk_valid, rows < tq_valid)
        if causal:
            mask = jnp.logical_and(mask, rows >= cols)
            if window:
                mask = jnp.logical_and(mask, rows - cols < window)
        s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse_ref[0, :, :1])        # [bq, bk]
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)      # [bk, D]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[0, :, :1]) * scale).astype(q.dtype)
        dk_acc[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)      # [bk, D]

    @pl.when(t == n_t - 1)
    def _():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


# -------------------------------------------------------------- dispatcher
def _pad_t(x, block):
    t = x.shape[1]
    pad = (-t) % block
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    return x


def _fwd_impl(q, k, v, scale, causal, block_q, block_k, interpret, rep=1,
              window=0):
    """q: [BH, T, D]; k, v: [BH // rep, T, D] (GQA: ``rep`` consecutive
    q-heads share one kv head — remapped in the BlockSpec index, no
    materialized repeat) -> (o [BH, Tq, D], lse [BH, Tq])."""
    BH, Tq, D = q.shape
    Tk = k.shape[1]
    bq, bk = min(block_q, Tq), min(block_k, Tk)
    qp, kp, vp = _pad_t(q, bq), _pad_t(k, bk), _pad_t(v, bk)
    Tqp, Tkp = qp.shape[1], kp.shape[1]
    n_q, n_k = Tqp // bq, Tkp // bk

    kern = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                             block_q=bq, block_k=bk, n_k=n_k, tk_valid=Tk,
                             window=window)
    o, lse = pl.pallas_call(
        kern,
        name="flash_fwd",
        grid=(BH, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b // rep, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b // rep, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            # 3D (1, bq, 1): TPU block rules need the trailing dims
            # divisible by (8, 128) or equal to the array's — a [BH, T]
            # row vector can't satisfy that, [BH, T, 1] can.
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Tqp, D), q.dtype),
            jax.ShapeDtypeStruct((BH, Tqp, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
        ],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qp, kp, vp)
    return o[:, :Tq], lse[:, :Tq, 0]


def _block_defaults() -> tuple:
    """Kernel tile defaults, env-overridable for per-chip tuning
    (``HVD_TPU_FLASH_BLOCK_Q`` / ``HVD_TPU_FLASH_BLOCK_K`` — read at
    trace time; ``tools/flash_sweep.py --blocks`` measures candidates).
    An earlier installation's sweep chose 512x512 (bigger tiles amortize
    the grid/rescale overhead and keep the MXU fed); on this chip the
    three kernels reach 46-78 % of the MXU's peak at it at the benchmark's
    five geometries (PERF.md section 6, PR 41) and no other tile has been
    measured (ROADMAP queue 1 item 4a).  The sublane rule (multiples of
    8) is enforced here so a bad value keeps the default instead of dying
    in Mosaic lowering."""
    ok = lambda v: v >= 8 and v % 8 == 0  # noqa: E731
    return (_env_int("HVD_TPU_FLASH_BLOCK_Q", 512, ok),
            _env_int("HVD_TPU_FLASH_BLOCK_K", 512, ok))


def resolve_blocks(block_q: Optional[int],
                   block_k: Optional[int]) -> tuple:
    """Fill ``None`` tile sizes from :func:`_block_defaults` — the one
    resolution point shared by every flash call site (single-device,
    Ulysses, ring)."""
    dq, dk = _block_defaults()
    return (dq if block_q is None else block_q,
            dk if block_k is None else block_k)


def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    window: Optional[int] = None):
    """Memory-efficient exact attention.

    q: ``[B, T, H, D]``; k, v: ``[B, T, K, D]`` with ``H % K == 0`` — GQA
    is native (each group of ``H // K`` consecutive q-heads reads its kv
    head through the kernel's block index map; the kv tensors are never
    repeated in HBM).  Differentiable via flash backward kernels; matches
    ``parallel.ring_attention.local_flash_attention`` numerically.
    """
    B, Tq, H, D = q.shape
    K = k.shape[2]
    if v.shape[2] != K:
        raise ValueError(f"k has {K} heads but v has {v.shape[2]}")
    if H % K:
        raise ValueError(f"q heads ({H}) must be a multiple of kv heads "
                         f"({K}) for GQA")
    if k.dtype != q.dtype or v.dtype != q.dtype:
        # The kernels feed RAW operands to the MXU (bf16 at full rate) —
        # mixed dtypes would die with a cryptic dot_general trace error.
        raise ValueError(f"q/k/v must share one dtype, got {q.dtype}/"
                         f"{k.dtype}/{v.dtype}; cast before the call")
    rep = H // K
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    block_q, block_k = resolve_blocks(block_q, block_k)
    interpret = _interpret_default() if interpret is None else interpret
    if window is not None:
        if not causal:
            raise ValueError("window (sliding-window attention) requires "
                             "causal=True")
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")

    def to_bh(x):
        h = x.shape[2]
        return x.transpose(0, 2, 1, 3).reshape(B * h, x.shape[1], D)

    def from_bh(x, t):
        return x.reshape(B, H, t, D).transpose(0, 2, 1, 3)

    o = _flash_core(to_bh(q), to_bh(k), to_bh(v), scale, causal,
                    block_q, block_k, interpret, rep, window or 0)
    return from_bh(o, Tq)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash_core(q, k, v, scale, causal, block_q, block_k, interpret, rep,
                window):
    o, _ = _fwd_impl(q, k, v, scale, causal, block_q, block_k, interpret,
                     rep, window)
    return o


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret, rep,
               window):
    o, lse = _fwd_impl(q, k, v, scale, causal, block_q, block_k, interpret,
                       rep, window)
    return o, (q, k, v, o, lse)


def _flash_bwd(scale, causal, block_q, block_k, interpret, rep, window,
               res, do):
    q, k, v, o, lse = res
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)                                 # [BH, Tq]
    # The backward kernels dot do against v/q — same raw-dtype contract
    # as the forward (an f32 cotangent over bf16 primals is legal in jax).
    do = do.astype(q.dtype)
    return _bwd_impl(q, k, v, do, lse, delta, scale=scale, causal=causal,
                     block_q=block_q, block_k=block_k, interpret=interpret,
                     rep=rep, window=window)


def _bwd_impl(q, k, v, do, lse, delta, *, scale, causal, block_q, block_k,
              interpret, rep=1, window=0):
    """Flash backward over one (q-shard, kv-shard) pair: q/do [BH, Tq, D],
    k/v [BK, Tk, D], lse/delta [BH, Tq] (lse may be the GLOBAL logsumexp —
    that is exactly what makes this reusable as one ring-attention backward
    step) -> (dq, dk, dv) in the input dtypes."""
    BH, Tq, D = q.shape
    BK = k.shape[0]
    Tk = k.shape[1]
    bq, bk = min(block_q, Tq), min(block_k, Tk)

    qp, dop = _pad_t(q, bq), _pad_t(do, bq)
    kp, vp = _pad_t(k, bk), _pad_t(v, bk)
    pad_q = qp.shape[1] - Tq
    # Pad with 0 (see the forward's empty-row sentinel): padded rows then
    # produce p = exp(NEG_INF - 0) = 0 and contribute nothing.  3D
    # [BH, T, 1] for the same block-shape rule as the forward's lse.
    lsep = jnp.pad(lse, ((0, 0), (0, pad_q)))[..., None]
    deltap = jnp.pad(delta, ((0, 0), (0, pad_q)))[..., None]
    Tqp, Tkp = qp.shape[1], kp.shape[1]
    n_q, n_k = Tqp // bq, Tkp // bk

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, n_k=n_k,
                          tq_valid=Tq, tk_valid=Tk, window=window),
        name="flash_bwd_dq",
        grid=(BH, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b // rep, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b // rep, j, 0)),
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Tqp, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qp, kp, vp, dop, lsep, deltap)[:, :Tq]

    # dk/dv accumulate over the rep q-heads sharing each kv head: grid is
    # (B*K, n_k, rep*n_q) and the q-side index map walks head r = t // n_q,
    # block qi = t % n_q of the kv head's group.
    def _qix(b, j, t):
        return (b * rep + t // n_q, t % n_q, 0)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, n_q=n_q, n_t=rep * n_q,
                          tq_valid=Tq, tk_valid=Tk, window=window),
        name="flash_bwd_dkv",
        grid=(BK, n_k, rep * n_q),
        in_specs=[
            pl.BlockSpec((1, bq, D), _qix),
            pl.BlockSpec((1, bk, D), lambda b, j, t: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j, t: (b, j, 0)),
            pl.BlockSpec((1, bq, D), _qix),
            pl.BlockSpec((1, bq, 1), _qix),
            pl.BlockSpec((1, bq, 1), _qix),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BK, Tkp, D), k.dtype),
            jax.ShapeDtypeStruct((BK, Tkp, D), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qp, kp, vp, dop, lsep, deltap)
    return dq, dk[:, :Tk], dv[:, :Tk]


_flash_core.defvjp(_flash_fwd, _flash_bwd)
