"""Pallas TPU flash attention (forward + backward kernels).

The hot op of the flagship Llama path (SURVEY.md §7 "pallas kernels for the
hot ops"; no reference analogue — Horovod ships no model math).  Standard
flash attention: the [Tq, Tk] score matrix is never materialized in HBM;
each (batch·head, q-block) streams k/v blocks through VMEM with an
online-softmax accumulator whose row statistics stay lane-replicated, in
the layout the score block's reductions leave them.  The backward pass
recomputes probabilities blockwise from the saved logsumexp — two kernels
(dq; dk/dv) so every accumulator lives in VMEM scratch across the inner
grid dimension.  Where the mask takes whole blocks out (causal, a window)
that inner dimension is no dense row of blocks but the steps of a schedule
made at trace time (:func:`block_schedule`): the blocks in which the mask
keeps an element, so a block that computes nothing costs no grid step.

Layout: ``[B, T, H, D]`` (the llama layout).  GQA is native: pass kv with
``K = H / rep`` heads and each q-head group reads its shared kv head
through the kernels' block index maps — the repeat never touches HBM.
Queries and keys have one width and values another (latent attention scores
over 192 numbers and sums values of 128): ``q``, ``k``, ``dq`` and ``dk``
blocks are ``Dqk`` wide, ``v``, ``o``, ``do`` and ``dv`` blocks and the
output's accumulator ``Dv``; a width off the 128 lanes' grid is the array's
own last dimension, which a block may have, and is padded in VMEM, never in
HBM.

On non-TPU backends the kernels run in Pallas interpret mode (tests), so
the same code path is exercised everywhere; ``models/llama`` routes to
this kernel on TPU and keeps the jnp reference elsewhere.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import trace
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


# ---------------------------------------------------------------- schedule
# What a step of the schedule is: the FIRST and the LAST step of its row
# (where the accumulators start and where the row is written) and a LIVE
# block to compute.  A row with no live block keeps one step that is FIRST
# and LAST alone and writes zeros.
FIRST, LAST, LIVE = 1, 2, 4

# The steps of a list that scalar memory is asked to hold: a word a step,
# three quarters of a v5e's 1 MiB (Mosaic refuses a kernel past 1 MiB with
# RESOURCE_EXHAUSTED).  A longer list is not made: see :class:`_Walk`.
MAX_LIST = 3 * 2 ** 16


def _live(q_start, k_start, *, Tq, Tk, block_q, block_k, causal, window):
    """Whether the mask keeps an element of the block that starts at
    ``(q_start, k_start)``: not whole above the diagonal (causal), not
    whole below the window's band, the padded tail left out.  Plain
    operators, so NumPy arrays (the schedule) and a kernel's scalars (the
    dense grid) both pass through."""
    if not causal:
        return True
    live = (k_start <= q_start + block_q - 1) & (k_start <= Tq - 1)
    if window:
        live &= ((k_start + block_k - 1 > q_start - window)
                 & (Tk - 1 > q_start - window))
    return live


def block_schedule(Tq, Tk, block_q, block_k, causal, window=0, rep=1,
                   by_k=False):
    """The steps a kernel walks, made at trace time from what the call
    sees: ``(rows, cols, flags)``, three ``int32`` arrays a step.

    Only live blocks (:func:`_live`) are steps, so a block that computes
    nothing is no grid step and fetches nothing.  (The rule is exact: the
    dense grid's test kept a few blocks more, all of them masked whole —
    one whose last column is the first the window drops, one whose columns
    inside the band are padding.  Such a block added zeros.)
    ``flash_fwd`` and ``flash_bwd_dq`` walk a q block's k blocks ascending
    (``rows`` = ``qi``, ``cols`` = ``ki``); ``flash_bwd_dkv`` (``by_k``) a
    k block's ``t = r * n_q + qi`` ascending over the ``rep`` q-heads of
    its group (``rows`` = ``ki``, ``cols`` = ``t``): the order in which a
    dense grid reached them, so every accumulator sums in that order."""
    n_q, n_k = -(-Tq // block_q), -(-Tk // block_k)
    live = np.broadcast_to(_live(
        np.arange(n_q)[:, None] * block_q, np.arange(n_k)[None, :] * block_k,
        Tq=Tq, Tk=Tk, block_q=block_q, block_k=block_k, causal=causal,
        window=window), (n_q, n_k))
    if by_k:
        live = np.tile(live.T, (1, rep))
    steps = live.copy()
    steps[~live.any(axis=1), 0] = True
    rows, cols = np.nonzero(steps)
    flags = np.where(live[rows, cols], LIVE, 0)
    turn = np.flatnonzero(np.diff(rows))
    flags[np.r_[0, turn + 1]] |= FIRST
    flags[np.r_[turn, -1]] |= LAST
    return tuple(x.astype(np.int32) for x in (rows, cols, flags))


class _Walk:
    """How a kernel call's index maps and bodies find the blocks of a step.

    The grid after the head's dimension is ``grid``; ``at`` is what follows
    the head in an index map's arguments (the step's place in ``grid``, then
    the refs of ``operands``), and ``blocks(*at)`` / ``flags(*at)`` read it.

    * Where the mask takes blocks out, ``grid`` is the steps of
      :func:`block_schedule` and ``operands`` its list in scalar memory, a
      word a step: flags, column, row (``flash_bwd_dkv``'s column ``t`` as
      ``r`` and ``qi`` apart, so no map divides).
    * Where every block is live (a non-causal call; one block) the list
      would be the whole grid: ``grid`` is ``(rows, columns)``, a step's
      blocks are its place, and nothing is read.  (A step that reads its
      entry costs the forward 0.05 us, ``dq`` and ``dkv`` 0.10 more than
      one that does not: PERF.md section 6, PR 44.)  The same walk,
      with :func:`_live` as a step's test, takes a list of more than
      ``MAX_LIST`` steps: such a call runs its dead steps, as every call
      did before the schedule."""

    def __init__(self, Tq, Tk, block_q, block_k, causal, window, rep=1,
                 by_k=False):
        n_q, n_k = -(-Tq // block_q), -(-Tk // block_k)
        rows, cols, flags = block_schedule(Tq, Tk, block_q, block_k, causal,
                                           window, rep, by_k)
        self.n_q, self.by_k = n_q, by_k
        self.block_q, self.block_k = block_q, block_k
        self.q_bits = int(n_q - 1).bit_length()
        if by_k:
            cols = (cols // n_q) << self.q_bits | cols % n_q
        self.col_bits = int(cols.max()).bit_length()
        self.listed = (len(flags) < rep * n_q * n_k
                       and len(flags) <= MAX_LIST and 3 + self.col_bits
                       + int(rows.max()).bit_length() <= 31)
        if self.listed:
            self.grid = (len(flags),)
            self.operands = (jnp.asarray(
                flags | cols << 3 | rows << (3 + self.col_bits)),)
        else:
            self.grid = (n_k, rep * n_q) if by_k else (n_q, n_k)
            self.operands = ()
            self.live = functools.partial(
                _live, Tq=Tq, Tk=Tk, block_q=block_q, block_k=block_k,
                causal=causal, window=window)
        trace.flash_blocks["grid"] += rep * n_q * n_k
        trace.flash_blocks["steps"] += int(np.prod(self.grid))

    @property
    def semantics(self):
        return tpu_compiler_params(dimension_semantics=(
            "parallel",) * len(self.grid) + ("arbitrary",))

    def split(self, refs):
        """A kernel's refs as (``at`` of this step, the refs after the
        walk's own)."""
        n = len(self.operands)
        here = tuple(pl.program_id(1 + d) for d in range(len(self.grid)))
        return here + refs[:n], refs[n:]

    def blocks(self, *at):
        """``(qi, ki, r)`` of the step at ``at``: its q block, its k block,
        and (``by_k``) its q-head within the kv head's group."""
        if self.listed:
            step, words = at
            row = words[step] >> (3 + self.col_bits)
            col = (words[step] >> 3) & ((1 << self.col_bits) - 1)
            if not self.by_k:
                return row, col, 0
            return col & ((1 << self.q_bits) - 1), row, col >> self.q_bits
        row, col = at
        if not self.by_k:
            return row, col, 0
        return col % self.n_q, row, col // self.n_q

    def flags(self, *at):
        """``(first, live, last)`` of the step at ``at``."""
        if self.listed:
            step, words = at
            word = words[step]
            return word & FIRST != 0, word & LIVE != 0, word & LAST != 0
        qi, ki, _ = self.blocks(*at)
        return (at[1] == 0, self.live(qi * self.block_q, ki * self.block_k),
                at[1] == self.grid[1] - 1)


def _mask(s, q_start, k_start, *, causal, window, tq_valid, tk_valid):
    """A block's scores with ``NEG_INF`` where the pair is out: a padded
    row or column, above the diagonal, below the window.  (In most blocks
    of a schedule the mask is all true; building it there reads no slower
    on the chip than a second body without it: PERF.md section 6, PR 44.)"""
    block_q, block_k = s.shape
    rows = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    terms = []
    if tk_valid % block_k:
        terms.append(cols < tk_valid)
    if tq_valid % block_q:
        terms.append(rows < tq_valid)
    if causal:
        terms.append(rows >= cols)
        if window:
            terms.append(rows - cols < window)
    if not terms:       # non-causal and no padded tail: nothing to mask
        return s
    return jnp.where(functools.reduce(jnp.logical_and, terms), s, NEG_INF)


def _step(walk, at, block, *, first, last):
    """One step of a kernel: ``first()`` where its row starts, ``block()``
    for a live block, ``last()`` where the row ends."""
    is_first, is_live, is_last = walk.flags(*at)
    pl.when(is_first)(first)
    pl.when(is_live)(block)
    pl.when(is_last)(last)


# ----------------------------------------------------------------- forward
LANES = 128     # of a vector register: the row statistics' scratch width


def _fwd_kernel(*refs, walk, scale, block_q, block_k, **edges):
    """Online softmax over the live k-blocks of one (head, q-block).  The
    running max ``m`` and sum ``l`` live as ``[block_q, LANES]`` scratch, a
    row's value in every lane: that is the layout a reduction along the
    lanes of the ``[block_q, block_k]`` scores leaves behind, so nothing
    between the two products changes orientation (as ``(block_q,)`` vectors
    each block paid four relayouts through VMEM, 2.9 us a live block where
    this form takes 1.1: PERF.md section 6, PR 41)."""
    at, (q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref,
         l_ref) = walk.split(refs)
    head_dim = acc_ref.shape[1]

    def first():
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

    def block():
        # Dots take the RAW input dtype (bf16 in training) with an f32
        # accumulator: bf16×bf16 products are exact in f32 accumulation,
        # so this matches the old cast-to-f32-first numerics while running
        # the MXU at full bf16 rate instead of the slower f32 path.
        q = q_ref[0]                                # [bq, D]
        k = k_ref[0]                                # [bk, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [bq, bk]
        qi, ki, _ = walk.blocks(*at)
        s = _mask(s, qi * block_q, ki * block_k, **edges)

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

    def last():
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

    _step(walk, at, block, first=first, last=last)


# ---------------------------------------------------------------- backward
def _dq_kernel(*refs, walk, scale, block_q, block_k, **edges):
    at, (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
         acc_ref) = walk.split(refs)

    def first():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def block():
        # Raw-dtype MXU operands + f32 accumulators (see _fwd_kernel): the
        # f32 intermediates p/ds are quantized back to the operand dtype
        # for their second matmuls.
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qi, ki, _ = walk.blocks(*at)
        s = _mask(s, qi * block_q, ki * block_k, **edges)
        p = jnp.exp(s - lse_ref[0, :, :1])        # [bq, bk]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[0, :, :1]) * scale).astype(k.dtype)
        acc_ref[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    def last():
        dq_ref[0] = acc_ref[:].astype(dq_ref.dtype)

    _step(walk, at, block, first=first, last=last)


def _dkv_kernel(*refs, walk, scale, block_q, block_k, **edges):
    at, (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
         dk_acc, dv_acc) = walk.split(refs)

    def first():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def block():
        # Raw-dtype MXU operands + f32 accumulators (see _fwd_kernel).
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qi, ki, _ = walk.blocks(*at)
        s = _mask(s, qi * block_q, ki * block_k, **edges)
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

    def last():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)

    _step(walk, at, block, first=first, last=last)


# -------------------------------------------------------------- dispatcher
def _by_q(walk):
    """Index map of a q-side block at a step of a q block's walk."""
    return lambda b, *at: (b, walk.blocks(*at)[0], 0)


def _kv_of_q(walk, rep):
    """Index map of the k or v block at a step of a q block's walk:
    ``rep`` consecutive q-heads read one kv head."""
    return lambda b, *at: (b // rep, walk.blocks(*at)[1], 0)


def _pad_t(x, block):
    t = x.shape[1]
    pad = (-t) % block
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    return x


def _fwd_impl(q, k, v, scale, causal, block_q, block_k, interpret, rep=1,
              window=0):
    """q: [BH, T, Dqk]; k: [BH // rep, T, Dqk]; v: [BH // rep, T, Dv] (GQA:
    ``rep`` consecutive q-heads share one kv head — remapped in the
    BlockSpec index, no materialized repeat) -> (o [BH, Tq, Dv], lse [BH,
    Tq])."""
    BH, Tq, D = q.shape
    Tk, Dv = k.shape[1], v.shape[2]
    bq, bk = min(block_q, Tq), min(block_k, Tk)
    qp, kp, vp = _pad_t(q, bq), _pad_t(k, bk), _pad_t(v, bk)
    Tqp = qp.shape[1]

    walk = _Walk(Tq, Tk, bq, bk, causal, window)
    by_q, kv_of_q = _by_q(walk), _kv_of_q(walk, rep)
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, walk=walk, scale=scale, block_q=bq,
                          block_k=bk, causal=causal, window=window,
                          tq_valid=Tq, tk_valid=Tk),
        name="flash_fwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(walk.operands),
            grid=(BH,) + walk.grid,
            in_specs=[
                pl.BlockSpec((1, bq, D), by_q),
                pl.BlockSpec((1, bk, D), kv_of_q),
                pl.BlockSpec((1, bk, Dv), kv_of_q),
            ],
            out_specs=[
                pl.BlockSpec((1, bq, Dv), by_q),
                # 3D (1, bq, 1): TPU block rules need the trailing dims
                # divisible by (8, 128) or equal to the array's — a [BH, T]
                # row vector can't satisfy that, [BH, T, 1] can.
                pl.BlockSpec((1, bq, 1), by_q),
            ],
            scratch_shapes=[
                pltpu.VMEM((bq, Dv), jnp.float32),
                pltpu.VMEM((bq, LANES), jnp.float32),
                pltpu.VMEM((bq, LANES), jnp.float32),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((BH, Tqp, Dv), q.dtype),
            jax.ShapeDtypeStruct((BH, Tqp, 1), jnp.float32),
        ],
        compiler_params=walk.semantics,
        interpret=interpret,
    )(*walk.operands, qp, kp, vp)
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

    q: ``[B, T, H, Dqk]``; k: ``[B, T, K, Dqk]``; v: ``[B, T, K, Dv]`` with
    ``H % K == 0``; returns ``[B, T, H, Dv]``.  GQA is native (each group
    of ``H // K`` consecutive q-heads reads its kv head through the
    kernel's block index map; the kv tensors are never repeated in HBM).
    ``Dv`` may differ from ``Dqk`` (see the module's note); the default
    ``scale`` is ``Dqk ** -0.5``.  Differentiable via flash backward
    kernels; matches ``parallel.ring_attention.local_flash_attention``
    numerically.
    """
    B, Tq, H, D = q.shape
    K = k.shape[2]
    if v.shape[2] != K:
        raise ValueError(f"k has {K} heads but v has {v.shape[2]}")
    if k.shape[3] != D:
        raise ValueError(f"q heads are {D} wide but k heads {k.shape[3]}")
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
        _, t, h, d = x.shape
        return x.transpose(0, 2, 1, 3).reshape(B * h, t, d)

    def from_bh(x, t):
        return x.reshape(B, H, t, x.shape[-1]).transpose(0, 2, 1, 3)

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
    """Flash backward over one (q-shard, kv-shard) pair: q [BH, Tq, Dqk],
    do [BH, Tq, Dv], k [BK, Tk, Dqk], v [BK, Tk, Dv], lse/delta [BH, Tq]
    (lse may be the GLOBAL logsumexp — that is exactly what makes this
    reusable as one ring-attention backward step) -> (dq, dk, dv) in the
    input dtypes."""
    BH, Tq, D = q.shape
    BK = k.shape[0]
    Tk, Dv = k.shape[1], v.shape[2]
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

    edges = dict(causal=causal, window=window, tq_valid=Tq, tk_valid=Tk)

    walk = _Walk(Tq, Tk, bq, bk, causal, window)
    by_q, kv_of_q = _by_q(walk), _kv_of_q(walk, rep)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, walk=walk, scale=scale, block_q=bq,
                          block_k=bk, **edges),
        name="flash_bwd_dq",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(walk.operands),
            grid=(BH,) + walk.grid,
            in_specs=[
                pl.BlockSpec((1, bq, D), by_q),
                pl.BlockSpec((1, bk, D), kv_of_q),
                pl.BlockSpec((1, bk, Dv), kv_of_q),
                pl.BlockSpec((1, bq, Dv), by_q),
                pl.BlockSpec((1, bq, 1), by_q),
                pl.BlockSpec((1, bq, 1), by_q),
            ],
            out_specs=pl.BlockSpec((1, bq, D), by_q),
            scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((BH, Tqp, D), q.dtype),
        compiler_params=walk.semantics,
        interpret=interpret,
    )(*walk.operands, qp, kp, vp, dop, lsep, deltap)[:, :Tq]

    # dk/dv accumulate over the rep q-heads sharing each kv head: a k block's
    # steps walk t = r * n_q + qi, and the q-side index map reads block qi
    # of head r of the kv head's group.
    walk = _Walk(Tq, Tk, bq, bk, causal, window, rep, by_k=True)

    def q_of_k(b, *at):
        qi, _, r = walk.blocks(*at)
        return (b * rep + r, qi, 0)

    def by_k(b, *at):
        return (b, walk.blocks(*at)[1], 0)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, walk=walk, scale=scale, block_q=bq,
                          block_k=bk, **edges),
        name="flash_bwd_dkv",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(walk.operands),
            grid=(BK,) + walk.grid,
            in_specs=[
                pl.BlockSpec((1, bq, D), q_of_k),
                pl.BlockSpec((1, bk, D), by_k),
                pl.BlockSpec((1, bk, Dv), by_k),
                pl.BlockSpec((1, bq, Dv), q_of_k),
                pl.BlockSpec((1, bq, 1), q_of_k),
                pl.BlockSpec((1, bq, 1), q_of_k),
            ],
            out_specs=[
                pl.BlockSpec((1, bk, D), by_k),
                pl.BlockSpec((1, bk, Dv), by_k),
            ],
            scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                            pltpu.VMEM((bk, Dv), jnp.float32)]),
        out_shape=[
            jax.ShapeDtypeStruct((BK, Tkp, D), k.dtype),
            jax.ShapeDtypeStruct((BK, Tkp, Dv), v.dtype),
        ],
        compiler_params=walk.semantics,
        interpret=interpret,
    )(*walk.operands, qp, kp, vp, dop, lsep, deltap)
    return dq, dk[:, :Tk], dv[:, :Tk]


_flash_core.defvjp(_flash_fwd, _flash_bwd)
