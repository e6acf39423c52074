"""Pallas TPU kernels for the chunked gated delta rule (Yang et al., "Gated
Delta Networks"): ``S <- exp(g_t) S + k_t (beta_t (v_t - S^T k_t))^T``,
``o_t = S^T q_t`` with ``S_0 = 0``, a chunk of ``C`` tokens at a time, told
its sizes and never which model it serves.

XLA's code for the plain formulation (``models/gated_delta.py``
``chunked_gated_delta_rule``) computes every chunk at once: the decay
matrix, ``A``, the solve's two sides, ``U | W``, the in-chunk scores and
every chunk's state are float32 arrays of a sequence's whole length that
go to HBM and come back, forward, recomputed and again as cotangents.  Here
a chunk's whole algebra lives in VMEM and only the rule's arguments, its
result and the state each chunk starts from cross HBM:

- **forward**: grid ``(B, key heads, T / (block * C))``, the last axis
  walked in order.  A grid step takes ``block`` chunks of every value head
  that shares the key head (``q`` and ``k`` are read once a key head: the
  index map reads head ``h // repeat`` where the plain formulation reads a
  ``jnp.repeat`` copy); the ``dk x dv`` float32 state of each of those
  value heads is scratch that lives along the chunk axis, zeroed at a
  sequence's first chunk.  A chunk: ``G`` the running sum of ``g`` (a
  product with a triangular matrix of ones), the decay ``exp(G_i - G_j)``
  only where ``i >= j``, ``A = tril(diag(beta) K K^T * D, -1)``, the
  inverse of ``I + A`` (:func:`_unit_lower_inverse`), ``U``, ``W``, ``V' =
  U - W S``, ``o = (Q * e^G) S + tril(Q K^T * D) V'``, ``S <- e^{G_C} S +
  (K * e^{G_C - G})^T V'``.  Two (chunk, head) problems are stacked into
  128 rows with their blocks down the diagonal (:func:`_stacked`): a
  float32 product in Mosaic is six passes that each latch the right
  operand, padded to 128 rows, and at a chunk's sizes the latches are most
  of the matrix units' time — two problems share them.  :data:`GROUP`
  chunks are one stretch of straight-line code (:func:`_chunks`).
- **backward**: the chunk axis walked from the end with ``dS`` carried in
  scratch.  The forward rule of the ``custom_vjp`` saves the state every
  :data:`GROUP` chunks start from (``[B, H, N / GROUP, dk, dv]`` float32)
  and every stack's inverse; a group's gradient is ``jax.vjp`` of the same
  chunk function, traced inside the kernel body, so the two kernels cannot
  disagree about a term, with the saved inverse's own cotangent ``-T^T dT
  T^T`` (:func:`_saved_inverse`) in place of the recursion's.  ``dq`` and
  ``dk`` are float32 sums over the value heads that share a key head,
  rounded once.

The rounding points are the plain formulation's: ``g``, its sums, the
decay, the solve and the state float32; the other matrix products take
their operands in the inputs' type and accumulate in float32.  ``T`` need
not be a multiple of the chunk or of the block: padding tokens have ``k =
0``, ``beta = 0``, ``g = 0``, which write nothing and decay nothing.

A head is a column block of ``[B, T, H * d]``, so the kernels' widths are
whole lanes.  A key or value width that is not (96 x 192, say) runs at the
widths rounded up (:func:`widths`: 128 x 256) on ``q``, ``k``, ``v`` padded
with zeros, and the result is cut back to the value width: a key padded
with zeros has the same length and the same products with every other key
and query, the padded rows of the state stay zero, and the padded columns
of ``v`` give zero columns of ``U``, of the state and of ``o``.

On non-TPU backends the kernels run in Pallas interpret mode (tests);
``gated_delta_net`` in ``models/gated_delta.py`` routes here on a TPU where
:func:`tiles` finds the shape a fit and keeps the plain formulation
elsewhere.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..compat import tpu_compiler_params

LANES = 128
# chunks a grid step: a step costs 0.3-0.5 us on this chip, a chunk about
# one; eight rows are also a float32 sublane tile of the ``[N, C]`` gates
BLOCK = 8
# chunks of a block whose algebra is one stretch of straight-line code
# (:func:`_chunks`)
GROUP = 2
F32 = jnp.float32


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def kernel_enabled() -> bool:
    """Whether :func:`tiles` is worth asking: on a TPU.  Read at trace
    time, as ``causal_conv.kernel_enabled`` is."""
    return jax.default_backend() == "tpu"


def widths(dk: int, dv: int) -> Optional[tuple]:
    """The key and value widths the kernels run at for heads of ``dk x
    dv``: each rounded up to whole lanes (96 x 192 -> 128 x 256;
    :func:`gated_delta_rule` pads with zeros and cuts the result back).
    ``None`` where a width is under a quarter of the lanes: the kernels'
    cost is the padded problem's whatever the width and the plain
    formulation's falls with it.  A reading on each side of the line, 30
    heads at 16 k tokens: at 32 x 32 the kernels are ahead (14.2 ms forward
    and 30.3 with the backward against 15.6 and 41.8), at 16 x 16 behind
    (14.1 and 29.9 against 11.8 and 28.8): PERF.md section 6, PR 48."""
    if min(dk, dv) < LANES // 4:
        return None
    return tuple(-(-d // LANES) * LANES for d in (dk, dv))


def tiles(q_shape, v_shape, chunk: int, dtype) -> Optional[int]:
    """The chunks a grid step takes for q (and k) of ``q_shape [B, T, Hk,
    dk]`` and v of ``v_shape [B, T, Hv, dv]``: :data:`BLOCK`, or all of
    them where the sequence has no more than twice that (the gates' block
    is then the whole array: a sequence is padded to whole blocks, and a
    short one would be mostly padding).  ``None`` where the kernels do
    not take the shape: a key or a value width that :func:`widths` does
    not take (under 32: whole lanes would be mostly padding), a
    chunk that is no multiple of 16 rows (a sublane tile of a 16-bit
    type) or wider than the lanes, value heads that are no multiple of
    the key heads, or a type other than bfloat16 and float32."""
    if len(q_shape) != 4 or len(v_shape) != 4:
        return None
    (_, T, hk, dk), (hv, dv) = q_shape, v_shape[2:]
    if (widths(dk, dv) is None or chunk % 16 or not 16 <= chunk <= LANES
            or hk < 1 or hv % hk
            or jnp.dtype(dtype) not in (jnp.dtype(jnp.bfloat16),
                                        jnp.dtype(jnp.float32))):
        return None
    chunks = -(-T // chunk)
    return chunks if chunks <= 2 * BLOCK else BLOCK


# ------------------------------------------------------------------ a chunk
def _mm(a, b, contract=((1,), (0,)), precision=None):
    return lax.dot_general(a, b, (contract, ((), ())), precision=precision,
                           preferred_element_type=F32)


def _mm32(a, b, contract=((1,), (0,))):
    """A product of two float32 matrices to float32's accuracy (Mosaic's
    six passes, XLA's ``HIGHEST``).  The same six bfloat16 products made
    here, each of the right operand's pieces latched once, halve the
    matrix units' work and read slower on the chip: the vector units then
    bind (PERF.md section 6, PR 45)."""
    return _mm(a, b, contract, precision=lax.Precision.HIGHEST)


def _iota(M):
    return (lax.broadcasted_iota(jnp.int32, (M, M), 0),
            lax.broadcasted_iota(jnp.int32, (M, M), 1))


def _masks(M, C):
    """The 0 / 1 float32 masks ``[M, M]`` of ``M / C`` problems of ``C``
    rows stacked down the diagonal, made once a grid step and not once a
    chunk (index arithmetic and comparisons are vector work like any
    other): ``eye``; ``lower`` and ``strict``, a problem's own block on
    and below, and below, the diagonal; ``last``, a row's problem's last
    column; and for :func:`_unit_lower_inverse` ``levels``: the entries
    that join two diagonal blocks of 1, 2, 4, ... rows into one of
    twice as many."""
    row, col = _iota(M)
    own = row // C == col // C
    f32 = lambda m: m.astype(F32)
    levels, s = [], 1
    while s < C:
        levels.append(f32((row // (2 * s) == col // (2 * s))
                          & (row // s > col // s)))
        s *= 2
    return {"eye": f32(row == col), "lower": f32((row >= col) & own),
            "strict": f32((row > col) & own),
            "last": f32(col == row // C * C + C - 1),
            "levels": tuple(levels)}


def _unit_lower_inverse(A, masks):
    """``(I + A)^-1`` for a float32 ``A [M, M]`` that is strictly lower
    triangular inside diagonal blocks of ``C`` rows and zero outside them
    (``M / C`` independent problems down the diagonal: the inverse is
    block-diagonal as ``A`` is; ``masks``: :func:`_masks`), exactly the
    block recursion ``[[L11, 0], [L21,
    L22]]^-1 = [[T11, 0], [-T22 L21 T11, T22]]`` from blocks of one row
    up: with ``Td`` the inverse's diagonal blocks of ``s`` rows and
    ``Aoff`` the entries of ``A`` that join two such blocks into one of
    ``2 s``, the next level is ``Td - Td Aoff Td``.  Every intermediate is
    an entry of the inverse of a leading block of ``I + A`` — what
    substitution computes, so beta near 2 costs no more digits than it
    does there (the product ``(I - A)(I + A^2)(I + A^4)...`` is cheaper
    and loses them all where the keys of a chunk are alike)."""
    M, levels = A.shape[0], masks["levels"]
    # blocks of two rows: the inverse of [[1, 0], [a, 1]] is [[1, 0], [-a, 1]]
    T = masks["eye"] - A * levels[0]
    for level, mask in enumerate(levels[1:], 1):
        s, off = 2 ** level, A * mask
        if s % 8:
            T = T - _mm32(_mm32(T, off), T)
        else:
            # only the rows of every second block change, and from eight
            # rows up a block is whole sublane tiles: half the rows go
            # through the products
            odd = lambda X: jnp.concatenate(
                [X[i:i + s] for i in range(s, M, 2 * s)], axis=0)
            new = odd(T) - _mm32(_mm32(odd(T), off), T)
            T = jnp.concatenate(
                [new[(i - s) // 2:(i + s) // 2] if i % (2 * s) else T[i:i + s]
                 for i in range(0, M, s)], axis=0)
    return T


@jax.custom_vjp
def _saved_inverse(A, T):
    """``T``, the inverse of ``I + A`` that the forward kernel saved, as a
    function of ``A``: the inverse's own cotangent, ``dA = -T^T dT T^T``,
    two products where the recursion has ten and its autodiff twenty."""
    return T


def _saved_inverse_fwd(A, T):
    return T, T


def _saved_inverse_bwd(T, dT):
    return (-_mm32(_mm32(T, dT, ((0,), (0,))), T, ((1,), (1,))),
            jnp.zeros_like(T))


_saved_inverse.defvjp(_saved_inverse_fwd, _saved_inverse_bwd)


def _column(x_row, eye):
    """``[1, M] -> [M, 1]``: the diagonal of the row repeated down the
    sublanes, summed along the lanes."""
    return jnp.sum(eye * x_row, axis=1, keepdims=True)


def _rows(x, axis=0):
    return x[0] if len(x) == 1 else jnp.concatenate(x, axis=axis)


def _stacked(q, k, v, G_row, beta_row, S, dt, masks, inverse):
    """A chunk each of one or two independent problems — two value heads
    on one chunk, or one head on two chunks — with their rows stacked into
    ``M = n C`` rows, so that the two share the matrix units' 128 x 128
    tiles: a tuple with an entry a problem of q, k ``[C, dk]`` and v ``[C,
    dv]`` holding values of the inputs' type ``dt``, ``G_row`` (the
    running sum of g inside the chunk) and ``beta_row`` float32 ``[1,
    C]``, and the state ``S [dk, dv]`` float32 its chunk starts from
    (``None``: where the problem before it ends, the second of one head's
    two chunks) -> ``(o, S_next)``, an entry a problem of ``[C, dv]`` and
    ``[dk, dv]`` float32.  The decay, ``A``, its inverse, ``U | W`` and the in-chunk
    scores are computed on the stacked rows, blocks of ``C`` rows down the
    diagonal (``masks``: :func:`_masks` of ``M`` and ``C``); what meets a
    state a problem."""
    n, C = len(q), q[0].shape[0]
    q, k = (_rows([x.astype(dt) for x in xs]) for xs in (q, k))
    qf, kf = q.astype(F32), k.astype(F32)
    eye, lower = masks["eye"], masks["lower"]
    G_row, beta_row = _rows(G_row, axis=1), _rows(beta_row, axis=1)
    G_col, beta_col = _column(G_row, eye), _column(beta_row, eye)
    # a problem's last running sum on each of its rows
    total = jnp.sum(masks["last"] * G_row, axis=1, keepdims=True)
    # exp only of differences that are <= 0
    decay = jnp.exp((G_col - G_row) * lower) * lower
    nt = ((1,), (1,))
    A = _mm(k, k, nt) * (decay * masks["strict"]) * beta_col
    T = inverse(A, masks)
    grow = jnp.exp(G_col)
    vf = _rows([x.astype(dt).astype(F32) for x in v])
    dv = vf.shape[1]
    UW = _mm32(T, jnp.concatenate([vf * beta_col, kf * (beta_col * grow)],
                                  axis=1))
    U, W = UW[:, :dv], UW[:, dv:].astype(dt)
    scores = (_mm(q, k, nt) * decay).astype(dt)
    q_grown = (qf * grow).astype(dt)
    k_tail = (kf * jnp.exp(total - G_col)).astype(dt)
    v_new, o, S_next = [], [], []
    for i in range(n):
        at = slice(i * C, (i + 1) * C)
        S_i = S_next[i - 1] if S[i] is None else S[i]
        Sd = S_i.astype(dt)
        v_new.append(U[at] - _mm(W[at], Sd))
        o.append(_mm(q_grown[at], Sd))
        S_next.append(S_i * jnp.exp(total[at][C - 1:]) + _mm(
            k_tail[at], v_new[i].astype(dt), ((0,), (0,))))
    inside = _mm(scores, _rows(v_new).astype(dt))
    return (tuple(o[i] + inside[i * C:(i + 1) * C] for i in range(n)),
            tuple(S_next))


def _stacks(chunks, heads, C):
    """The (chunk, head) problems of ``chunks`` consecutive chunks in the
    order they are computed, two a stack where two chunks' rows fit the
    lanes and the problems pair off (every stack has as many)."""
    todo = [(j, r) for j in range(chunks) for r in range(heads)]
    pair = 2 if 2 * C <= LANES and len(todo) % 2 == 0 else 1
    return [todo[i:i + pair] for i in range(0, len(todo), pair)]


def _stack_masks(chunks, heads, C):
    """:func:`_masks` for the stacks that :func:`_stacks` makes."""
    return _masks(len(_stacks(chunks, heads, C)[0]) * C, C)


def _chunks(q, k, v, G_row, beta_row, S, dt, masks,
            inverse=_unit_lower_inverse):
    """Consecutive chunks of the value heads that share a key head: a
    tuple with an entry a chunk of q, k ``[C, dk]`` and, an entry a value
    head, of v ``[C, dv]``, ``G_row`` and ``beta_row`` ``[1, C]``
    (:func:`_stacked` has their meaning), from the states ``S`` (an entry a
    head) the first chunk starts from, with ``masks`` from
    :func:`_stack_masks` and ``inverse(A, masks)`` called once a stack in
    :func:`_stacks`' order -> ``(o, S_next)``, ``o`` an entry a chunk and
    head.  The (chunk, head) problems go through :func:`_stacked`
    two at a time where two chunks' rows fit the lanes.  Straight-line
    code: what does not depend on the state — the decay, the inverse, ``U``
    and ``W`` — is independent from chunk to chunk, and the compiler's
    scheduler fills the matrix units with several chunks' chains of
    products at once, where one chain leaves them waiting for a result."""
    heads = len(S)
    S, o = list(S), [[None] * heads for _ in q]
    for taken in _stacks(len(q), heads, q[0].shape[0]):
        o_p, S_p = _stacked(
            tuple(q[j] for j, _ in taken), tuple(k[j] for j, _ in taken),
            tuple(v[j][r] for j, r in taken),
            tuple(G_row[j][r] for j, r in taken),
            tuple(beta_row[j][r] for j, r in taken),
            # the second of two chunks of one head starts where the first
            # ended
            tuple(None if i and taken[0][1] == r else S[r]
                  for i, (_, r) in enumerate(taken)), dt, masks, inverse)
        for (j, r), o_i, S_i in zip(taken, o_p, S_p):
            o[j][r], S[r] = o_i, S_i
    return tuple(tuple(x) for x in o), tuple(S)


def _running_sums(g_rows):
    """The running sum along the lanes of float32 ``[n, C]``: a product
    with an upper-triangular matrix of ones (Mosaic has no ``cumsum``)."""
    row, col = _iota(g_rows.shape[1])
    return _mm32(g_rows, (row <= col).astype(F32))


# ----------------------------------------------------------------- forward
def _loads(c0, group, chunk, heads, mine, q_ref, k_ref, v_ref, G_ref,
           beta_ref, cast=lambda x: x):
    """:func:`_chunks`' q, k, v, ``G_row`` and ``beta_row`` for the
    ``group`` chunks from chunk ``c0`` (traced) of the block, and the
    chunks' rows in the block."""
    at = [pl.ds(pl.multiple_of((c0 + j) * chunk, chunk), chunk)
          for j in range(group)]
    return at, (
        tuple(cast(q_ref[0, a, :]) for a in at),
        tuple(cast(k_ref[0, a, :]) for a in at),
        tuple(tuple(cast(v_ref[0, a, mine[r]]) for r in heads) for a in at),
        tuple(tuple(G_ref[r, pl.ds(c0 + j, 1), :] for r in heads)
              for j in range(group)),
        tuple(tuple(beta_ref[0, r, pl.ds(c0 + j, 1), :] for r in heads)
              for j in range(group)))


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, *rest, chunk, block,
                group, repeat, dv, save_states):
    if save_states:
        o_ref, states_ref, inverses_ref, S_ref, G_ref = rest
    else:
        o_ref, S_ref, G_ref = rest
    heads = range(repeat)
    mine = [slice(r * dv, (r + 1) * dv) for r in heads]

    @pl.when(pl.program_id(2) == 0)
    def _():
        S_ref[...] = jnp.zeros_like(S_ref)

    for r in heads:
        G_ref[r] = _running_sums(g_ref[0, r])
    masks = _stack_masks(group, repeat, chunk)

    def one(n, carry):
        S = tuple(S_ref[r] for r in heads)
        made = []

        def inverse(A, masks):
            made.append(_unit_lower_inverse(A, masks))
            return made[-1]

        at, of_chunks = _loads(n * group, group, chunk, heads, mine, q_ref,
                               k_ref, v_ref, G_ref, beta_ref)
        if save_states:
            for r in heads:
                states_ref[0, r, n] = S[r]
        o, S = _chunks(*of_chunks, S, q_ref.dtype, masks, inverse)
        if save_states:
            for i, T in enumerate(made):
                inverses_ref[0, 0, n * len(made) + i] = T
        for r in heads:
            S_ref[r] = S[r]
            for a, o_c in zip(at, o):
                o_ref[0, a, mine[r]] = o_c[r].astype(o_ref.dtype)
        return carry

    lax.fori_loop(0, block // group, one, 0)


def _specs(block, chunk, repeat, dk, dv, at):
    """The block specs of q, k, v, g and beta; ``at`` maps the grid's last
    index to a block of the sequence."""
    rows = block * chunk
    key = pl.BlockSpec((1, rows, dk), lambda b, h, i: (b, at(i), h))
    value = pl.BlockSpec((1, rows, repeat * dv),
                         lambda b, h, i: (b, at(i), h))
    gate = pl.BlockSpec((1, repeat, block, chunk),
                        lambda b, h, i: (b, h, at(i), 0))
    return [key, key, value, gate, gate]


def _params():
    return tpu_compiler_params(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def _fwd_impl(q, k, v, g, beta, chunk, block, group, dk, dv, save_states,
              interpret):
    """q, k ``[B, T, Hk * dk]``, v ``[B, T, Hv * dv]``, g and beta ``[B,
    Hv, N, C]`` float32 (T = N C, N a multiple of ``block``) -> ``o`` like
    v and, with ``save_states``, what the backward kernel starts from: the
    state every ``group`` chunks start from, ``[B, Hv, N / group, dk, dv]``
    float32, and every stack's inverse, ``[B, Hk, stacks, M, M]``
    float32."""
    B, hv, N, _ = g.shape
    hk = q.shape[2] // dk
    repeat = hv // hk
    stacks = _stacks(group, repeat, chunk)
    rows = len(stacks[0]) * chunk
    out_specs = [pl.BlockSpec((1, block * chunk, repeat * dv),
                              lambda b, h, i: (b, i, h))]
    out_shape = [jax.ShapeDtypeStruct(v.shape, v.dtype)]
    if save_states:
        out_specs.append(pl.BlockSpec((1, repeat, block // group, dk, dv),
                                      lambda b, h, i: (b, h, i, 0, 0)))
        out_shape.append(
            jax.ShapeDtypeStruct((B, hv, N // group, dk, dv), F32))
        out_specs.append(pl.BlockSpec(
            (1, 1, block // group * len(stacks), rows, rows),
            lambda b, h, i: (b, h, i, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct(
            (B, hk, N // group * len(stacks), rows, rows), F32))
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, block=block,
                          group=group, repeat=repeat, dv=dv,
                          save_states=save_states),
        name="delta_rule_fwd",
        grid=(B, hk, N // block),
        in_specs=_specs(block, chunk, repeat, dk, dv, lambda i: i),
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((repeat, dk, dv), F32),
                        pltpu.VMEM((repeat, block, chunk), F32)],
        compiler_params=_params(),
        interpret=interpret,
    )(q, k, v, g, beta)
    return out if save_states else out[0]


# ---------------------------------------------------------------- backward
def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, states_ref,
                inverses_ref, do_ref, dq_ref, dk_ref, dv_ref, dg_ref,
                dbeta_ref, dS_ref, G_ref, *, chunk, block, group, repeat,
                dv):
    heads = range(repeat)
    mine = [slice(r * dv, (r + 1) * dv) for r in heads]
    masks = _stack_masks(group, repeat, chunk)
    stacks = len(_stacks(group, repeat, chunk))
    f32 = lambda x: x.astype(F32)

    @pl.when(pl.program_id(2) == 0)      # the sequence's last block
    def _():
        dS_ref[...] = jnp.zeros_like(dS_ref)

    for r in heads:
        G_ref[r] = _running_sums(g_ref[0, r])

    # from the block's last chunks to its first, carrying dL/dS as the
    # chunks after left it
    def one(i, carry):
        n = block // group - 1 - i
        at, of_chunks = _loads(n * group, group, chunk, heads, mine, q_ref,
                               k_ref, v_ref, G_ref, beta_ref, f32)
        saved = iter([inverses_ref[0, 0, n * stacks + i]
                      for i in range(stacks)])
        rule = functools.partial(
            _chunks, dt=q_ref.dtype, masks=masks,
            inverse=lambda A, masks: _saved_inverse(A, next(saved)))
        _, pull = jax.vjp(rule, *of_chunks,
                          tuple(states_ref[0, r, n] for r in heads))
        dq, dk, dv_, dG, dbeta, dS = pull(
            (tuple(tuple(f32(do_ref[0, a, mine[r]]) for r in heads)
                   for a in at), tuple(dS_ref[r] for r in heads)))
        for j, a in enumerate(at):
            dq_ref[0, a, :] = dq[j].astype(dq_ref.dtype)
            dk_ref[0, a, :] = dk[j].astype(dk_ref.dtype)
            here = pl.ds(n * group + j, 1)
            for r in heads:
                dv_ref[0, a, mine[r]] = dv_[j][r].astype(dv_ref.dtype)
                dg_ref[0, r, here, :] = dG[j][r]    # of the running sums
                dbeta_ref[0, r, here, :] = dbeta[j][r]
        for r in heads:
            dS_ref[r] = dS[r]
        return carry

    lax.fori_loop(0, block // group, one, 0)
    # g reaches every running sum from its own token on
    row, col = _iota(chunk)
    for r in heads:
        dg_ref[0, r] = _mm32(dg_ref[0, r], (row >= col).astype(F32))


def _bwd_impl(q, k, v, g, beta, states, inverses, do, chunk, block, group,
              dk, dv, interpret):
    """``(dq, dk, dv, dg, dbeta)`` in the layouts of their primals."""
    B, hv, N, _ = g.shape
    hk = q.shape[2] // dk
    repeat, n_b = hv // hk, N // block
    last = lambda i: n_b - 1 - i                        # from the end
    specs = _specs(block, chunk, repeat, dk, dv, last)
    key, _, value, gate, _ = specs
    return pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, block=block,
                          group=group, repeat=repeat, dv=dv),
        name="delta_rule_bwd",
        grid=(B, hk, n_b),
        in_specs=specs + [
            pl.BlockSpec((1, repeat, block // group, dk, dv),
                         lambda b, h, i: (b, h, last(i), 0, 0)),
            pl.BlockSpec((1, 1) + (inverses.shape[2] // n_b,)
                         + inverses.shape[3:],
                         lambda b, h, i: (b, h, last(i), 0, 0)), value],
        out_specs=[key, key, value, gate, gate],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(g.shape, F32),
                   jax.ShapeDtypeStruct(beta.shape, F32)],
        scratch_shapes=[pltpu.VMEM((repeat, dk, dv), F32),
                        pltpu.VMEM((repeat, block, chunk), F32)],
        compiler_params=_params(),
        interpret=interpret,
    )(q, k, v, g, beta, states, inverses, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _rule_core(q, k, v, g, beta, static):
    return _fwd_impl(q, k, v, g, beta, *static[:-1], False, static[-1])


def _rule_fwd(q, k, v, g, beta, static):
    o, states, inverses = _fwd_impl(q, k, v, g, beta, *static[:-1], True,
                                    static[-1])
    return o, (q, k, v, g, beta, states, inverses)


def _rule_bwd(static, res, do):
    q, k, v, g, beta, states, inverses = res
    return _bwd_impl(q, k, v, g, beta, states, inverses,
                     do.astype(v.dtype), *static)


_rule_core.defvjp(_rule_fwd, _rule_bwd)


def gated_delta_rule(q, k, v, g, beta, chunk: int = 64,
                     block: Optional[int] = None,
                     group: Optional[int] = None,
                     interpret: Optional[bool] = None):
    """The rule by the kernel pair; differentiable in q, k, v, g and beta.
    q, k ``[B, T, Hk, dk]`` **at the key heads** (value head ``h`` reads key
    head ``h // (Hv / Hk)``, what ``jnp.repeat`` along the heads gives), v
    ``[B, T, Hv, dv]``, g and beta ``[B, T, Hv]`` float32 -> o ``[B, T, Hv,
    dv]`` in v's type.  ``block`` (the chunks a grid step) comes from
    :func:`tiles` where it is not given (tests give small ones); a shape
    that the kernels do not take is an error here — the caller asks
    :func:`tiles` first.  The sequence is padded to whole blocks and the
    heads to :func:`widths`, with zeros, outside the ``custom_vjp``: the
    cotangents' slices and pads are autodiff's."""
    B, T, hk, dk = q.shape
    hv, dv = v.shape[2:]
    fit = tiles(q.shape, v.shape, chunk, v.dtype)
    if not fit or k.shape != q.shape or q.dtype != v.dtype:
        raise ValueError(
            f"no tiles for q {q.shape}, v {v.shape} in {v.dtype} at a chunk "
            f"of {chunk}: widths must be at least {LANES // 4}, the chunk a "
            f"multiple of 16 up to {LANES}, the value heads a multiple of "
            "the key heads")
    dk_run, dv_run = widths(dk, dv)
    block = block or fit
    group = group or next(n for n in range(min(GROUP, block), 0, -1)
                          if block % n == 0)
    if block % group:
        raise ValueError(f"a block of {block} chunks in groups of {group}")
    interpret = _interpret_default() if interpret is None else interpret
    pad = (-T) % (chunk * block)
    N = (T + pad) // chunk

    def rows(x, width):     # [B, T, H, d] -> [B, T + pad, H * width]
        if width != x.shape[3]:
            x = jnp.pad(x, ((0, 0),) * 3 + ((0, width - x.shape[3]),))
        return jnp.pad(x.reshape(B, T, -1), ((0, 0), (0, pad), (0, 0)))

    def gates(x):           # [B, T, H] -> [B, H, N, C] float32
        x = jnp.pad(x.astype(F32), ((0, 0), (0, pad), (0, 0)))
        return jnp.moveaxis(x, 1, 2).reshape(B, hv, N, chunk)

    o = _rule_core(rows(q, dk_run), rows(k, dk_run), rows(v, dv_run),
                   gates(g), gates(beta),
                   (chunk, block, group, dk_run, dv_run, interpret))
    o = o[:, :T].reshape(B, T, hv, dv_run)
    return o if dv_run == dv else o[..., :dv]
