"""The selective scan of a Mamba-1 layer (Gu and Dao, "Mamba: Linear-Time
Sequence Modeling with Selective State Spaces"): a recurrence with a decay
of its own for every (channel, state) pair, told its sizes and never which
model it serves.

``selective_scan(x, delta, A, B, C, D)``: x and delta ``[b, T, d]``, A
``[d, n]`` (``<= 0``), B and C ``[b, T, n]``, D ``[d]``; for every channel
``c`` and state ``s``

    h_t[c, s] = exp(delta_t[c] A[c, s]) h_{t-1}[c, s]
                + delta_t[c] B_t[s] x_t[c]                      (h_0 = 0)
    y_t[c]    = sum_s C_t[s] h_t[c, s] + D[c] x_t[c]

with the state in float32 and ``y`` in x's type.  ``exp(delta_t[c] A[c,
s])`` does not factor into a scalar a head, so the chunked matrix form of
``models/mamba2.py`` cannot compute it: it is a scan.

One algorithm, two implementations chosen at trace time from what can be
observed (``ops/causal_conv.py``'s template):

- **the kernels**, on a TPU where :func:`tiles` fits the shape (d in 128s,
  T in 128s, a state of 8 to 32 in 8s).  Forward: grid ``(b, d / tile_c,
  T / 128)`` with the time axis walked in order, ``h [n, tile_c]`` float32
  carried in VMEM scratch, channels on the lanes and states on the
  sublanes; x and delta are read once, ``y`` is written once, and the
  state each block of 128 tokens starts from is written out for the
  backward pass (``[b, T / 128, n, d]`` float32: 21 MB a layer at 8192
  tokens and 5120 channels).  Backward: time from the end;
  a block recomputes its 128 states from the saved one into VMEM, walks
  them backwards carrying ``dh``, writes ``dx`` and ``ddelta`` once, ``dB``
  and ``dC`` as one partial a channel tile (time on the lanes; summed and
  transposed outside), and keeps ``dA`` and ``dD`` as float32 sums in
  output blocks that stay in VMEM along the time axis, summed over the
  batch and rounded outside.  B and C reach the kernels with every state's
  value repeated over 128 lanes (``[b, T, n, 128]``, made outside by a
  broadcast), so that a token's ``[n, 128]`` tile is one load and nothing
  is moved across lanes inside; their block does not depend on the channel
  tile and the time axis is the grid's innermost, so every channel tile
  fetches them again (ten times at 5120 channels: two thirds of the bytes
  a pass moves; docs/performance.md "The selective scan").
- **the plain path**, everywhere else (the CPU tests and rehearsals, a
  shape no tile fits): a ``lax.scan`` over chunks of :data:`CHUNK` tokens
  that carries ``h``, each chunk walked token by token under
  ``jax.checkpoint``, so that the backward pass keeps one state a chunk
  and not one a token; some channels at a time where the sequence is long.
  Its backward pass is autodiff's.

Only products ``delta A <= 0`` are exponentiated and nothing is divided by
a decay (``models/mamba2.py``'s rule).  ``trace.selective_scan`` counts the
call sites of each path.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import trace
from ..compat import tpu_compiler_params

# tokens of the plain path walked under one ``jax.checkpoint``
CHUNK = 64
# (token, channel) pairs of the plain path computed together: 1024 of 5120
# channels at 8192 tokens, every channel at once up to 1024 tokens
TOKEN_CHANNELS = 1 << 23
# tokens of a kernel block: the backward kernel writes ``dB`` and ``dC``
# with time on the lanes, a token a lane
TILE_T = 128
MAX_TILE_C = 512
# float32 bytes of the states a backward block keeps in VMEM
MAX_STATE_BYTES = 4 << 20
MAX_STATE = 32
# B and C are repeated over this many lanes for the kernels
LANES = 128
# tokens a trip of the kernels' loops: a layer's forward and backward at
# 8192 tokens and 5120 channels took 16.1, 10.7, 9.0 and 8.1 ms at 1, 2, 4
# and 8 (my chip run, PR 43)
UNROLL = 8


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def kernel_enabled() -> bool:
    """Whether :func:`tiles` is worth asking: on a TPU.  Read at trace
    time, as ``causal_conv.kernel_enabled`` is."""
    return jax.default_backend() == "tpu"


def tiles(shape, state: int) -> Optional[int]:
    """``tile_c`` for x of ``shape [b, T, d]`` and a state of ``state``:
    the widest block of up to 512 lanes that divides d in multiples of 128
    and whose 128 states fit :data:`MAX_STATE_BYTES`; ``None`` where there
    is none, T is no multiple of 128 or the state no multiple of 8 up to
    32."""
    if (len(shape) != 3 or shape[1] % TILE_T or state % 8
            or not 8 <= state <= MAX_STATE):
        return None
    most = min(MAX_TILE_C, MAX_STATE_BYTES // (4 * TILE_T * state))
    return next((t for t in range(most // LANES * LANES, 0, -LANES)
                 if shape[2] % t == 0), None)


# -------------------------------------------------------------- plain path
def plain_selective_scan(x, delta, A, B, C, D, chunk: int = CHUNK,
                         token_channels: int = TOKEN_CHANNELS):
    """The recurrence as XLA's own code: a scan over chunks of ``chunk``
    tokens, each chunk token by token and recomputed in the backward pass.
    ``T`` need not be a multiple of ``chunk``: a padding token has ``delta
    = 0``, which decays nothing and writes nothing.  Channels are
    independent, so where ``b * T * d`` exceeds ``token_channels`` the
    channels are taken some at a time (a ``lax.map`` over equal parts, each
    recomputed in the backward pass: ``mamba2.by_state_groups``' reason):
    the float32 copies of x, delta and y and their cotangents are then a
    part's."""
    b, T, d = x.shape
    fit = max(1, token_channels // (b * T))
    width = max(w for w in range(1, d + 1) if d % w == 0 and w <= fit)
    if width == d:
        return _plain_channels(x, delta, A, B, C, D, chunk)
    parts = lambda v, axis: jnp.moveaxis(
        v.reshape(v.shape[:axis] + (d // width, width) + v.shape[axis + 1:]),
        axis, 0)
    one = jax.checkpoint(lambda x, delta, A, D: _plain_channels(
        x, delta, A, B, C, D, chunk))
    y = lax.map(lambda a: one(*a), (parts(x, 2), parts(delta, 2),
                                    parts(A, 0), parts(D, 0)))
    return jnp.moveaxis(y, 0, 2).reshape(b, T, d)


def _plain_channels(x, delta, A, B, C, D, chunk):
    b, T, d = x.shape
    f32 = jnp.float32
    pad = (-T) % chunk
    At = A.astype(f32).T                                    # [n, d]

    def chunks(v):          # [b, T, w] -> [T / chunk, chunk, b, w] float32
        v = jnp.pad(v.astype(f32), ((0, 0), (0, pad), (0, 0)))
        return jnp.moveaxis(v, 1, 0).reshape(-1, chunk, b, v.shape[-1])

    def token(h, of_token):     # h [b, n, d]: channels minor, as the kernels
        x_t, delta_t, B_t, C_t = of_token
        h = jnp.exp(delta_t[:, None] * At) * h + (
            (delta_t * x_t)[:, None] * B_t[..., None])
        return h, jnp.sum(h * C_t[..., None], axis=1)

    walk = jax.checkpoint(lambda h, xs: lax.scan(token, h, xs))
    _, y = lax.scan(walk, jnp.zeros((b, A.shape[1], d), f32),
                    tuple(chunks(v) for v in (x, delta, B, C)))
    y = jnp.moveaxis(y.reshape(-1, b, d), 0, 1)[:, :T]
    return (y + D.astype(f32) * x.astype(f32)).astype(x.dtype)


# ----------------------------------------------------------------- kernels
def _row(ref, t):
    """Row ``t`` of a float32 ``[TILE_T, tile_c]`` block, ``[1, tile_c]``."""
    return ref[pl.ds(t, 1), :]


def _over_lanes(tile, tile_c):
    """A token's ``[n, 128]`` tile of B or C at the block's width."""
    tile = tile.astype(jnp.float32)
    return tile if tile_c == LANES else jnp.concatenate(
        [tile] * (tile_c // LANES), axis=1)


def _walk(token, carry, unroll):
    """``token(t, carry)`` for the block's 128 tokens in order, ``unroll``
    of them a trip of the loop (Mosaic's own ``unroll`` is all or one)."""
    def trip(g, carry):
        for j in range(unroll):
            carry = token(g * unroll + j, carry)
        return carry
    return lax.fori_loop(0, TILE_T // unroll, trip, carry)


def _fwd_kernel(x_ref, dl_ref, at_ref, b_ref, c_ref, d_ref,
                y_ref, hs_ref, h_ref, xf_ref, yf_ref, *, tile_c, unroll):
    f32 = jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _():
        h_ref[...] = jnp.zeros_like(h_ref)

    hs_ref[0, 0] = h_ref[...]           # what the block starts from
    at = at_ref[...]
    xf_ref[...] = x_ref[0].astype(f32)

    def token(t, h):
        x_t, delta_t = _row(xf_ref, t), dl_ref[0, pl.ds(t, 1), :]
        h = jnp.exp(delta_t * at) * h + (delta_t * x_t) * _over_lanes(
            b_ref[0, t], tile_c)
        yf_ref[pl.ds(t, 1), :] = jnp.sum(
            h * _over_lanes(c_ref[0, t], tile_c), axis=0, keepdims=True)
        return h

    h_ref[...] = _walk(token, h_ref[...], unroll)
    y_ref[0] = (yf_ref[...] + d_ref[...].astype(f32) * xf_ref[...]).astype(
        y_ref.dtype)


def _bwd_kernel(x_ref, dl_ref, at_ref, b_ref, c_ref, d_ref, hs_ref, dy_ref,
                dx_ref, ddl_ref, db_ref, dc_ref, da_ref, dd_ref,
                dh_ref, st_ref, xf_ref, dyf_ref, dxf_ref, *, tile_c, unroll):
    f32 = jnp.float32
    n = at_ref.shape[0]

    @pl.when(pl.program_id(2) == 0)      # the sequence's last block
    def _():
        dh_ref[...] = jnp.zeros_like(dh_ref)
        da_ref[...] = jnp.zeros_like(da_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    at = at_ref[...]
    xf_ref[...] = x_ref[0].astype(f32)
    dyf_ref[...] = dy_ref[0].astype(f32)

    # the block's states again: st[t] is what token t starts from
    def again(t, h):
        st_ref[t] = h
        x_t, delta_t = _row(xf_ref, t), dl_ref[0, pl.ds(t, 1), :]
        return jnp.exp(delta_t * at) * h + (delta_t * x_t) * _over_lanes(
            b_ref[0, t], tile_c)

    h_last = _walk(again, hs_ref[0, 0], unroll)
    lane = lax.broadcasted_iota(jnp.int32, (n, TILE_T), 1)

    # from the block's last token to its first, carrying dL/dh_t as the
    # tokens after t left it and h_t itself
    def back(k, carry):
        dh, h, db, dc = carry
        t = TILE_T - 1 - k
        before = st_ref[t]
        x_t, delta_t = _row(xf_ref, t), dl_ref[0, pl.ds(t, 1), :]
        dy_t = _row(dyf_ref, t)
        b_t = _over_lanes(b_ref[0, t], tile_c)
        dh = dh + _over_lanes(c_ref[0, t], tile_c) * dy_t
        dc = jnp.where(lane == t, jnp.sum(h * dy_t, axis=1, keepdims=True),
                       dc)
        db = jnp.where(lane == t, jnp.sum(dh * (delta_t * x_t), axis=1,
                                          keepdims=True), db)
        through = dh * jnp.exp(delta_t * at)        # dL/dh_{t-1}
        held = through * before
        da_ref[0] += held * delta_t
        of_b = jnp.sum(dh * b_t, axis=0, keepdims=True)
        ddl_ref[0, pl.ds(t, 1), :] = (
            jnp.sum(held * at, axis=0, keepdims=True) + of_b * x_t)
        dxf_ref[pl.ds(t, 1), :] = of_b * delta_t
        return through, before, db, dc

    zeros = jnp.zeros((n, TILE_T), f32)
    dh, _, db, dc = _walk(back, (dh_ref[...], h_last, zeros, zeros), unroll)
    dh_ref[...] = dh
    db_ref[0, 0] = db
    dc_ref[0, 0] = dc
    dx_ref[0] = (dxf_ref[...] + d_ref[...].astype(f32) * dyf_ref[...]).astype(
        dx_ref.dtype)
    skip = dyf_ref[...] * xf_ref[...]
    dd_ref[0] += sum(skip[r:r + 8] for r in range(0, TILE_T, 8))


def _over_lane_tiles(v):
    """B or C ``[b, T, n]`` with every value repeated over 128 lanes."""
    return jnp.broadcast_to(v[..., None], v.shape + (LANES,))


def _specs(tile_c, n, block):
    """The block specs of x, delta, ``A^T``, B, C and D; ``block`` maps the
    grid's time index to a block of the sequence."""
    wide = pl.BlockSpec((1, TILE_T, tile_c),
                        lambda b, c, i: (b, block(i), c))
    tile = pl.BlockSpec((1, TILE_T, n, LANES),
                        lambda b, c, i: (b, block(i), 0, 0))
    return [wide, wide, pl.BlockSpec((n, tile_c), lambda b, c, i: (0, c)),
            tile, tile, pl.BlockSpec((1, tile_c), lambda b, c, i: (0, c))]


def _params():
    return tpu_compiler_params(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def _fwd_impl(x, delta, At, Bl, Cl, D, tile_c, unroll, interpret):
    """``(y, hs)``: ``hs [b, T / 128, n, d]`` float32 holds the state every
    block starts from."""
    b, T, d = x.shape
    n, n_t, f32 = At.shape[0], T // TILE_T, jnp.float32
    return pl.pallas_call(
        functools.partial(_fwd_kernel, tile_c=tile_c, unroll=unroll),
        name="selective_scan_fwd",
        grid=(b, d // tile_c, n_t),
        in_specs=_specs(tile_c, n, lambda i: i),
        out_specs=[
            pl.BlockSpec((1, TILE_T, tile_c), lambda b, c, i: (b, i, c)),
            pl.BlockSpec((1, 1, n, tile_c), lambda b, c, i: (b, i, 0, c)),
        ],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((b, n_t, n, d), f32)],
        scratch_shapes=[pltpu.VMEM((n, tile_c), f32),
                        pltpu.VMEM((TILE_T, tile_c), f32),
                        pltpu.VMEM((TILE_T, tile_c), f32)],
        compiler_params=_params(),
        interpret=interpret,
    )(x, delta, At, Bl, Cl, D.reshape(1, d))


def _bwd_impl(x, delta, At, Bl, Cl, D, hs, dy, tile_c, unroll, interpret):
    """``(dx, ddelta, dB^T, dC^T, dA^T, dD)``: ``dB^T`` and ``dC^T`` ``[b, d
    / tile_c, n, T]`` float32, a partial a channel tile; ``dA^T [b, n, d]``
    and ``dD [b, 8, d]`` float32, a sequence's sums (the second as eight
    partial sums, a row of the sublanes each)."""
    b, T, d = x.shape
    n, n_t, n_c, f32 = At.shape[0], T // TILE_T, d // tile_c, jnp.float32
    last = lambda i: n_t - 1 - i                        # from the end
    wide = pl.BlockSpec((1, TILE_T, tile_c),
                        lambda b, c, i: (b, last(i), c))
    partial = pl.BlockSpec((1, 1, n, TILE_T),
                           lambda b, c, i: (b, c, 0, last(i)))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, tile_c=tile_c, unroll=unroll),
        name="selective_scan_bwd",
        grid=(b, n_c, n_t),
        in_specs=_specs(tile_c, n, last) + [
            pl.BlockSpec((1, 1, n, tile_c),
                         lambda b, c, i: (b, last(i), 0, c)), wide],
        out_specs=[
            wide, wide, partial, partial,
            pl.BlockSpec((1, n, tile_c), lambda b, c, i: (b, 0, c)),
            pl.BlockSpec((1, 8, tile_c), lambda b, c, i: (b, 0, c)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct(x.shape, f32),
            jax.ShapeDtypeStruct((b, n_c, n, T), f32),
            jax.ShapeDtypeStruct((b, n_c, n, T), f32),
            jax.ShapeDtypeStruct((b, n, d), f32),
            jax.ShapeDtypeStruct((b, 8, d), f32),
        ],
        scratch_shapes=[pltpu.VMEM((n, tile_c), f32),
                        pltpu.VMEM((TILE_T, n, tile_c), f32),
                        pltpu.VMEM((TILE_T, tile_c), f32),
                        pltpu.VMEM((TILE_T, tile_c), f32),
                        pltpu.VMEM((TILE_T, tile_c), f32)],
        compiler_params=_params(),
        interpret=interpret,
    )(x, delta, At, Bl, Cl, D.reshape(1, d), hs, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _scan_core(x, delta, A, B, C, D, tile_c, unroll, interpret):
    return _scan_fwd(x, delta, A, B, C, D, tile_c, unroll, interpret)[0]


def _scan_fwd(x, delta, A, B, C, D, tile_c, unroll, interpret):
    y, hs = _fwd_impl(x, delta.astype(jnp.float32), A.astype(jnp.float32).T,
                      _over_lane_tiles(B), _over_lane_tiles(C), D, tile_c,
                      unroll, interpret)
    return y, (x, delta, A, B, C, D, hs)


def _scan_bwd(tile_c, unroll, interpret, res, dy):
    x, delta, A, B, C, D, hs = res
    dx, ddelta, dB, dC, dA, dD = _bwd_impl(
        x, delta.astype(jnp.float32), A.astype(jnp.float32).T,
        _over_lane_tiles(B), _over_lane_tiles(C), D, hs, dy.astype(x.dtype),
        tile_c, unroll, interpret)
    of_token = lambda g, like: jnp.swapaxes(jnp.sum(g, axis=1), 1, 2).astype(
        like.dtype)
    return (dx, ddelta.astype(delta.dtype),
            jnp.sum(dA, axis=0).T.astype(A.dtype), of_token(dB, B),
            of_token(dC, C), jnp.sum(dD, axis=(0, 1)).astype(D.dtype))


_scan_core.defvjp(_scan_fwd, _scan_bwd)


def kernel_selective_scan(x, delta, A, B, C, D, tile_c: Optional[int] = None,
                          unroll: int = UNROLL,
                          interpret: Optional[bool] = None):
    """The recurrence by the kernel pair; differentiable in every argument.
    ``tile_c`` comes from :func:`tiles` where it is not given (tests give a
    small one); a shape that no tile fits is an error here — the caller
    asks :func:`tiles` first."""
    n = A.shape[1]
    widest = tiles(x.shape, n)
    tile_c = tile_c or widest
    if (not widest or tile_c % LANES or x.shape[2] % tile_c
            or tile_c > widest):
        raise ValueError(
            f"no tile for x {x.shape} with a state of {n}: T must be a "
            f"multiple of {TILE_T}, d of a tile of {LANES}s, the state a "
            f"multiple of 8 up to {MAX_STATE}")
    interpret = _interpret_default() if interpret is None else interpret
    return _scan_core(x, delta, A, B, C, D, tile_c, unroll, interpret)


# -------------------------------------------------------------- dispatcher
def selective_scan(x, delta, A, B, C, D):
    """``y [b, T, d]`` in x's type (the module's docstring has the
    equations): the kernel pair on a TPU where :func:`tiles` fits the
    shape, the plain path everywhere else.  ``trace.selective_scan``
    counts the call sites of each."""
    if kernel_enabled() and tiles(x.shape, A.shape[1]):
        trace.selective_scan["kernel"] += 1         # Python: once a trace
        return kernel_selective_scan(x, delta, A, B, C, D)
    trace.selective_scan["plain"] += 1
    return plain_selective_scan(x, delta, A, B, C, D)
