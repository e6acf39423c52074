"""Pallas TPU kernels for the recurrent mixers' causal convolution.

``SiLU(depthwise causal conv(x) + bias)`` for x ``[B, T, C]`` and a
``kernel [taps, C]`` (tap ``j`` weighs the input ``taps - 1 - j`` back), in
one pass over the input a direction.  XLA's code for the plain formulation
(``models/gated_delta.py`` ``causal_conv_silu``) writes the padded input
and one float32 ``[B, T, C]`` a tap to HBM and reads them back: each
shifted window is another operand of its fusion, and the transpose of a
slice is a pad.  A kernel that holds a ``[tile_t + 8, tile_c]`` block in
VMEM takes the windows as slices of it:

- **forward**: grid ``(B, C / tile_c, T / tile_t)``, the T axis walked in
  order; the last eight float32 rows of a block are carried in scratch to
  the next (zeros before position 0 of every sequence), ``y`` is written
  once in x's type.  Nothing float32 reaches HBM, and the only residuals
  are the arguments.
- **backward**: the T axis walked from the end.  A block reads ``x`` with
  the sixteen rows before it and ``dy``, recomputes the pre-activation,
  forms ``dpre = dy * silu'(pre)``, carries its first eight rows back to
  the block before (whose ``dx`` needs them), writes ``dx`` once, and adds
  its float32 column sums ``sum_t dpre[t] * x[t - (taps - 1 - j)]`` and
  ``sum_t dpre[t]`` to an output block that stays in VMEM along the T
  axis: one float32 row a tap and one for the bias a sequence, summed over
  the batch and rounded once outside the kernel.

Float32 inside, x's type out: the rounding points of the plain formulation.
On non-TPU backends the kernels run in Pallas interpret mode (tests);
``causal_conv_silu`` in ``models/gated_delta.py`` routes here on a TPU
where :func:`tiles` finds the shape a fit and keeps the plain formulation
elsewhere.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..compat import tpu_compiler_params

# float32 rows carried from a block to the next: a sublane tile, so the
# carried rows and the block concatenate on a tile's edge
CARRY = 8
# rows of x read again before a block in the backward pass: a sublane tile
# of a 16-bit type
HALO = 16
MAX_TAPS = CARRY
# the largest block: 512 lanes wide and a MiB of x's type (1024 rows of
# bfloat16, 512 of float32), which keeps the backward kernel's six buffers
# (x, dy and dx, each twice) well inside the scoped VMEM of a v5e
MAX_TILE_C = 512
MAX_TILE_BYTES = 1 << 20
# rows the kernels work on at a time inside a block: at 512 lanes a
# float32 intermediate is then 16 vector registers
CHUNK = 32


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def kernel_enabled() -> bool:
    """Whether :func:`tiles` is worth asking: on a TPU.  Read at trace
    time, as ``flash_enabled`` is."""
    return jax.default_backend() == "tpu"


def _largest_tile(n: int, unit: int, most: int) -> Optional[int]:
    return next((t for t in range(min(most, n) // unit * unit, 0, -unit)
                 if n % t == 0), None)


def tiles(shape, taps: int, dtype) -> Optional[tuple]:
    """``(tile_t, tile_c)`` for x of ``shape [B, T, C]`` and ``dtype``:
    the widest block up to 512 lanes that divides C in multiples of 128,
    and the longest up to a MiB that divides T in multiples of 16 rows;
    ``None`` where there are none or the taps outnumber the carried
    rows."""
    if len(shape) != 3 or not 1 <= taps <= MAX_TAPS:
        return None
    tile_c = _largest_tile(shape[2], 128, MAX_TILE_C)
    if not tile_c:
        return None
    tile_t = _largest_tile(
        shape[1], HALO, MAX_TILE_BYTES // (tile_c * jnp.dtype(dtype).itemsize))
    return (tile_t, tile_c) if tile_t else None


def _windows(rows, taps, n, first):
    """The ``taps`` windows of ``n`` rows of ``rows``, window ``j``
    starting at row ``first + j``: a roll along the sublanes and a slice
    on a tile's edge (the rows that wrap land outside the slice), which
    the chip does faster than the slice that starts inside a tile."""
    m = rows.shape[0]
    found = []
    for start in range(first, first + taps):
        edge = start // 8 * 8
        assert start + n <= m
        shifted = rows if start == edge else pltpu.roll(
            rows, m - (start - edge), axis=0)
        found.append(shifted[edge:edge + n])
    return found


def _pre_activation(before, x, k, b, taps):
    """``conv(x) + bias`` in float32 for the rows ``x [n, tile_c]`` and
    the :data:`CARRY` rows ``before`` them, and the taps' windows of the
    input (``windows[j][t] = x[t - (taps - 1 - j)]``)."""
    windows = _windows(jnp.concatenate([before, x], axis=0), taps,
                       x.shape[0], CARRY - (taps - 1))
    pre = sum(k[j:j + 1] * windows[j] for j in range(taps))
    return (pre if b is None else pre + b), windows


def _chunk_rows(c, chunk):
    return pl.ds(pl.multiple_of(c * chunk, chunk), chunk)


# ----------------------------------------------------------------- forward
def _fwd_kernel(*refs, taps, tile_t, chunk, has_bias):
    x_ref, k_ref = refs[:2]
    y_ref, carry_ref = refs[-2:]
    f32 = jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    k = k_ref[...].astype(f32)
    b = refs[2][...].astype(f32) if has_bias else None

    # a chunk of rows at a time, so that a chunk's float32 intermediates
    # stay in registers; the chunk's last rows go to the next as its first
    def rows(c, before):
        at = _chunk_rows(c, chunk)
        x = x_ref[0, at, :].astype(f32)
        pre, _ = _pre_activation(before, x, k, b, taps)
        y_ref[0, at, :] = (pre * jax.nn.sigmoid(pre)).astype(y_ref.dtype)
        return x[chunk - CARRY:]

    carry_ref[...] = jax.lax.fori_loop(0, tile_t // chunk, rows,
                                       carry_ref[...])


def _fwd_impl(x, kernel, bias, tile_t, tile_c, chunk, interpret):
    B, T, C = x.shape
    taps = kernel.shape[0]
    operands = [x, kernel] + ([] if bias is None else [bias.reshape(1, C)])
    in_specs = [
        pl.BlockSpec((1, tile_t, tile_c), lambda b, c, i: (b, i, c)),
        pl.BlockSpec((taps, tile_c), lambda b, c, i: (0, c)),
    ] + ([] if bias is None else
         [pl.BlockSpec((1, tile_c), lambda b, c, i: (0, c))])
    return pl.pallas_call(
        functools.partial(_fwd_kernel, taps=taps, tile_t=tile_t,
                          chunk=chunk, has_bias=bias is not None),
        name="causal_conv_fwd",
        grid=(B, C // tile_c, T // tile_t),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, tile_t, tile_c),
                               lambda b, c, i: (b, i, c)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((CARRY, tile_c), jnp.float32)],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*operands)


# ---------------------------------------------------------------- backward
def _bwd_kernel(*refs, taps, tile_t, chunk, has_bias):
    x_ref, halo_ref, dy_ref, k_ref = refs[:4]
    dx_ref, sums_ref, carry_ref = refs[-3:]
    f32 = jnp.float32
    i = pl.program_id(2)                # 0 is the sequence's last block
    first_block = i == pl.num_programs(2) - 1
    n_chunks = tile_t // chunk

    @pl.when(i == 0)
    def _():
        carry_ref[...] = jnp.zeros_like(carry_ref)
        sums_ref[...] = jnp.zeros_like(sums_ref)

    k = k_ref[...].astype(f32)
    b = refs[4][...].astype(f32) if has_bias else None
    # the rows before the block; zeros before position 0 (there the halo's
    # index is clamped to the block itself)
    halo = jnp.where(first_block, 0.0,
                     halo_ref[0].astype(f32)[HALO - CARRY:])

    def by_sublane(p):          # [chunk, tile_c] -> [8, tile_c] partial sums
        return sum(p[r:r + 8] for r in range(0, chunk, 8))

    # from the block's last chunk to its first: a chunk's ``dx`` needs the
    # first rows of ``dpre`` of the chunk after it
    def rows(n, after):
        c = n_chunks - 1 - n
        at = _chunk_rows(c, chunk)
        x = x_ref[0, at, :].astype(f32)
        behind = x_ref[0, pl.ds(pl.multiple_of(
            jnp.maximum(c * chunk - HALO, 0), HALO), HALO), :].astype(f32)
        before = jnp.where(c == 0, halo, behind[HALO - CARRY:])
        pre, windows = _pre_activation(before, x, k, b, taps)
        sig = jax.nn.sigmoid(pre)
        dpre = dy_ref[0, at, :].astype(f32) * (
            sig * (1.0 + pre * (1.0 - sig)))
        # dx[t] = sum_j kernel[j] * dpre[t + taps - 1 - j]
        ahead = _windows(jnp.concatenate([dpre, after], axis=0), taps,
                         chunk, 0)
        dx_ref[0, at, :] = sum(k[j:j + 1] * ahead[taps - 1 - j]
                               for j in range(taps)).astype(dx_ref.dtype)
        for j in range(taps):
            sums_ref[0, j] += by_sublane(dpre * windows[j])
        sums_ref[0, taps] += by_sublane(dpre)
        return dpre[:CARRY]

    carry_ref[...] = jax.lax.fori_loop(0, n_chunks, rows, carry_ref[...])


def _bwd_impl(x, kernel, bias, dy, tile_t, tile_c, chunk, interpret):
    """``(dx, sums)``: ``sums [B, taps + 1, 8, C]`` float32 holds a
    sequence's ``dkernel`` a tap and then its ``dbias``, each as eight
    partial sums (a row of the sublanes each)."""
    B, T, C = x.shape
    taps = kernel.shape[0]
    n_t = T // tile_t
    block = lambda b, c, i: (b, n_t - 1 - i, c)         # from the end
    halo = lambda b, c, i: (
        b, jnp.maximum((n_t - 1 - i) * (tile_t // HALO) - 1, 0), c)
    operands = [x, x, dy, kernel] + (
        [] if bias is None else [bias.reshape(1, C)])
    in_specs = [
        pl.BlockSpec((1, tile_t, tile_c), block),
        pl.BlockSpec((1, HALO, tile_c), halo),
        pl.BlockSpec((1, tile_t, tile_c), block),
        pl.BlockSpec((taps, tile_c), lambda b, c, i: (0, c)),
    ] + ([] if bias is None else
         [pl.BlockSpec((1, tile_c), lambda b, c, i: (0, c))])
    return pl.pallas_call(
        functools.partial(_bwd_kernel, taps=taps, tile_t=tile_t,
                          chunk=chunk, has_bias=bias is not None),
        name="causal_conv_bwd",
        grid=(B, C // tile_c, n_t),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, tile_t, tile_c), block),
            pl.BlockSpec((1, taps + 1, 8, tile_c),
                         lambda b, c, i: (b, 0, 0, c)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((B, taps + 1, 8, C), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((CARRY, tile_c), jnp.float32)],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*operands)


# -------------------------------------------------------------- dispatcher
def causal_conv_silu(x, kernel, bias=None, tile_t: Optional[int] = None,
                     tile_c: Optional[int] = None,
                     chunk: Optional[int] = None,
                     interpret: Optional[bool] = None):
    """``SiLU(conv(x) + bias)`` by the kernel pair; differentiable in
    ``x``, ``kernel`` and ``bias``.  The tiles come from :func:`tiles`
    where they are not given (tests give small ones); a shape that no tile
    fits is an error here — the caller asks :func:`tiles` first."""
    taps = kernel.shape[0]
    fit = tiles(x.shape, taps, x.dtype)
    tile_t = tile_t or (fit and fit[0])
    tile_c = tile_c or (fit and fit[1])
    if (not tile_t or not tile_c or taps > MAX_TAPS
            or x.shape[1] % tile_t or tile_t % HALO
            or x.shape[2] % tile_c or tile_c % 128):
        raise ValueError(
            f"no tiles for x {x.shape} with {taps} taps: T must be a "
            f"multiple of a tile of {HALO}s, C of one of 128s, taps <= "
            f"{MAX_TAPS}")
    chunk = chunk or _largest_tile(tile_t, HALO, CHUNK)
    interpret = _interpret_default() if interpret is None else interpret
    return _conv_core(x, kernel, bias, tile_t, tile_c, chunk, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _conv_core(x, kernel, bias, tile_t, tile_c, chunk, interpret):
    return _fwd_impl(x, kernel, bias, tile_t, tile_c, chunk, interpret)


def _conv_fwd(x, kernel, bias, tile_t, tile_c, chunk, interpret):
    return (_fwd_impl(x, kernel, bias, tile_t, tile_c, chunk, interpret),
            (x, kernel, bias))


def _conv_bwd(tile_t, tile_c, chunk, interpret, res, dy):
    x, kernel, bias = res
    dx, sums = _bwd_impl(x, kernel, bias, dy.astype(x.dtype), tile_t,
                         tile_c, chunk, interpret)
    sums = jnp.sum(sums, axis=(0, 2))   # float32 over batch and sublanes
    taps = kernel.shape[0]
    return (dx, sums[:taps].astype(kernel.dtype),
            None if bias is None else sums[taps].astype(bias.dtype))


_conv_core.defvjp(_conv_fwd, _conv_bwd)
