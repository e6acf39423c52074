"""Pallas flash attention vs the jnp reference — values AND gradients, with
padding (T not a block multiple), causal and full (SURVEY.md §7 "pallas
kernels for the hot ops").  Runs in Pallas interpret mode on the CPU mesh;
the identical kernel compiles on TPU.
"""

import jax
import jax.export  # noqa: F401
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops.flash_attention import flash_attention
from horovod_tpu.parallel.ring_attention import local_flash_attention


def _value_and_grads(attend, q, k, v, w=None):
    """``attend(q, k, v)`` and the three gradients of the sum of its
    squares (of its products with ``w``, where given), as one program (op
    by op each is some twenty)."""
    def loss(*a):
        o = attend(*a)
        return jnp.sum(o ** 2 if w is None else o * w)
    return jax.jit(lambda q, k, v: (attend(q, k, v), jax.grad(
        loss, argnums=(0, 1, 2))(q, k, v)))(q, k, v)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("shape,blocks", [
    ((2, 70, 3, 16), (32, 32)),   # padded: 70 % 32 != 0
    ((1, 64, 2, 32), (32, 32)),   # exact multiple
    ((2, 33, 1, 8), (16, 16)),    # tiny + padding
    # keys and values of two widths (latent attention's 192 and 128, and a
    # small odd pair), T no multiple of the block
    ((1, 40, 2, 192, 128), (16, 16)),
    ((2, 33, 3, 24, 10), (16, 16)),
    ((1, 70, 2, 16, 40), (32, 16)),   # values wider than keys
])
def test_flash_matches_reference(shape, blocks, causal):
    B, T, H, D, *rest = shape
    Dv = rest[0] if rest else D
    bq, bk = blocks
    rng = np.random.RandomState(hash((shape, causal)) % (2**31))
    q = jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, T, H, Dv), jnp.float32)

    flash = lambda q, k, v: flash_attention(q, k, v, causal=causal,
                                            block_q=bq, block_k=bk)
    plain = lambda q, k, v: local_flash_attention(q, k, v, causal=causal)
    (out, gf), (ref, gr) = (_value_and_grads(f, q, k, v)
                            for f in (flash, plain))
    assert out.shape == (B, T, H, Dv)
    assert [g.shape[-1] for g in gf] == [D, D, Dv]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5, rtol=3e-5)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("D, Dv", [(16, 16), (24, 16)],
                         ids=["one-width", "keys24-values16"])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_gqa_matches_repeated(causal, D, Dv):
    """Native GQA (kv heads shared via block index maps) == materialized
    jnp.repeat, for values and all three gradients (dk/dv accumulate over
    the q-head group), with keys and values of one width and of two."""
    B, T, H, K = 2, 40, 4, 2
    rep = H // K
    rng = np.random.RandomState(7)
    q = jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, T, K, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, T, K, Dv), jnp.float32)

    flash = lambda q, k, v: flash_attention(q, k, v, causal=causal,
                                            block_q=16, block_k=16)
    repeated = lambda q, k, v: local_flash_attention(
        q, jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2),
        causal=causal)
    (out, gf), (ref, gr) = (_value_and_grads(f, q, k, v)
                            for f in (flash, repeated))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5, rtol=3e-5)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("T, blocks", [(64, (32, 32)), (70, (16, 32))],
                         ids=["t64", "t70-padded"])
def test_flash_twenty_query_heads_on_one_key_head(T, blocks):
    """20 query heads on ONE key-value head (``jamba``'s attention layer;
    the other families' kernels' tests go up to 16 a key head): values and
    all three gradients against the materialized repeat; ``flash_bwd_dkv``
    sums ``dk`` and ``dv`` over the 20 heads of the one group."""
    B, H, K, D = 2, 20, 1, 128
    rng = np.random.RandomState(43)
    q = jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, T, K, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, T, K, D), jnp.float32)
    w = jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
    flash = lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=blocks[0], block_k=blocks[1])
    plain = lambda q, k, v: local_flash_attention(
        q, jnp.repeat(k, H, axis=2), jnp.repeat(v, H, axis=2), causal=True)
    (out, gf), (ref, gr) = (_value_and_grads(f, q, k, v, w)
                            for f in (flash, plain))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5, rtol=3e-5)
    assert gf[1].shape == (B, T, K, D)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("H, window", [(9, 12), (6, 12), (6, None)],
                         ids=["9-to-1-window", "6-to-1-window", "6-to-1"])
def test_flash_nine_and_six_query_heads_a_key_head_under_a_window(H, window):
    """9 and 6 query heads a key-value head (``laguna``'s sliding layers
    at 72 on 8 and its full layers at 48 on 8: the first odd ratio a cell
    runs) with a window well under T, where blocks below the band are no
    steps: values and all three gradients against the plain path;
    ``flash_bwd_dkv`` sums ``dk`` and ``dv`` over the 9 (or 6) heads of a
    group, each over its band's blocks alone."""
    B, T, K, D = 1, 70, 2, 128
    rng = np.random.RandomState(47 + H)
    q = jnp.asarray(rng.randn(B, T, H * K, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, T, K, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, T, K, D), jnp.float32)
    w = jnp.asarray(rng.randn(B, T, H * K, D), jnp.float32)
    flash = lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=window, block_q=16, block_k=16)
    plain = lambda q, k, v: local_flash_attention(
        q, k, v, causal=True, window=window)
    (out, gf), (ref, gr) = (_value_and_grads(f, q, k, v, w)
                            for f in (flash, plain))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5, rtol=3e-5)
    assert gf[1].shape == (B, T, K, D)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


def test_flash_cross_attention_shapes():
    """Tq != Tk (cross attention / KV cache shapes)."""
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(1, 17, 2, 16), jnp.float32)
    k = jnp.asarray(rng.randn(1, 50, 2, 16), jnp.float32)
    v = jnp.asarray(rng.randn(1, 50, 2, 16), jnp.float32)
    out = flash_attention(q, k, v, causal=False, block_q=16, block_k=16)
    ref = local_flash_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("window,shape,blocks", [
    (8, (2, 70, 3, 16), (32, 32)),    # window smaller than a block
    (40, (1, 64, 2, 32), (16, 16)),   # window spans several blocks
    (4, (2, 33, 1, 8), (16, 16)),     # tiny + padding
])
def test_flash_sliding_window_matches_reference(window, shape, blocks):
    """Sliding-window (Mistral) flash == jnp reference with the same
    band mask — values and all three gradients, including the
    whole-block skip path (window < block)."""
    B, T, H, D = shape
    bq, bk = blocks
    rng = np.random.RandomState(hash((shape, window)) % (2**31))
    q = jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, T, H, D), jnp.float32)

    flash = lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=window, block_q=bq, block_k=bk)
    plain = lambda q, k, v: local_flash_attention(q, k, v, causal=True,
                                                  window=window)
    (out, gf), (ref, gr) = (_value_and_grads(f, q, k, v)
                            for f in (flash, plain))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5, rtol=3e-5)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


def test_flash_sliding_window_gqa():
    """Windowed attention through the native-GQA kv index maps."""
    B, T, H, K, D = 2, 48, 4, 2, 16
    rng = np.random.RandomState(11)
    q = jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, T, K, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, T, K, D), jnp.float32)
    out = flash_attention(q, k, v, causal=True, window=12,
                          block_q=16, block_k=16)
    ref = local_flash_attention(q, k, v, causal=True, window=12)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5, rtol=3e-5)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, causal=False, window=12)


def _reference_forward(q, k, v, scale, causal, rep, window):
    """Plain float64 attention on the kernels' ``[BH, T, D]`` layout with
    its logsumexp: ``(o, lse)``, both 0 in a row with no visible key."""
    q, k, v = (np.asarray(x, np.float64) for x in (q, k, v))
    k, v = np.repeat(k, rep, axis=0), np.repeat(v, rep, axis=0)
    s = np.einsum("bqd,bkd->bqk", q, k) * scale
    rows = np.arange(s.shape[1])[:, None]
    cols = np.arange(s.shape[2])[None]
    visible = np.ones(s.shape[1:], bool)
    if causal:
        visible &= rows >= cols
        if window:
            visible &= rows - cols < window
    s = np.where(visible, s, -np.inf)
    empty = ~visible.any(axis=-1)
    top = np.where(empty, 0.0, np.max(s, axis=-1))
    p = np.exp(s - top[..., None])
    total = np.where(empty, 1.0, p.sum(axis=-1))
    o = np.einsum("bqk,bkd->bqd", p, v) / total[..., None]
    return o, np.where(empty, 0.0, top + np.log(total)), empty


@pytest.mark.parametrize("D,rep,causal,window,Tq,Tk,blocks", [
    pytest.param(64, 1, True, 0, 64, 64, (32, 32), id="d64-rep1-causal"),
    pytest.param(64, 4, False, 0, 64, 64, (32, 32), id="d64-rep4-full"),
    pytest.param(128, 1, False, 0, 48, 48, (16, 16), id="d128-rep1-full"),
    pytest.param(128, 4, True, 0, 70, 70, (32, 32),
                 id="d128-rep4-causal-padded"),
    pytest.param(128, 16, True, 0, 64, 64, (32, 32), id="d128-rep16-causal"),
    pytest.param(128, 20, True, 0, 64, 64, (32, 32), id="d128-rep20-causal"),
    pytest.param(256, 1, True, 0, 33, 33, (16, 16),
                 id="d256-rep1-causal-padded"),
    pytest.param(256, 16, False, 0, 32, 32, (16, 16), id="d256-rep16-full"),
    pytest.param(128, 4, True, 8, 70, 70, (32, 32),
                 id="d128-rep4-window8-padded"),
    pytest.param(64, 1, True, 40, 64, 64, (16, 16), id="d64-rep1-window40"),
    pytest.param(64, 4, False, 0, 17, 50, (16, 16), id="d64-rep4-tq17-tk50"),
    pytest.param(128, 1, True, 0, 50, 17, (16, 16),
                 id="d128-rep1-causal-tq50-tk17"),
    pytest.param(64, 1, True, 8, 40, 16, (16, 16),
                 id="d64-rep1-window8-rows-without-a-key"),
    pytest.param(128, 4, True, 0, 256, 256, (128, 128),
                 id="d128-rep4-causal-tile128"),
])
def test_flash_forward_output_and_logsumexp(D, rep, causal, window, Tq, Tk,
                                            blocks):
    """The forward kernel alone: ``o`` AND ``lse`` against a plain
    float64 logsumexp.  The backward kernels and ring attention's merge of
    shards read ``lse``; a row that sees no key stores ``lse`` 0 (not
    -inf) and ``o`` 0."""
    from horovod_tpu.ops.flash_attention import _fwd_impl

    K = 2
    rng = np.random.RandomState(D + 31 * rep + Tq + 7 * Tk + window)
    q = jnp.asarray(rng.randn(K * rep, Tq, D), jnp.float32)
    k = jnp.asarray(rng.randn(K, Tk, D), jnp.float32)
    v = jnp.asarray(rng.randn(K, Tk, D), jnp.float32)
    scale = D ** -0.5
    o, lse = _fwd_impl(q, k, v, scale, causal, *blocks, True, rep, window)
    ref_o, ref_lse, empty = _reference_forward(q, k, v, scale, causal, rep,
                                               window)
    assert o.shape == (K * rep, Tq, D) and lse.shape == (K * rep, Tq)
    assert lse.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(o), ref_o, atol=3e-5, rtol=3e-5)
    np.testing.assert_allclose(np.asarray(lse), ref_lse, atol=3e-5,
                               rtol=3e-5)
    # causal with a window and more queries than keys: row r sees the keys
    # in (r - window, r], none of them under Tk from r = Tk + window - 1 on
    assert empty.sum() == (max(0, Tq - (Tk + window - 1)) if window else 0)
    assert np.all(np.asarray(lse)[:, empty] == 0.0)
    assert np.all(np.asarray(o)[:, empty] == 0.0)


# (Tq, Tk, tiles, causal, window, rep): the seven cells' attention layers
# (``laguna-s-2_1-5l`` has two kinds: a band of 512 at 9 a key head, and
# full layers at 6), then
# what the small shapes reach — a padded tail, a window's edge inside a
# block and across blocks, odd windows (the band's last column the first of
# a block), more queries than keys under a window (q rows with no live
# block), more keys than queries (k rows with none), cross attention, a
# grid with no mask at all.
SCHEDULES = [
    pytest.param(8192, 8192, (512, 512), True, 0, 1, id="ouro2_6b-16l"),
    pytest.param(16384, 16384, (512, 512), True, 0, 1,
                 id="olmo-hybrid-7b-4l"),
    pytest.param(4096, 4096, (512, 512), True, 4096, 4, id="mistral7b-4l"),
    pytest.param(8192, 8192, (512, 512), True, 0, 16,
                 id="nemotron3-super-11l"),
    pytest.param(8192, 8192, (512, 512), True, 0, 8, id="qwen3next-4l"),
    pytest.param(8192, 8192, (512, 512), True, 0, 20, id="jamba2-3b-14l"),
    pytest.param(16384, 16384, (512, 512), True, 512, 9,
                 id="laguna-s-2_1-5l-window512"),
    pytest.param(16384, 16384, (512, 512), True, 0, 6,
                 id="laguna-s-2_1-5l-full"),
    pytest.param(4096, 4096, (512, 512), True, 1024, 4, id="window1024"),
    pytest.param(3000, 3000, (512, 512), True, 0, 20, id="t3000-padded"),
    pytest.param(70, 70, (32, 32), True, 0, 4, id="t70-padded"),
    pytest.param(70, 70, (16, 32), True, 8, 1, id="t70-window8-tiles16x32"),
    pytest.param(64, 64, (16, 16), True, 40, 1, id="t64-window40"),
    pytest.param(64, 64, (32, 16), True, 16, 2, id="t64-window16-tiles32x16"),
    pytest.param(64, 64, (16, 16), True, 1, 2, id="t64-window1"),
    pytest.param(64, 64, (16, 16), True, 17, 1, id="t64-window17"),
    pytest.param(70, 70, (16, 32), True, 33, 4,
                 id="t70-window33-tiles16x32"),
    pytest.param(4096, 4096, (512, 512), True, 1025, 1, id="window1025"),
    pytest.param(40, 16, (16, 16), True, 8, 1,
                 id="window8-rows-without-a-key"),
    pytest.param(45, 17, (16, 16), True, 9, 2,
                 id="window9-padding-inside-the-band"),
    pytest.param(50, 17, (16, 16), True, 0, 1, id="causal-tq50-tk17"),
    pytest.param(17, 50, (16, 16), True, 0, 2, id="causal-tq17-tk50"),
    pytest.param(17, 50, (16, 16), False, 0, 4, id="cross-tq17-tk50"),
    pytest.param(64, 64, (32, 32), False, 0, 1, id="full-t64-no-mask"),
    pytest.param(33, 33, (16, 16), False, 0, 1, id="full-t33-padded"),
]


def _block_masks(Tq, Tk, bq, bk, causal, window):
    """The kernels' mask by brute force, a block at a time:
    ``[n_q, n_k, bq, bk]``."""
    n_q, n_k = -(-Tq // bq), -(-Tk // bk)
    rows = np.arange(n_q * bq)[:, None]
    cols = np.arange(n_k * bk)[None]
    mask = (cols < Tk) & (rows < Tq)
    if causal:
        mask = mask & (rows >= cols)
        if window:
            mask = mask & (rows - cols < window)
    return mask.reshape(n_q, bq, n_k, bk).transpose(0, 2, 1, 3)


@pytest.mark.parametrize("window, rep, by_q, by_k", [
    (512, 9, 63, 567), (0, 6, 528, 3168), (4096, 4, 252, 1008),
], ids=["laguna-window512", "laguna-full", "a-band-of-8-blocks"])
def test_the_live_blocks_of_a_band_and_of_a_triangle(window, rep, by_q, by_k):
    """16384 positions in 32 x 32 blocks of 512.  A band of 512 keeps the
    diagonal block and the one before it: 1 + 31 x 2 = 63 of 1024, every one
    with an edge through it; the causal triangle 32 x 33 / 2 = 528; a band
    of 4096 (eight blocks, and the ninth that a block's first query still
    reaches) 8 x 9 / 2 + 24 x 9 = 252.  ``flash_bwd_dkv`` walks each once a
    query head of its group."""
    from horovod_tpu.ops import flash_attention as fa
    live = lambda *a, **kw: int(np.sum(
        fa.block_schedule(16384, 16384, 512, 512, True, window, *a,
                          **kw)[2] & fa.LIVE != 0))
    assert live() == by_q
    assert live(rep, by_k=True) == by_k


@pytest.mark.parametrize("by_k", [False, True], ids=["by_q", "by_k"])
@pytest.mark.parametrize("Tq,Tk,blocks,causal,window,rep", SCHEDULES)
def test_block_schedule(Tq, Tk, blocks, causal, window, rep, by_k):
    """The schedule alone: its live blocks are exactly those in which a
    brute-force mask keeps an element (every one a block the dense grid's
    ``live`` rule kept), each once, rows contiguous and in the dense
    grid's order, first / last flags on a row's ends, and one step that
    computes nothing for an output row with no live block."""
    from horovod_tpu.ops import flash_attention as fa

    bq, bk = blocks
    n_q, n_k = -(-Tq // bq), -(-Tk // bk)
    kept = _block_masks(Tq, Tk, bq, bk, causal, window).any(axis=(2, 3))

    def dense_rule(qi, ki):             # the dense kernels' own test
        ok = (not causal) or ki * bk <= qi * bq + bq - 1
        if window:
            ok = ok and ki * bk + bk > qi * bq - window
        return ok

    assert all(dense_rule(qi, ki) for qi, ki in zip(*np.nonzero(kept)))

    rows, cols, flags = fa.block_schedule(Tq, Tk, bq, bk, causal, window,
                                          rep if by_k else 1, by_k=by_k)
    assert rows.dtype == cols.dtype == flags.dtype == np.int32
    n_rows, n_cols = (n_k, rep * n_q) if by_k else (n_q, n_k)
    pair = (lambda r, c: (c % n_q, r)) if by_k else (lambda r, c: (r, c))
    at = 0
    for r in range(n_rows):
        live_cols = [c for c in range(n_cols) if kept[pair(r, c)]]
        steps = live_cols or [0]        # a row with no live block: one step
        here = slice(at, at + len(steps))
        assert list(rows[here]) == [r] * len(steps)
        assert list(cols[here]) == steps
        want = np.full(len(steps), fa.LIVE if live_cols else 0)
        want[0] |= fa.FIRST
        want[-1] |= fa.LAST
        assert list(flags[here]) == list(want)
        at += len(steps)
    assert at == len(flags)


@pytest.mark.parametrize("Tq,Tk,blocks,causal,window,rep,dtype", [
    pytest.param(70, 70, (16, 32), True, 0, 1, jnp.float32,
                 id="rep1-causal-padded"),
    pytest.param(64, 64, (16, 16), True, 0, 4, jnp.bfloat16,
                 id="rep4-causal-bf16"),
    pytest.param(64, 64, (16, 16), True, 0, 20, jnp.float32,
                 id="rep20-causal"),
    pytest.param(70, 70, (16, 16), True, 40, 4, jnp.float32,
                 id="rep4-window40-padded"),
    pytest.param(64, 64, (16, 16), True, 17, 1, jnp.bfloat16,
                 id="rep1-window17-bf16"),
    pytest.param(64, 64, (16, 16), True, 1, 20, jnp.float32,
                 id="rep20-window1"),
    pytest.param(40, 16, (16, 16), True, 8, 1, jnp.float32,
                 id="rep1-window8-rows-without-a-key"),
    pytest.param(45, 17, (16, 16), True, 9, 2, jnp.float32,
                 id="rep2-window9-padding-inside-the-band"),
    pytest.param(50, 17, (16, 16), True, 0, 4, jnp.float32,
                 id="rep4-causal-tq50-tk17"),
    pytest.param(17, 50, (16, 16), True, 0, 4, jnp.float32,
                 id="rep4-causal-tq17-tk50"),
])
def test_flash_dense_grid_gives_the_same_bits(
        monkeypatch, Tq, Tk, blocks, causal, window, rep, dtype):
    """A list too long for scalar memory is not made: the kernels then walk
    the dense grid with ``_live`` as each step's test (what every call did
    before the schedule), and ``o``, ``lse``, ``dq``, ``dk`` and ``dv``
    equal the list's bit for bit — same blocks, same order."""
    from horovod_tpu import trace
    from horovod_tpu.ops import flash_attention as fa

    K, D = 2, 64
    rng = np.random.RandomState(Tq + 3 * Tk + 5 * window + 7 * rep)
    q, do = (jnp.asarray(rng.randn(K * rep, Tq, D), dtype) for _ in "ab")
    k, v = (jnp.asarray(rng.randn(K, Tk, D), dtype) for _ in "ab")
    scale = D ** -0.5

    def five():
        before = dict(trace.flash_blocks)
        o, lse = fa._fwd_impl(q, k, v, scale, causal, *blocks, True, rep,
                              window)
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), -1)
        out = (o, lse) + tuple(fa._bwd_impl(
            q, k, v, do, lse, delta, scale=scale, causal=causal,
            block_q=blocks[0], block_k=blocks[1], interpret=True, rep=rep,
            window=window))
        return out, {key: trace.flash_blocks[key] - n
                     for key, n in before.items()}

    want, listed = five()
    monkeypatch.setattr(fa, "MAX_LIST", 0)
    got, dense = five()
    assert listed["steps"] < listed["grid"] == dense["grid"] == dense["steps"]
    for name, a, b in zip(("o", "lse", "dq", "dk", "dv"), got, want):
        assert a.dtype == b.dtype and np.array_equal(
            np.asarray(a.astype(jnp.float32)),
            np.asarray(b.astype(jnp.float32))), name


def test_flash_blocks_counts_grid_and_steps():
    """``trace.flash_blocks``: a traced causal call at 16 x 16 blocks adds
    a head's ``grid`` 256 and ``steps`` 136 a kernel; its gradient the
    forward's and the two backward kernels', ``rep`` times that for
    ``flash_bwd_dkv``; a non-causal call keeps its whole grid; and
    ``/metrics`` carries the two series."""
    from horovod_tpu import trace
    from horovod_tpu.monitor.agent import MonitorAgent

    class Engine:
        monitor = None

    def moved(fn, *shapes):
        before = dict(trace.flash_blocks)
        jax.eval_shape(fn, *(jax.ShapeDtypeStruct(s, jnp.bfloat16)
                             for s in shapes))
        return {key: trace.flash_blocks[key] - n for key, n in before.items()}

    attn = lambda causal: lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, causal=causal, block_q=16, block_k=16)
    one = (1, 256, 4, 64)
    agent = MonitorAgent(engine=Engine())
    try:
        first = agent.registry.snapshot()
        assert moved(attn(True), one, one, one) == {"grid": 256, "steps": 136}
        assert moved(attn(False), one, one, one) == {
            "grid": 256, "steps": 256}
        grad = jax.grad(lambda q, k, v: attn(True)(q, k, v).astype(
            jnp.float32).sum(), argnums=(0, 1, 2))
        assert moved(grad, one, (1, 256, 2, 64), (1, 256, 2, 64)) == {
            "grid": 4 * 256, "steps": 4 * 136}
        second = agent.registry.snapshot()
        text = agent.registry.to_prometheus('rank="0"')
    finally:
        agent.close()

    def value(snap, name):
        return snap[name]["value"] if isinstance(snap[name], dict) \
            else snap[name]

    for key, n in {"grid": 6 * 256, "steps": 5 * 136 + 256}.items():
        name = f"hvd_flash_blocks_{key}_total"
        assert value(second, name) - value(first, name) == n
        assert name in text


def _flash_sweep():
    """tools/flash_sweep.py as a module (``tools`` is no package)."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "flash_sweep", os.path.join(os.path.dirname(__file__), os.pardir,
                                    "tools", "flash_sweep.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# A llama layer and the seven decoder cells' attention layers (``laguna``'s
# two kinds) as a step sees them, from the table tools/flash_sweep.py times on the chip; then 20 query
# heads on one key head with a padded tail, and a cross-attention shape.
CELL_GEOMETRIES = [pytest.param(1, 1024, 1024, 8, 4, 128, True, None,
                                id="llama-d128-h8k4-t1024")] + [
    pytest.param(g["B"], g["T"], g["T"], g["H"], g["K"], g["D"], True,
                 g["window"] or None, id=cell)
    for cell, g in _flash_sweep().GEOMETRIES.items()] + [
    pytest.param(1, 3000, 3000, 20, 1, 128, True, None,
                 id="h20k1-t3000-padded"),
    pytest.param(2, 1024, 4096, 8, 2, 128, False, None,
                 id="cross-tq1024-tk4096"),
    pytest.param(1, 700, 1500, 12, 12, 64, False, None,
                 id="cross-d64-tq700-tk1500-padded"),
    # joyai_flash-5l-spmd-1c: 32 heads, keys of 192 beside values of 128
    pytest.param(1, 16384, 16384, 32, 32, (192, 128), True, None,
                 id="joyai_flash-5l-spmd-1c-keys192-values128")]


@pytest.mark.parametrize("B,Tq,Tk,H,K,D,causal,window", CELL_GEOMETRIES)
def test_flash_tpu_lowering(B, Tq, Tk, H, K, D, causal, window):
    """Cross-platform lowering: the Mosaic/TPU pipeline runs client-side,
    so a CPU host can verify the kernels lower for TPU at real llama
    shapes and at each benchmark cell's heads, head width, sequence and
    window — the guard that keeps the driver's on-TPU compile check safe
    (tests/test_tpu_compile.py compiles them for a described v5e)."""
    def f(q, k, v):
        return jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=causal, window=window,
            interpret=False).astype(jnp.float32)),
            argnums=(0, 1, 2))(q, k, v)

    D, Dv = D if isinstance(D, tuple) else (D, D)
    spec_q = jax.ShapeDtypeStruct((B, Tq, H, D), jnp.bfloat16)
    spec_k = jax.ShapeDtypeStruct((B, Tk, K, D), jnp.bfloat16)  # GQA
    spec_v = jax.ShapeDtypeStruct((B, Tk, K, Dv), jnp.bfloat16)
    exp = jax.export.export(jax.jit(f), platforms=["tpu"])(
        spec_q, spec_k, spec_v)
    assert exp.mlir_module().count("tpu_custom_call") == 3


def test_prefill_tpu_lowering(monkeypatch):
    """The blockwise prefill lowers for TPU WITH the Pallas flash kernel
    in the module (≥1 tpu_custom_call per layer) — proof the serving
    prompt path rides the MXU kernel, not the jnp fallback, checked
    client-side without a chip."""
    from horovod_tpu.models import llama
    from horovod_tpu.ops import flash_attention as fa

    monkeypatch.setenv("HVD_TPU_FLASH", "1")
    # Trace happens on a CPU host: force the kernel's compiled (Mosaic)
    # path rather than the interpret default so the export carries the
    # real tpu_custom_calls.
    monkeypatch.setattr(fa, "_interpret_default", lambda: False)
    cfg = llama.tiny(n_heads=8, n_kv_heads=4, d_model=256, d_ff=512,
                     vocab_size=512, max_seq=1024, n_layers=2,
                     dtype=jnp.bfloat16, dp_axis=None, tp_axis=None,
                     sp_axis=None, use_flash=True)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    cache = llama.init_cache(cfg, 1, 1024)
    toks = jax.ShapeDtypeStruct((1, 512), jnp.int32)

    def f(params, cache, toks):
        return llama.prefill(params, cache, toks, cfg)[0]

    exp = jax.export.export(jax.jit(f), platforms=["tpu"])(
        jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params),
        jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), cache),
        toks)
    mod = exp.mlir_module()
    assert mod.count("tpu_custom_call") >= cfg.n_layers, \
        mod.count("tpu_custom_call")


def test_ulysses_routes_through_flash(monkeypatch):
    """HVD_TPU_FLASH=1 makes Ulysses run the pallas kernel on its local
    heads INSIDE shard_map over the sp mesh — the real sp usage."""
    from horovod_tpu.compat import shard_map
    from jax.sharding import Mesh, PartitionSpec as P
    from horovod_tpu.parallel.ulysses import ulysses_attention

    monkeypatch.setenv("HVD_TPU_FLASH", "1")
    # Spy: if routing regresses to the jnp fallback, fail loudly instead of
    # passing vacuously (flash and reference are numerically identical).
    # NB: horovod_tpu.parallel re-exports the ring_attention FUNCTION, which
    # shadows the submodule attribute — import the module explicitly.
    import importlib
    ra = importlib.import_module("horovod_tpu.parallel.ring_attention")

    def _boom(*a, **k):
        raise AssertionError("routing fell back to local_flash_attention "
                             "despite HVD_TPU_FLASH=1")
    monkeypatch.setattr(ra, "local_flash_attention", _boom)
    rng = np.random.RandomState(5)
    q = jnp.asarray(rng.randn(1, 64, 8, 16), jnp.float32)
    k = jnp.asarray(rng.randn(1, 64, 8, 16), jnp.float32)
    v = jnp.asarray(rng.randn(1, 64, 8, 16), jnp.float32)
    ref = local_flash_attention(q, k, v, causal=True)

    mesh = Mesh(np.array(jax.devices()[:8]), ("sp",))
    out = jax.jit(shard_map(
        lambda q, k, v: ulysses_attention(q, k, v, axis_name="sp",
                                          causal=True),
        mesh=mesh, in_specs=(P(None, "sp"),) * 3, out_specs=P(None, "sp"),
        check_vma=False))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=3e-5)


def test_bert_uses_flash_when_forced(monkeypatch):
    from horovod_tpu.models import bert

    cfg = bert.tiny(dtype=jnp.float32,
                    dp_axis=None, tp_axis=None, sp_axis=None)
    params = bert.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jnp.asarray(np.random.RandomState(0).randint(0, 256, (2, 24)),
                         jnp.int32)
    monkeypatch.setenv("HVD_TPU_FLASH", "0")
    ref = bert.forward(params, tokens, cfg)
    monkeypatch.setenv("HVD_TPU_FLASH", "1")
    monkeypatch.setattr(
        bert, "local_flash_attention",
        lambda *a, **k: (_ for _ in ()).throw(AssertionError(
            "bert fell back to local_flash_attention under "
            "HVD_TPU_FLASH=1")))
    out = bert.forward(params, tokens, cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-4, rtol=2e-4)


def test_llama_uses_flash_when_forced(monkeypatch):
    """HVD_TPU_FLASH=1 routes llama attention through the pallas kernel;
    logits must match the jnp-reference path."""
    from horovod_tpu.models import llama

    cfg = llama.tiny(n_heads=4, n_kv_heads=2, d_model=64, d_ff=128,
                     vocab_size=128, dtype=jnp.float32,
                     dp_axis=None, tp_axis=None, sp_axis=None)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jnp.asarray(np.random.RandomState(0).randint(0, 128, (2, 40)),
                         jnp.int32)
    monkeypatch.setenv("HVD_TPU_FLASH", "0")
    ref = llama.forward(params, tokens, cfg)
    monkeypatch.setenv("HVD_TPU_FLASH", "1")
    # Spy: the forced run must NOT touch the jnp fallback (otherwise this
    # test is vacuous — both paths produce identical numbers).
    monkeypatch.setattr(
        llama, "local_flash_attention",
        lambda *a, **k: (_ for _ in ()).throw(AssertionError(
            "llama fell back to local_flash_attention under "
            "HVD_TPU_FLASH=1")))
    out = llama.forward(params, tokens, cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-4, rtol=2e-4)


def test_flash_auto_seq_threshold(monkeypatch):
    """Auto routing is sequence-aware: on TPU, auto mode picks flash only
    at/above HVD_TPU_FLASH_MIN_SEQ; explicit forces ignore the
    threshold."""
    from horovod_tpu.ops import flash_attention as fa

    monkeypatch.delenv("HVD_TPU_FLASH", raising=False)
    monkeypatch.setenv("HVD_TPU_FLASH_MIN_SEQ", "1024")
    monkeypatch.setattr(fa.jax, "default_backend", lambda: "tpu")
    assert fa.flash_enabled(seq=512) is False
    assert fa.flash_enabled(seq=1024) is True
    assert fa.flash_enabled(seq=4096) is True
    assert fa.flash_enabled() is True          # unknown seq: legacy default
    assert fa.resolve_flash(None, seq=512) is False
    assert fa.resolve_flash(True, seq=512) is True    # config force wins
    assert fa.resolve_flash(False, seq=8192) is False

    # Causality-aware defaults: causal crossover 512, non-causal 1024.
    monkeypatch.delenv("HVD_TPU_FLASH_MIN_SEQ", raising=False)
    assert fa.flash_min_seq(causal=True) == 512
    assert fa.flash_min_seq(causal=False) == 1024
    assert fa.flash_enabled(seq=512, causal=True) is True
    assert fa.flash_enabled(seq=256, causal=True) is False
    assert fa.flash_enabled(seq=512, causal=False) is False
    assert fa.flash_enabled(seq=1024, causal=False) is True
    monkeypatch.setenv("HVD_TPU_FLASH_MIN_SEQ", "2048")  # overrides BOTH
    assert fa.flash_enabled(seq=1024, causal=True) is False
    assert fa.flash_enabled(seq=2048, causal=False) is True
    monkeypatch.setenv("HVD_TPU_FLASH_MIN_SEQ", "1024")

    monkeypatch.setenv("HVD_TPU_FLASH", "1")   # env force beats threshold
    assert fa.flash_enabled(seq=128) is True
    monkeypatch.setenv("HVD_TPU_FLASH", "0")
    assert fa.flash_enabled(seq=8192) is False

    # Off-TPU auto stays off at any length.
    monkeypatch.delenv("HVD_TPU_FLASH", raising=False)
    monkeypatch.setattr(fa.jax, "default_backend", lambda: "cpu")
    assert fa.flash_enabled(seq=8192) is False


def test_flash_block_env_defaults(monkeypatch):
    """HVD_TPU_FLASH_BLOCK_Q/K tune the kernel tiles without a code
    change (tools/flash_sweep.py feeds these); unset keeps the 512x512
    default."""
    from horovod_tpu.ops import flash_attention as fa
    monkeypatch.delenv("HVD_TPU_FLASH_BLOCK_Q", raising=False)
    monkeypatch.delenv("HVD_TPU_FLASH_BLOCK_K", raising=False)
    assert fa._block_defaults() == (512, 512)
    monkeypatch.setenv("HVD_TPU_FLASH_BLOCK_Q", "256")
    monkeypatch.setenv("HVD_TPU_FLASH_BLOCK_K", "1024")
    assert fa._block_defaults() == (256, 1024)
    monkeypatch.setenv("HVD_TPU_FLASH_BLOCK_Q", "junk")
    assert fa._block_defaults()[0] == 512


def test_flash_rejects_mixed_dtypes():
    """The kernels feed raw operands to the MXU, so mixed q/k/v dtypes
    must fail with the explicit entry-point error, not a cryptic
    dot_general trace error."""
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(1, 16, 2, 8), jnp.float32)
    k = jnp.asarray(rng.randn(1, 16, 2, 8), jnp.bfloat16)
    v = jnp.asarray(rng.randn(1, 16, 2, 8), jnp.bfloat16)
    with pytest.raises(ValueError, match="share one dtype"):
        flash_attention(q, k, v, causal=True, block_q=16, block_k=16)


def test_flash_bwd_casts_f32_cotangent():
    """An f32 cotangent over bf16 primals is legal in jax; the backward
    must cast it rather than die on the raw-dtype contract."""
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(1, 32, 2, 8), jnp.bfloat16)
    k = jnp.asarray(rng.randn(1, 32, 2, 8), jnp.bfloat16)
    v = jnp.asarray(rng.randn(1, 32, 2, 8), jnp.bfloat16)

    def loss(q, k, v):
        # .astype(f32) before the reduction makes the incoming cotangent
        # of the flash output an f32 array.
        return jnp.sum(flash_attention(q, k, v, causal=True, block_q=16,
                                       block_k=16).astype(jnp.float32) ** 2)

    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for a in g:
        assert a.dtype == jnp.bfloat16
        assert np.all(np.isfinite(np.asarray(a, np.float32)))


def test_vit_uses_flash_when_forced(monkeypatch):
    """HVD_TPU_FLASH=1 routes ViT's (reused bert) attention through the
    pallas kernel; logits must match the jnp-reference path."""
    from horovod_tpu.models import vit, bert

    cfg = vit.tiny(dtype=jnp.float32, dp_axis=None, tp_axis=None)
    params = vit.init_params(cfg, jax.random.PRNGKey(0))
    images = jnp.asarray(np.random.RandomState(0).randn(2, 32, 32, 3),
                         jnp.float32)
    monkeypatch.setenv("HVD_TPU_FLASH", "0")
    ref = vit.logits(params, images, cfg)
    monkeypatch.setenv("HVD_TPU_FLASH", "1")
    monkeypatch.setattr(
        bert, "local_flash_attention",
        lambda *a, **k: (_ for _ in ()).throw(AssertionError(
            "vit fell back to local_flash_attention under "
            "HVD_TPU_FLASH=1")))
    out = vit.logits(params, images, cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-4, rtol=2e-4)


def test_gpt2_uses_flash_when_forced(monkeypatch):
    """HVD_TPU_FLASH=1 routes GPT-2's causal attention through the
    pallas kernel; logits must match the jnp-reference path."""
    from horovod_tpu.models import gpt2

    cfg = gpt2.tiny(dtype=jnp.float32, dp_axis=None, tp_axis=None)
    params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jnp.asarray(np.random.RandomState(0).randint(0, 256, (2, 24)),
                         jnp.int32)
    monkeypatch.setenv("HVD_TPU_FLASH", "0")
    ref = gpt2.forward(params, tokens, cfg)
    monkeypatch.setenv("HVD_TPU_FLASH", "1")
    import importlib
    ra = importlib.import_module("horovod_tpu.parallel.ring_attention")
    monkeypatch.setattr(
        ra, "local_flash_attention",
        lambda *a, **k: (_ for _ in ()).throw(AssertionError(
            "gpt2 fell back to local_flash_attention under "
            "HVD_TPU_FLASH=1")))
    out = gpt2.forward(params, tokens, cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-4, rtol=2e-4)


def test_flash_sweep_counts_blocks_and_names_kernels():
    """tools/flash_sweep.py's arithmetic: a head's blocks of the dense
    grid and the steps of the kernels' schedule (136 of 256 at 8192
    tokens); and a device event's kernel by its instruction's name."""
    sweep = _flash_sweep()
    assert sweep.blocks_of(8192, 512, 512, 0) == (256, 136)
    assert sweep.blocks_of(16384, 512, 512, 0) == (1024, 528)
    assert sweep.blocks_of(4096, 512, 512, 4096) == (64, 36)
    assert sweep.blocks_of(4096, 512, 512, 1024) == (64, 21)
    assert sweep.blocks_of(3000, 512, 512, 0) == (36, 21)
    assert sweep.blocks_of(8192, 512, 512, 0, causal=False) == (256, 256)
    for event, kernel in [
            ("%flash_fwd.13 = (bf16[16,8192,128]{2,1,0}) custom-call(...)",
             "flash_fwd"),
            ("%jvp_flash_bwd_dkv_.9 = (bf16[8,4096,128]) custom-call(...)",
             "flash_bwd_dkv"),
            ("flash_bwd_dq.1", "flash_bwd_dq")]:
        assert sweep.EVENT_KERNEL.match(event).group(1) == kernel
