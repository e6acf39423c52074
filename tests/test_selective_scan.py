"""The Mamba-1 mixers' selective scan (``ops/selective_scan.py``): the plain
path against a token-by-token loop in float64 — values and every gradient,
at an odd ``T``, ``b`` > 1 and a channel count that is no multiple of a
lane tile, whole and some channels at a time; the Pallas kernel pair in
interpret mode on the CPU against the plain path, over several time
blocks and channel tiles; sequences of a
batch that see nothing of each other; a state that carries across the
plain path's chunks and the kernels' blocks; the float32 sums of ``dA`` and
``dD``; which shapes take which path and the counts that say so
(``trace.selective_scan``, the two ``/metrics`` series); and who lowers it:
the ``jamba`` family, never ``nemotron_h``'s Mamba-2.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from horovod_tpu import trace
from horovod_tpu.ops import selective_scan as ss

KEY = jax.random.PRNGKey(43)
NAMES = ("x", "delta", "A", "B", "C", "D")


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def draw(b, T, d, n, dtype=jnp.float32, key=KEY):
    """Inputs as a Mamba layer makes them: steps of 0.001 to 1, decays
    ``-1 .. -16``.  (One program a shape, as ``value_and_grads`` is: op by
    op the file compiles 760 programs.)"""
    ks = jax.random.split(key, 7)
    return (jax.random.normal(ks[0], (b, T, d)).astype(dtype),
            jax.nn.softplus(2.0 * jax.random.normal(ks[1], (b, T, d)) - 3.0),
            -jnp.exp(jax.random.uniform(ks[2], (d, n), minval=0.0,
                                        maxval=np.log(16.0))),
            jax.random.normal(ks[3], (b, T, n)).astype(dtype),
            jax.random.normal(ks[4], (b, T, n)).astype(dtype),
            jax.random.normal(ks[5], (d,)).astype(dtype),
            jax.random.normal(ks[6], (b, T, d)).astype(dtype))


def token_loop(x, delta, A, B, C, D, dy):
    """The recurrence and its gradients a token at a time in float64, the
    backward pass written out: what both paths are held to."""
    x, delta, A, B, C, D, dy = (np.asarray(v, np.float64)
                                for v in (x, delta, A, B, C, D, dy))
    b, T, d = x.shape
    n = A.shape[1]
    y = np.zeros_like(x)
    h = np.zeros((b, T + 1, d, n))
    for t in range(T):
        decay = np.exp(delta[:, t, :, None] * A)
        h[:, t + 1] = decay * h[:, t] + (
            (delta[:, t] * x[:, t])[..., None] * B[:, t, None, :])
        y[:, t] = np.einsum("bdn,bn->bd", h[:, t + 1], C[:, t]) + D * x[:, t]
    g = {k: np.zeros_like(v) for k, v in zip(NAMES, (x, delta, A, B, C, D))}
    dh = np.zeros((b, d, n))
    for t in reversed(range(T)):
        dh = dh + dy[:, t, :, None] * C[:, t, None, :]
        g["C"][:, t] = np.einsum("bdn,bd->bn", h[:, t + 1], dy[:, t])
        decay = np.exp(delta[:, t, :, None] * A)
        held = dh * decay * h[:, t]
        of_b = np.einsum("bdn,bn->bd", dh, B[:, t])
        g["A"] += np.sum(held * delta[:, t, :, None], axis=0)
        g["delta"][:, t] = np.sum(held * A, axis=-1) + of_b * x[:, t]
        g["x"][:, t] = of_b * delta[:, t] + D * dy[:, t]
        g["B"][:, t] = np.einsum("bdn,bd->bn", dh, delta[:, t] * x[:, t])
        g["D"] += np.sum(dy[:, t] * x[:, t], axis=0)
        dh = dh * decay
    return y, g


def value_and_grads(fn, args):
    @jax.jit
    def both(*inputs):
        *inputs, dy = inputs
        y, back = jax.vjp(fn, *inputs)
        return y, back(dy.astype(y.dtype))
    y, grads = both(*args)
    return y, dict(zip(NAMES, grads))


def gap(got, want):
    """Largest difference over the largest value, in float64."""
    got, want = (np.asarray(a, np.float64) for a in (got, want))
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                  1e-30))


def kernels(tile_c=None):
    return lambda *a: ss.kernel_selective_scan(*a, tile_c=tile_c,
                                               interpret=True)


def counts():
    return dict(trace.selective_scan)


def moved(before):
    return {k: v - before[k] for k, v in counts().items()}


# float32 against float64: rounding of a sum over T tokens
EXACT = 2e-5


# ------------------------------------------------------------ the plain path
@pytest.mark.parametrize("b, T, d, n, chunk", [
    pytest.param(1, 64, 128, 16, 64, id="one-chunk"),
    pytest.param(2, 77, 40, 16, 16, id="odd-T-b2-d40"),
    pytest.param(3, 33, 24, 8, 64, id="shorter-than-a-chunk"),
])
def test_the_plain_path_is_the_token_loop(b, T, d, n, chunk):
    args = draw(b, T, d, n)
    want_y, want_g = token_loop(*args)
    y, g = value_and_grads(
        lambda *a: ss.plain_selective_scan(*a, chunk=chunk), args)
    assert y.shape == (b, T, d) and y.dtype == jnp.float32
    assert gap(y, want_y) <= EXACT
    for name in NAMES:
        assert gap(g[name], want_g[name]) <= EXACT, name


@pytest.mark.parametrize("token_channels, parts", [
    (2 * 77 * 40, 1), (2 * 77 * 20, 2), (2 * 77 * 8, 5), (1, 40)])
def test_some_channels_at_a_time_is_all_channels_at_once(token_channels,
                                                         parts):
    """Channels are independent: the plain path in equal parts of the
    channels (as many as keep ``b x T x width`` within ``token_channels``,
    one channel at least) gives what it gives whole, values and every
    gradient, with the parts under one ``lax.map``."""
    args = draw(2, 77, 40, 16)
    want_y, want_g = value_and_grads(
        lambda *a: ss.plain_selective_scan(*a, token_channels=1 << 30), args)
    fn = lambda *a: ss.plain_selective_scan(
        *a, token_channels=token_channels)
    y, g = value_and_grads(fn, args)
    assert gap(y, want_y) <= 1e-6
    for name in NAMES:
        assert gap(g[name], want_g[name]) <= 1e-5, name
    jaxpr = str(jax.make_jaxpr(fn)(*args[:6]))
    assert (f"f32[{parts},2,77,{40 // parts}]" in jaxpr) == (parts > 1)


def test_the_plain_path_keeps_the_input_type_and_a_float32_state():
    """bfloat16 in, bfloat16 out; the state is float32 inside: the result
    is within a bfloat16 rounding of the float32 run on the same rounded
    inputs, where a bfloat16 state would drift by far more over 256
    tokens."""
    args = draw(1, 256, 128, 16, jnp.bfloat16)[:6]
    y = ss.plain_selective_scan(*args)
    assert y.dtype == jnp.bfloat16
    exact = ss.plain_selective_scan(*(a.astype(jnp.float32) for a in args))
    assert gap(y, exact) <= 2.0 ** -8


def test_sequences_of_a_batch_see_nothing_of_each_other():
    args = draw(2, 48, 32, 8)[:6]
    both = ss.plain_selective_scan(*args)
    for i in range(2):
        alone = ss.plain_selective_scan(
            *(a[i:i + 1] if a.ndim == 3 else a for a in args))
        np.testing.assert_allclose(both[i:i + 1], alone, rtol=1e-6,
                                   atol=1e-6)


def test_the_state_crosses_a_chunks_edge():
    """A token's input is still read a chunk later: with one decay near 1
    the output at the last token depends on the first."""
    b, T, d, n = 1, 40, 8, 8
    x, delta, A, B, C, D, _ = draw(b, T, d, n)
    A = jnp.full_like(A, -0.01)
    at = lambda x: ss.plain_selective_scan(x, delta, A, B, C, D,
                                           chunk=8)[0, -1].sum()
    assert float(jnp.abs(jax.grad(at)(x)[0, 0]).max()) > 1e-4


# --------------------------------------------------------------- the kernels
GEOMETRIES = [
    pytest.param((1, 128, 128, 16), None, id="one-block"),
    pytest.param((2, 256, 256, 16), 128, id="b2-two-blocks-two-tiles"),
    pytest.param((1, 384, 384, 8), 128, id="three-blocks-three-tiles-n8"),
    pytest.param((1, 128, 512, 16), None, id="one-tile-of-512"),
]


@pytest.mark.parametrize("shape, tile_c", GEOMETRIES)
def test_kernels_give_the_plain_path(shape, tile_c):
    """Values and all six gradients, float32: the same operations in
    another order (``dA`` and ``dD`` are float32 sums over ``b x T``)."""
    args = draw(*shape)
    want_y, want_g = value_and_grads(ss.plain_selective_scan, args)
    y, g = value_and_grads(kernels(tile_c), args)
    assert gap(y, want_y) <= EXACT
    for name in NAMES:
        assert g[name].shape == want_g[name].shape
        assert g[name].dtype == want_g[name].dtype
        assert gap(g[name], want_g[name]) <= EXACT, name


def test_kernels_against_the_token_loop():
    args = draw(2, 256, 128, 16)
    want_y, want_g = token_loop(*args)
    y, g = value_and_grads(kernels(), args)
    assert gap(y, want_y) <= EXACT
    for name in NAMES:
        assert gap(g[name], want_g[name]) <= EXACT, name


def test_kernels_in_bfloat16_round_where_the_plain_path_does():
    """bfloat16 x, B, C and D with a float32 step, as the mixer calls it:
    y and the gradients come back in their inputs' types and within a
    rounding or two of the plain path's."""
    args = draw(1, 256, 256, 16, jnp.bfloat16)
    want_y, want_g = value_and_grads(ss.plain_selective_scan, args)
    y, g = value_and_grads(kernels(128), args)
    assert y.dtype == jnp.bfloat16 and g["delta"].dtype == jnp.float32
    assert g["A"].dtype == jnp.float32 and g["B"].dtype == jnp.bfloat16
    assert gap(y, want_y) <= 2.0 ** -7
    for name in NAMES:
        assert gap(g[name], want_g[name]) <= 2.0 ** -6, name


def test_the_kernels_state_crosses_a_blocks_edge():
    """The forward kernel hands ``h`` to the next block and the backward
    kernel ``dh`` to the one before: the first token's input moves the
    last token's output two blocks on."""
    b, T, d, n = 1, 384, 128, 8
    x, delta, A, B, C, D, _ = draw(b, T, d, n)
    A, delta = jnp.full_like(A, -0.01), jnp.full_like(delta, 0.05)
    at = lambda fn: jax.jit(jax.grad(
        lambda x: fn(x, delta, A, B, C, D)[0, -1].sum()))(x)[0, 0]
    got, want = at(kernels()), at(ss.plain_selective_scan)
    assert float(jnp.abs(want).max()) > 1e-4
    assert gap(got, want) <= EXACT


def test_the_kernels_save_a_state_a_block():
    """The forward kernel's second result is the state each block of 128
    tokens starts from: zeros, then what the plain recurrence holds after
    128 and 256 tokens."""
    b, T, d, n = 1, 384, 128, 8
    x, delta, A, B, C, D, _ = draw(b, T, d, n)
    _, hs = ss._fwd_impl(x, delta, A.T, ss._over_lane_tiles(B),
                         ss._over_lane_tiles(C), D, 128, 2, True)
    assert hs.shape == (b, T // ss.TILE_T, n, d)
    assert not np.any(np.asarray(hs[0, 0]))
    h = np.zeros((d, n))
    for t in range(256):
        h = np.exp(np.asarray(delta[0, t])[:, None] * np.asarray(A)) * h + (
            np.asarray(delta[0, t] * x[0, t])[:, None]
            * np.asarray(B[0, t])[None])
        if t + 1 in (128, 256):
            assert gap(hs[0, (t + 1) // 128].T, h) <= EXACT


# ------------------------------------------------------- tiles and the paths
@pytest.mark.parametrize("shape, state, want", [
    pytest.param((1, 8192, 5120), 16, 512, id="jamba2-3b-t8192-d5120"),
    pytest.param((2, 256, 384), 16, 384, id="d384-one-tile"),
    pytest.param((1, 128, 640), 16, 128, id="d640-tiles-of-128"),
    pytest.param((1, 128, 1024), 32, 256, id="a-state-of-32-halves-the-tile"),
    pytest.param((1, 128, 1024), 8, 512, id="a-state-of-8"),
    pytest.param((1, 200, 256), 16, None, id="t200-no-block-of-128"),
    pytest.param((1, 128, 96), 16, None, id="d96-no-lane-tile"),
    pytest.param((1, 128, 256), 12, None, id="a-state-of-12"),
    pytest.param((1, 128, 256), 64, None, id="a-state-of-64"),
    pytest.param((128, 256), 16, None, id="no-batch-axis"),
])
def test_tiles_are_chosen_from_shape_and_state(shape, state, want):
    assert ss.tiles(shape, state) == want


def test_a_shape_without_a_tile_is_refused_by_the_kernels_entry():
    args = draw(1, 200, 256, 16)[:6]
    with pytest.raises(ValueError, match="no tile"):
        ss.kernel_selective_scan(*args, interpret=True)
    with pytest.raises(ValueError, match="no tile"):
        ss.kernel_selective_scan(*draw(1, 128, 256, 16)[:6], tile_c=192,
                                 interpret=True)


@pytest.mark.parametrize("shape, path", [
    pytest.param((1, 200, 256, 16), "plain", id="t200"),
    pytest.param((1, 128, 96, 16), "plain", id="d96"),
    pytest.param((1, 128, 256, 12), "plain", id="state-12"),
    pytest.param((1, 128, 256, 16), "kernel", id="fits"),
    pytest.param((2, 256, 128, 8), "kernel", id="fits-state-8"),
])
def test_the_path_follows_backend_and_shape(monkeypatch, shape, path):
    """With the backend said to be a TPU the shape decides (the kernels
    are interpreted here); on the CPU every shape is plain."""
    args = draw(*shape)[:6]
    before = counts()
    want = ss.selective_scan(*args)
    assert moved(before) == {"kernel": 0, "plain": 1}
    monkeypatch.setattr(ss, "kernel_enabled", lambda: True)
    before = counts()
    got = ss.selective_scan(*args)
    assert moved(before) == {"kernel": int(path == "kernel"),
                             "plain": int(path == "plain")}
    assert gap(got, want) <= EXACT
    jaxpr = str(jax.make_jaxpr(ss.selective_scan)(*args))
    assert ("pallas_call" in jaxpr) == (path == "kernel")


def test_one_trace_a_signature(monkeypatch):
    monkeypatch.setattr(ss, "kernel_enabled", lambda: True)
    fn = jax.jit(ss.selective_scan)
    args = draw(1, 128, 128, 8)[:6]
    before = counts()
    fn(*args), fn(args[0] + 1, *args[1:]), fn(*args)
    assert moved(before) == {"kernel": 1, "plain": 0}
    odd = draw(1, 40, 128, 8)[:6]
    fn(*odd), fn(*odd)                      # 40 tokens: no block of 128
    assert moved(before) == {"kernel": 1, "plain": 1}


def test_monitor_agent_exports_the_two_counts(monkeypatch):
    from horovod_tpu.monitor.agent import MonitorAgent

    class Engine:
        monitor = None

    args = draw(1, 128, 128, 8)[:6]
    agent = MonitorAgent(engine=Engine())
    try:
        first = agent.registry.snapshot()
        ss.selective_scan(*args)
        monkeypatch.setattr(ss, "kernel_enabled", lambda: True)
        ss.selective_scan(*args)
        ss.selective_scan(*args)
        second = agent.registry.snapshot()
        text = agent.registry.to_prometheus('rank="0"')
    finally:
        agent.close()

    def value(snap, name):
        return snap[name]["value"] if isinstance(snap[name], dict) \
            else snap[name]

    assert value(second, "hvd_selective_scan_kernel_total") \
        - value(first, "hvd_selective_scan_kernel_total") == 2
    assert value(second, "hvd_selective_scan_plain_total") \
        - value(first, "hvd_selective_scan_plain_total") == 1
    assert "hvd_selective_scan_kernel_total" in text
    assert "hvd_selective_scan_plain_total" in text


# --------------------------------------------------------------- who calls it
def lowered_step(module):
    opt = optax.adam(1e-3)
    cfg = module.tiny()
    tokens = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    params = jax.eval_shape(lambda k: module.init_params(cfg, k), KEY)
    return jax.jit(module.make_train_step(cfg, opt)).lower(
        params, jax.eval_shape(opt.init, params), tokens, tokens)


def test_on_the_cpu_a_jamba_step_is_plain_and_holds_no_kernel():
    from horovod_tpu.models import jamba
    before = counts()
    text = lowered_step(jamba).as_text()
    # the layers share one traced body where jax caches it: a site at least
    assert moved(before)["kernel"] == 0
    assert 1 <= moved(before)["plain"] <= 3
    assert "tpu_custom_call" not in text and "selective_scan_" not in text


def test_the_mamba2_family_never_calls_it():
    from horovod_tpu.models import nemotron_h
    before = counts()
    lowered_step(nemotron_h)
    assert moved(before) == {"kernel": 0, "plain": 0}
