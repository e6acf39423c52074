"""The recurrent mixers' causal convolution (``ops/causal_conv.py``): the
Pallas kernel pair in interpret mode on the CPU against the plain
formulation that ``models/gated_delta.py`` ``causal_conv_silu`` keeps for
every backend but a TPU — values and the gradients of ``x``, ``kernel`` and
``bias``; sequences of a batch that see nothing of each other; the halo
across a tile's and a chunk's edge in both directions; the float32 sums of
``dkernel`` and ``dbias``; which shapes take which branch and the counts
that say so (``trace.causal_conv``, the two ``/metrics`` series); and who
else lowers the changed code: the three hybrid families, never ``resnet``
or ``llama``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from horovod_tpu import trace
from horovod_tpu.models import gated_delta
from horovod_tpu.ops import causal_conv

KEY = jax.random.PRNGKey(39)
plain = gated_delta.causal_conv_silu        # on the CPU: the plain branch


def draw(B, T, C, taps, dtype, bias, key=KEY):
    ks = jax.random.split(key, 4)
    return (jax.random.normal(ks[0], (B, T, C)).astype(dtype),
            (0.5 * jax.random.normal(ks[1], (taps, C))).astype(dtype),
            jax.random.normal(ks[2], (C,)).astype(dtype) if bias else None,
            jax.random.normal(ks[3], (B, T, C)).astype(dtype))


def kernel_fn(tile_t, tile_c, chunk=None):
    return lambda x, k, b: causal_conv.causal_conv_silu(
        x, k, b, tile_t=tile_t, tile_c=tile_c, chunk=chunk, interpret=True)


def gap(got, want):
    """Largest difference over the largest value, in float32."""
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                  1e-30))


def counts():
    return dict(trace.causal_conv)


def moved(before):
    return {k: v - before[k] for k, v in counts().items()}


# B of 1 and 3; T of one tile and of several (and of several chunks a
# tile); C of one and several lane tiles
GEOMETRIES = [
    pytest.param((1, 32, 128), (32, 128, 32), id="b1-one-tile"),
    pytest.param((3, 64, 256), (16, 128, 16), id="b3-four-tiles-two-lanes"),
    pytest.param((3, 128, 256), (64, 256, 16), id="b3-two-tiles-of-chunks"),
]
# one rounding to bfloat16 at y, dx, dkernel, dbias: an element may land
# on the neighbouring bfloat16 where the float32 before it differs in its
# last bits
TOLERANCE = {jnp.float32: 2e-6, jnp.bfloat16: 2.0 ** -7}


@pytest.mark.parametrize("taps", [2, 4])
@pytest.mark.parametrize("bias", [False, True], ids=["no-bias", "bias"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("shape, tile", GEOMETRIES)
def test_values_and_gradients_are_the_plain_branchs(shape, tile, dtype, bias,
                                                    taps):
    x, k, b, w = draw(*shape, taps, dtype, bias)
    args = (0, 1, 2) if bias else (0, 1)

    def value_and_grads(f):
        """One program a branch: op by op a case compiles some thirty."""
        through = lambda x, k, b: jnp.sum(
            f(x, k, b).astype(jnp.float32) * w.astype(jnp.float32))
        return jax.jit(lambda x, k, b: (f(x, k, b), jax.grad(
            through, argnums=args)(x, k, b)))(x, k, b)

    (y, got), (y_plain, want) = (value_and_grads(kernel_fn(*tile)),
                                 value_and_grads(plain))
    assert y.dtype == dtype
    assert gap(y, y_plain) <= TOLERANCE[dtype]
    for g, r, name in zip(got, want, ("x", "kernel", "bias")):
        assert g.dtype == r.dtype and g.shape == r.shape
        assert gap(g, r) <= TOLERANCE[dtype], name


@pytest.mark.parametrize("taps", [2, 4])
def test_nothing_leaks_from_one_sequence_into_the_next(taps):
    """Each sequence of the batch alone gives its rows of the batched
    result and gradient; its first ``taps - 1`` outputs see zeros."""
    x, k, b, w = draw(3, 64, 128, taps, jnp.float32, True)
    fn = kernel_fn(16, 128)
    # one program a shape: the three sequences alone share theirs
    both = jax.jit(lambda x, w: (fn(x, k, b), jax.grad(
        lambda x: jnp.sum(fn(x, k, b) * w))(x)))
    whole, dx = both(x, w)
    for i in range(3):
        alone, dx_alone = both(x[i:i + 1], w[i:i + 1])
        assert np.array_equal(np.asarray(alone[0]), np.asarray(whole[i]))
        assert np.array_equal(np.asarray(dx_alone[0]), np.asarray(dx[i]))
        for t in range(taps - 1):
            pre = sum(np.asarray(k[taps - 1 - s], np.float64)
                      * np.asarray(x[i, t - s], np.float64)
                      for s in range(t + 1)) + np.asarray(b, np.float64)
            assert np.allclose(whole[i, t], pre / (1 + np.exp(-pre)),
                               atol=1e-5)


@pytest.mark.parametrize("checkpointed", [False, True],
                         ids=["grad-of-a-sum", "under-checkpoint"])
@pytest.mark.parametrize("bias", [False, True], ids=["no-bias", "bias"])
def test_the_gradient_of_a_sum_through_it(checkpointed, bias):
    x, k, b, _ = draw(2, 64, 256, 4, jnp.bfloat16, bias)

    def loss(f):
        f = jax.checkpoint(f) if checkpointed else f
        return lambda x, k, b: jnp.sum(f(x, k, b).astype(jnp.float32))

    args = (0, 1, 2) if bias else (0, 1)
    got = jax.jit(jax.grad(loss(kernel_fn(32, 128, 16)), argnums=args))(
        x, k, b)
    want = jax.grad(loss(plain), argnums=args)(x, k, b)
    for g, r in zip(got, want):
        assert gap(g, r) <= TOLERANCE[jnp.bfloat16]


@pytest.mark.parametrize("bias", [False, True], ids=["no-bias", "bias"])
def test_dkernel_and_dbias_are_float32_sums_over_every_token(bias):
    """T = 4096 in eight tiles, B = 2: the sums against float64 NumPy to
    1e-6 of their largest element."""
    B, T, C, taps = 2, 4096, 128, 4
    x, k, b, w = draw(B, T, C, taps, jnp.float32, bias)
    args = (1, 2) if bias else (1,)
    got = jax.grad(lambda x, k, b: jnp.sum(kernel_fn(512, 128)(x, k, b) * w),
                   argnums=args)(x, k, b)
    x64, k64, w64 = (np.asarray(a, np.float64) for a in (x, k, w))
    padded = np.pad(x64, ((0, 0), (taps - 1, 0), (0, 0)))
    windows = [padded[:, j:j + T] for j in range(taps)]
    pre = sum(k64[j] * windows[j] for j in range(taps))
    if bias:
        pre = pre + np.asarray(b, np.float64)
    sig = 1.0 / (1.0 + np.exp(-pre))
    dpre = w64 * sig * (1.0 + pre * (1.0 - sig))
    dk = np.stack([np.sum(dpre * windows[j], axis=(0, 1))
                   for j in range(taps)])
    assert got[0].dtype == jnp.float32
    assert np.max(np.abs(np.asarray(got[0]) - dk)) <= 1e-6 * np.max(
        np.abs(dk))
    if bias:
        db = np.sum(dpre, axis=(0, 1))
        assert np.max(np.abs(np.asarray(got[1]) - db)) <= 1e-6 * np.max(
            np.abs(db))


@pytest.mark.parametrize("shape, taps, dtype, want", [
    pytest.param((2, 8192, 8192), 4, jnp.bfloat16, (1024, 512),
                 id="qwen3next"),
    pytest.param((1, 16384, 11520), 4, jnp.bfloat16, (1024, 384),
                 id="olmo-hybrid"),
    pytest.param((1, 8192, 10240), 4, jnp.bfloat16, (1024, 512),
                 id="nemotron3"),
    pytest.param((1, 8192, 1024), 4, jnp.float32, (512, 512),
                 id="float32-half-the-rows"),
    pytest.param((1, 48, 256), 2, jnp.float32, (48, 256),
                 id="a-tile-the-whole"),
    pytest.param((1, 8208, 128), 4, jnp.float32, (912, 128),
                 id="the-largest-divisor"),
    pytest.param((2, 64, 96), 4, jnp.float32, None, id="c96-no-lane-tile"),
    pytest.param((2, 100, 128), 4, jnp.float32, None,
                 id="t100-no-row-tile"),
    pytest.param((2, 64, 128), 9, jnp.float32, None, id="nine-taps"),
    pytest.param((64, 128), 4, jnp.float32, None, id="no-batch-axis"),
])
def test_tiles_are_chosen_from_shape_and_type(shape, taps, dtype, want):
    assert causal_conv.tiles(shape, taps, dtype) == want


def test_a_shape_without_tiles_is_refused_by_the_kernels_entry():
    x, k, b, _ = draw(1, 100, 128, 4, jnp.float32, False)
    with pytest.raises(ValueError, match="no tiles"):
        causal_conv.causal_conv_silu(x, k, b, interpret=True)


@pytest.mark.parametrize("shape, taps, branch", [
    pytest.param((2, 64, 96), 4, "plain", id="c96"),
    pytest.param((2, 100, 128), 4, "plain", id="t100"),
    pytest.param((2, 64, 128), 9, "plain", id="nine-taps"),
    pytest.param((2, 64, 128), 4, "kernel", id="fits"),
    pytest.param((1, 32, 256), 2, "kernel", id="fits-two-taps"),
])
def test_the_branch_follows_backend_and_shape(monkeypatch, shape, taps,
                                              branch):
    """With the backend said to be a TPU the shape decides (the kernel is
    interpreted here); on the CPU every shape is plain."""
    x, k, b, _ = draw(*shape, taps, jnp.float32, True)
    before = counts()
    want = plain(x, k, b)
    assert moved(before) == {"kernel": 0, "plain": 1}
    monkeypatch.setattr(causal_conv, "kernel_enabled", lambda: True)
    before = counts()
    got = gated_delta.causal_conv_silu(x, k, b)
    assert moved(before) == {"kernel": int(branch == "kernel"),
                             "plain": int(branch == "plain")}
    assert gap(got, want) <= TOLERANCE[jnp.float32]
    jaxpr = str(jax.make_jaxpr(gated_delta.causal_conv_silu)(x, k, b))
    assert ("pallas_call" in jaxpr) == (branch == "kernel")


def test_one_trace_a_signature(monkeypatch):
    monkeypatch.setattr(causal_conv, "kernel_enabled", lambda: True)
    fn = jax.jit(gated_delta.causal_conv_silu)
    x, k, b, _ = draw(1, 32, 128, 4, jnp.float32, True)
    before = counts()
    fn(x, k, b), fn(x + 1, k, b), fn(x, k, b)
    assert moved(before) == {"kernel": 1, "plain": 0}
    x2, k2, b2, _ = draw(1, 40, 128, 4, jnp.float32, True)
    fn(x2, k2, b2), fn(x2, k2, b2)          # 40 rows: no tile of 16s
    assert moved(before) == {"kernel": 1, "plain": 1}


def test_monitor_agent_exports_the_two_counts(monkeypatch):
    from horovod_tpu.monitor.agent import MonitorAgent

    class Engine:
        monitor = None

    x, k, b, _ = draw(1, 32, 128, 4, jnp.float32, False)
    agent = MonitorAgent(engine=Engine())
    try:
        first = agent.registry.snapshot()
        gated_delta.causal_conv_silu(x, k, b)
        monkeypatch.setattr(causal_conv, "kernel_enabled", lambda: True)
        gated_delta.causal_conv_silu(x, k, b)
        gated_delta.causal_conv_silu(x, k, b)
        second = agent.registry.snapshot()
        text = agent.registry.to_prometheus('rank="0"')
    finally:
        agent.close()

    def value(snap, name):
        return snap[name]["value"] if isinstance(snap[name], dict) \
            else snap[name]

    assert value(second, "hvd_causal_conv_kernel_total") \
        - value(first, "hvd_causal_conv_kernel_total") == 2
    assert value(second, "hvd_causal_conv_plain_total") \
        - value(first, "hvd_causal_conv_plain_total") == 1
    assert "hvd_causal_conv_kernel_total" in text
    assert "hvd_causal_conv_plain_total" in text


# ------------------------------------------------- who lowers the changed code
def shapes_of(tree):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)


def lowered_step(family):
    """The family's training step at test size, lowered for this backend
    (the CPU) from shapes alone."""
    from horovod_tpu.models import (llama, nemotron_h, olmo_hybrid,
                                    qwen3_next, resnet)
    # A checkpointed region another file's test traced on this worker is
    # kept, and the counts below move when a region is traced.
    jax.clear_caches()
    opt = optax.adam(1e-3)
    tokens = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    if family == "resnet":
        cfg = resnet.ResNetConfig(depth=18, num_classes=10, width=8,
                                  sync_bn_axis=None)
        params, stats = jax.eval_shape(
            lambda k: resnet.init_params(cfg, k), KEY)
        return jax.jit(resnet.make_train_step(cfg, opt, axis_name=None)
                       ).lower(params, stats, jax.eval_shape(opt.init, params),
                               jax.ShapeDtypeStruct((2, 32, 32, 3),
                                                    jnp.float32),
                               jax.ShapeDtypeStruct((2,), jnp.int32))
    module = {"llama": llama, "qwen3_next": qwen3_next,
              "olmo_hybrid": olmo_hybrid, "nemotron_h": nemotron_h}[family]
    cfg = (llama.tiny(dp_axis=None, tp_axis=None, sp_axis=None,
                      sliding_window=32, use_flash=False)
           if family == "llama" else module.tiny())
    params = jax.eval_shape(lambda k: module.init_params(cfg, k), KEY)
    return jax.jit(module.make_train_step(cfg, opt)).lower(
        params, jax.eval_shape(opt.init, params), tokens, tokens)


@pytest.mark.parametrize("family", ["resnet", "llama"])
def test_a_step_that_has_no_recurrent_layer_never_calls_it(family):
    before = counts()
    lowered_step(family)
    assert moved(before) == {"kernel": 0, "plain": 0}


@pytest.mark.parametrize("family, sites", [
    ("qwen3_next", 3), ("olmo_hybrid", 3), ("nemotron_h", 5)])
def test_on_the_cpu_a_hybrid_step_is_plain_and_holds_no_kernel(family, sites):
    """``plain`` alone moves; nothing of Pallas in what the CPU would
    compile."""
    before = counts()
    text = lowered_step(family).as_text()
    # the layers share one traced body where jax caches it: a site at least
    assert moved(before)["kernel"] == 0
    assert 1 <= moved(before)["plain"] <= sites
    # (the CPU's triangular solve is a custom call to lapack: not a kernel)
    assert "tpu_custom_call" not in text and "causal_conv_" not in text
