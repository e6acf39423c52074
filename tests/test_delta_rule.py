"""The chunked gated delta rule's kernel pair (``ops/delta_rule.py``) in
Pallas interpret mode on the CPU: values and all five gradients against
the plain formulation (``models/gated_delta.py``
``chunked_gated_delta_rule``) and against the token-by-token recurrence
(``benchmark/reference/qwen3_next.py`` ``recurrence``) — lengths that are
and are not multiples of the chunk and of the kernel's block, decay 0.999
and 0.9, beta over (0, 2) and every beta at 1.999, ``dk != dv``, keys
shared by two value heads, bfloat16 and float32; widths that are no
multiple of 128 (96 x 192, 96 x 128: run at 128 x 256 and 128 x 128 on
zero-padded heads, and nothing of the padding reaches a result);
sequences and heads that see nothing of each other; the gradient through
``jax.checkpoint``; the in-kernel inverse against numpy's; which shapes
take which branch of ``gated_delta_net`` and the counts that say so
(``trace.delta_rule``, the three ``/metrics`` series).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark.reference import qwen3_next as ref
from horovod_tpu import trace
from horovod_tpu.models import gated_delta
from horovod_tpu.ops import delta_rule

plain_rule = gated_delta.chunked_gated_delta_rule


def draw(B, T, hk, hv, dk, dv, dtype=jnp.float32, decay=0.999, beta=None,
         seed=45):
    """Unit keys, queries of length ``dk ** -0.5``, a per-token decay
    between ``decay`` and 1, beta uniform over (0, 2) or every one at
    ``beta``, and a weight for the outputs."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (B, T, hk, dk))) / np.sqrt(dk)
    k = unit(jax.random.normal(ks[1], (B, T, hk, dk)))
    v = jax.random.normal(ks[2], (B, T, hv, dv))
    g = jnp.log(decay) * jax.random.uniform(ks[3], (B, T, hv))
    b = (2.0 * jax.random.uniform(ks[4], (B, T, hv)) if beta is None
         else jnp.full((B, T, hv), beta))
    w = jax.random.normal(ks[5], (B, T, hv, dv))
    return (q.astype(dtype), k.astype(dtype), v.astype(dtype), g, b), w


@functools.lru_cache(maxsize=None)     # one rule a tiling: see ``_both``
def kernel_rule(block=None, group=None, chunk=64):
    return lambda q, k, v, g, beta: delta_rule.gated_delta_rule(
        q, k, v, g, beta, chunk, block=block, group=group, interpret=True)


def at_value_heads(rule, chunk=64):
    """``rule`` (the plain signature: q and k a copy a value head) on q
    and k at the key heads."""
    def fn(q, k, v, g, beta):
        copies = v.shape[2] // q.shape[2]
        return rule(jnp.repeat(q, copies, axis=2),
                    jnp.repeat(k, copies, axis=2), v, g, beta, chunk)
    return fn


@functools.lru_cache(maxsize=None)
def _both(rule):
    """One jitted program a rule, kept: cases that differ in their data
    and not in their shapes share its compilation."""
    @jax.jit
    def both(w, *args):
        o, pull = jax.vjp(rule, *args)
        return (o,) + pull(w.astype(o.dtype))
    return both


PLAIN = at_value_heads(plain_rule)
RECURRENCE = at_value_heads(lambda *a: ref.recurrence(*a[:5]))


def with_gradients(rule, args, w):
    """``(o, dq, dk, dv, dg, dbeta)`` of ``sum(o * w)``, in float32 (one
    jitted program: op by op the plain branch alone takes seconds)."""
    return tuple(np.asarray(x, np.float32) for x in _both(rule)(w, *args))


def gaps(got, want):
    """Largest difference over the largest value, an array each."""
    return [float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))
            for a, b in zip(got, want)]


def counts():
    return dict(trace.delta_rule)


def moved(before):
    return {k: v - before[k] for k, v in counts().items()}


# (B, T, key heads, value heads, dk, dv), (block, group): one chunk; a
# length that is no multiple of the chunk, keys shared by two value heads
# (their problems stacked two a product); two blocks of two chunks, a
# value wider than a key, a head's consecutive chunks stacked; a length
# that is no multiple of the block
GEOMETRIES = [
    pytest.param((1, 64, 1, 1, 128, 128), (1, 1), id="one-chunk"),
    pytest.param((2, 100, 1, 2, 128, 128), (2, 1), id="t100-shared-keys"),
    pytest.param((1, 256, 1, 1, 128, 256), (2, 2), id="dv256-two-blocks"),
    pytest.param((1, 136, 2, 2, 128, 128), (2, 2), id="t136-two-heads"),
]
# widths that are no multiple of the lanes, run rounded up on zero-padded
# heads: ``olmo_hybrid``'s 96 x 192 (at 128 x 256, a head's two chunks
# stacked), and a key alone padded under two value heads of 128
PADDED = [
    pytest.param((1, 136, 2, 2, 96, 192), (2, 2), id="t136-96x192"),
    pytest.param((2, 100, 1, 2, 96, 128), (2, 1), id="t100-96x128-shared"),
]
# float32: the sums' order; bfloat16: the products round their operands
# where the plain formulation does, one more rounding of ``k beta`` there
TOLERANCE = {jnp.float32: 2e-5, jnp.bfloat16: 3e-2}


@pytest.mark.parametrize("shape, tile", GEOMETRIES + PADDED)
def test_values_and_gradients_are_the_plain_branchs(shape, tile,
                                                    dtype=jnp.float32,
                                                    beta=None):
    args, w = draw(*shape, dtype, beta=beta)
    got = with_gradients(kernel_rule(*tile), args, w)
    want = with_gradients(PLAIN, args, w)
    # the primals' own shapes: a padded width is cut off again
    assert [x.shape for x in got] == [x.shape for x in want]
    assert max(gaps(got, want)) <= TOLERANCE[dtype], gaps(got, want)


@pytest.mark.parametrize("shape, tile", GEOMETRIES[1:3] + PADDED)
def test_values_and_gradients_in_bfloat16(shape, tile):
    test_values_and_gradients_are_the_plain_branchs(shape, tile,
                                                    jnp.bfloat16)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("shape, tile", PADDED)
def test_padded_widths_with_every_beta_at_1_999(shape, tile, dtype):
    """``olmo_hybrid`` draws beta over (0, 2): the range where the
    in-kernel inverse has most to lose, at the widths it pads."""
    test_values_and_gradients_are_the_plain_branchs(shape, tile, dtype,
                                                    beta=1.999)


def test_nothing_of_the_padding_reaches_a_result(monkeypatch):
    """The kernels get zeros in the padded columns of q, k and v; and
    were v's (and q's) padded columns anything else, ``o`` would be the
    same, bit for bit: they meet zero rows of the state and zero columns
    of k, and give columns of ``o`` that are cut off."""
    args, _ = draw(1, 136, 1, 2, 96, 192)
    rule = lambda *a: delta_rule.gated_delta_rule(*a, 64, block=2, group=2,
                                                  interpret=True)
    want = rule(*args)
    assert want.shape == (1, 136, 2, 192)
    core, seen = delta_rule._rule_core, []

    def garbage(x, width, run):
        return jnp.where(jnp.arange(x.shape[-1]) % run >= width, 7.0, x)

    def double(q, k, v, g, beta, static):
        dk, dv = static[3:5]
        assert (dk, dv) == (128, 256)
        assert q.shape[-1] == k.shape[-1] == dk and v.shape[-1] == 2 * dv
        seen.append(all(
            not np.asarray(x).reshape(x.shape[:2] + (-1, run))[..., width:]
            .any() for x, width, run in ((q, 96, dk), (k, 96, dk),
                                         (v, 192, dv))))
        o = core(garbage(q, 96, dk), k, garbage(v, 192, dv), g, beta, static)
        assert o.shape == v.shape
        return o

    monkeypatch.setattr(delta_rule, "_rule_core", double)
    got = rule(*args)
    assert seen == [True]
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("beta", [None, 1.999], ids=["beta-0-2", "beta-1.999"])
@pytest.mark.parametrize("decay", [0.999, 0.9])
def test_values_and_gradients_are_the_recurrences(decay, beta):
    """Against the rule a token at a time, which has no chunk, no solve and
    no state handed on: every beta at 1.999 is where ``I - beta k k^T``
    all but flips a component and the in-kernel inverse has most to
    lose."""
    args, w = draw(2, 150, 1, 2, 128, 128, decay=decay, beta=beta)
    with jax.default_matmul_precision("highest"):
        want = with_gradients(RECURRENCE, args, w)
    got = with_gradients(kernel_rule(2, 2), args, w)
    assert max(gaps(got, want)) <= 1e-4, gaps(got, want)


def inverse(A, C):
    return delta_rule._unit_lower_inverse(A, delta_rule._masks(A.shape[0],
                                                               C))


def test_keys_of_a_chunk_that_are_all_alike():
    """One key all through a chunk with every beta at 2 and no decay: ``A``
    is 2 below the diagonal, its powers grow to 1e12 and cancel, and the
    inverse's entries are +-2.  The block recursion does not see the
    powers."""
    C = 64
    A = jnp.tril(jnp.full((C, C), 2.0), -1)
    T = inverse(A, C)
    want = np.linalg.inv(np.eye(C) + np.asarray(A, np.float64))
    assert float(np.max(np.abs(np.asarray(T) - want))) <= 1e-5
    two = jnp.zeros((2 * C, 2 * C)).at[:C, :C].set(A).at[C:, C:].set(A.T.T)
    T2 = np.asarray(inverse(two, C))
    assert np.max(np.abs(T2[:C, :C] - want)) <= 1e-5
    assert np.max(np.abs(T2[C:, C:] - want)) <= 1e-5
    assert not T2[C:, :C].any() and not T2[:C, C:].any()


def test_the_saved_inverses_cotangent_is_autodiffs():
    """The backward kernel takes the inverse the forward one saved, with
    the cotangent ``-T^T dT T^T``: what autodiff of the recursion gives."""
    key = jax.random.PRNGKey(3)
    A = jnp.tril(0.3 * jax.random.normal(key, (32, 32)), -1)
    w = jax.random.normal(jax.random.fold_in(key, 1), (32, 32))
    mine = jax.grad(lambda a: jnp.sum(
        delta_rule._saved_inverse(a, inverse(A, 32)) * w))(A)
    auto = jax.grad(lambda a: jnp.sum(inverse(a, 32) * w))(A)
    low = np.tril(np.ones((32, 32), bool), -1)
    assert np.max(np.abs(np.asarray(mine - auto))[low]) <= 1e-5 * float(
        jnp.max(jnp.abs(auto)))


def test_sequences_and_heads_see_nothing_of_each_other():
    args, _ = draw(2, 128, 2, 4, 128, 128)
    rule = jax.jit(kernel_rule(2, 2))
    o = rule(*args)
    q, k, v, g, beta = args
    # another first sequence; another first key head and its two value
    # heads
    other, _ = draw(2, 128, 2, 4, 128, 128, seed=46)
    swap = lambda a, b, at: a.at[at].set(b[at])
    first = rule(*(swap(a, b, (0,)) for a, b in zip(args, other)))
    assert np.array_equal(np.asarray(first[1]), np.asarray(o[1]))
    assert not np.array_equal(np.asarray(first[0]), np.asarray(o[0]))
    heads = rule(*(swap(a, b, (slice(None), slice(None), slice(0, n)))
                   for a, b, n in zip(args, other, (1, 1, 2, 2, 2))))
    assert np.array_equal(np.asarray(heads[:, :, 2:]), np.asarray(o[:, :, 2:]))
    assert not np.array_equal(np.asarray(heads[:, :, :2]),
                              np.asarray(o[:, :, :2]))


def test_the_state_carried_between_blocks_matters():
    """With the state dropped at a block's edge the outputs past it are
    far off: the comparisons above are not vacuous."""
    (q, k, v, g, beta), _ = draw(1, 128, 1, 1, 128, 128)
    rule = kernel_rule(1, 1)
    whole = rule(q, k, v, g, beta)
    alone = rule(*(x[:, 64:] for x in (q, k, v, g, beta)))
    assert float(jnp.max(jnp.abs(whole[:, 64:] - alone))) > 0.1 * float(
        jnp.max(jnp.abs(whole)))


def test_the_gradient_through_a_checkpoint():
    """A mixer is recomputed in the backward pass (``jax.checkpoint``
    round it): the kernel's residuals are made there again, and the
    gradient is the same."""
    args, w = draw(1, 128, 1, 2, 128, 128)
    rule = kernel_rule(2, 1)
    loss = lambda f: lambda *a: jnp.sum(f(*a) * w)
    want = jax.jit(jax.grad(loss(rule), argnums=range(5)))(*args)
    got = jax.jit(jax.grad(loss(jax.checkpoint(rule)),
                           argnums=range(5)))(*args)
    for a, b in zip(got, want):
        assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("q_shape, v_shape, chunk, dtype, want", [
    ((2, 8192, 16, 128), (2, 8192, 32, 128), 64, jnp.bfloat16, 8),
    ((2, 8192, 16, 128), (2, 8192, 32, 128), 64, jnp.float32, 8),
    ((1, 200, 1, 128), (1, 200, 2, 256), 64, jnp.bfloat16, 4),
    ((1, 64, 1, 128), (1, 64, 1, 128), 64, jnp.bfloat16, 1),
    ((1, 1000, 1, 128), (1, 1000, 1, 128), 64, jnp.bfloat16, 16),
    ((1, 1100, 1, 128), (1, 1100, 1, 128), 64, jnp.bfloat16, 8),
    ((1, 16384, 30, 96), (1, 16384, 30, 192), 64, jnp.bfloat16, 8),
    ((1, 16384, 30, 32), (1, 16384, 30, 32), 64, jnp.bfloat16, 8),
    ((2, 128, 3, 16), (2, 128, 3, 8), 64, jnp.float32, None),
    ((2, 128, 1, 16), (2, 128, 1, 128), 64, jnp.bfloat16, None),
    ((2, 128, 2, 128), (2, 128, 3, 128), 64, jnp.bfloat16, None),
    ((2, 128, 2, 128), (2, 128, 2, 128), 40, jnp.bfloat16, None),
    ((2, 128, 2, 128), (2, 128, 2, 128), 256, jnp.bfloat16, None),
    ((2, 128, 2, 128), (2, 128, 2, 128), 64, jnp.float16, None),
    ((2, 128, 256), (2, 128, 256), 64, jnp.bfloat16, None),
], ids=["qwen3next", "float32", "t200-dv256", "one-chunk", "t1000-one-block",
        "t1100-padded", "olmo-hybrid", "a-quarter-of-the-lanes",
        "narrow", "narrow-key", "heads-3-over-2", "chunk-40", "chunk-256", "float16",
        "three-axes"])
def test_tiles_by_shape_and_type(q_shape, v_shape, chunk, dtype, want):
    assert delta_rule.tiles(q_shape, v_shape, chunk, dtype) == want


@pytest.mark.parametrize("dk, dv, want", [
    (128, 128, (128, 128)), (128, 256, (128, 256)), (96, 192, (128, 256)),
    (96, 128, (128, 128)), (130, 128, (256, 128)), (64, 64, (128, 128)),
    (32, 32, (128, 128)), (31, 128, None), (128, 16, None), (16, 8, None)])
def test_widths_round_up_from_a_quarter_of_the_lanes(dk, dv, want):
    assert delta_rule.widths(dk, dv) == want


def test_a_shape_without_tiles_is_an_error():
    (q, k, v, g, beta), _ = draw(1, 64, 1, 1, 16, 128)
    with pytest.raises(ValueError, match="no tiles"):
        delta_rule.gated_delta_rule(q, k, v, g, beta, 64, interpret=True)
    (q, k, v, g, beta), _ = draw(1, 128, 1, 1, 128, 128)
    with pytest.raises(ValueError, match="groups of"):
        delta_rule.gated_delta_rule(q, k, v, g, beta, 64, block=2, group=3,
                                    interpret=True)


# ----------------------------------------------------- which branch is taken
def mixer(k_dim, v_dim, chunk=64, k_heads=1, v_heads=2, d_model=32, T=100,
          dtype=jnp.float32):
    dims = gated_delta.GatedDeltaDims(k_heads, v_heads, k_dim, v_dim,
                                      chunk=chunk, beta_scale=2.0)
    keys = iter(jax.random.split(jax.random.PRNGKey(5), 8))
    p = gated_delta.init_params(dims, d_model, dtype, keys)
    x = jax.random.normal(next(keys), (1, T, d_model)).astype(dtype)
    return x, p, dims


@pytest.mark.parametrize("k_dim, v_dim, chunk, branch", [
    pytest.param(128, 128, 64, "kernel", id="fits"),
    pytest.param(128, 256, 32, "kernel", id="fits-dv256-chunk32"),
    pytest.param(96, 192, 64, "padded", id="widths-96-192"),
    pytest.param(16, 128, 64, "plain", id="narrow-key-16"),
    pytest.param(128, 128, 40, "plain", id="chunk-40"),
])
def test_the_branch_follows_backend_and_shape(monkeypatch, k_dim, v_dim,
                                              chunk, branch):
    """With the backend said to be a TPU the shape decides (the kernel is
    interpreted here; ``padded``: the kernel at widths rounded up); on
    the CPU every shape is plain.  A caller's own ``rule`` is the plain
    branch's and never the kernel's."""
    x, p, dims = mixer(k_dim, v_dim, chunk)
    net = lambda **kw: jax.jit(lambda x, p: gated_delta.gated_delta_net(
        x, p, dims, **kw))(x, p)       # traced once: the counts hold
    before = counts()
    want = net()
    assert moved(before) == {"kernel": 0, "padded": 0, "plain": 1}
    monkeypatch.setattr(delta_rule, "kernel_enabled", lambda: True)
    called = []

    def rule(*a):
        called.append(1)
        return plain_rule(*a)

    before = counts()
    got = net(rule=rule)
    assert moved(before) == {"kernel": int(branch != "plain"),
                             "padded": int(branch == "padded"),
                             "plain": int(branch == "plain")}
    assert len(called) == int(branch == "plain")
    assert got.shape == want.shape
    assert max(gaps([np.asarray(got)], [np.asarray(want)])) <= 2e-5
    jaxpr = str(jax.make_jaxpr(
        lambda x, p: gated_delta.gated_delta_net(x, p, dims))(x, p))
    assert ("delta_rule_fwd" in jaxpr) == (branch != "plain")


@pytest.mark.parametrize("k_dim, v_dim", [(128, 128), (96, 192)],
                         ids=["128x128", "96x192-padded"])
def test_the_mixers_gradient_is_the_same_on_both_branches(monkeypatch,
                                                          k_dim, v_dim):
    x, p, dims = mixer(k_dim, v_dim)
    loss = lambda x, p: jnp.sum(jnp.square(
        gated_delta.gated_delta_net(x, p, dims)))
    want = jax.jit(jax.grad(loss, argnums=(0, 1)))(x, p)
    monkeypatch.setattr(delta_rule, "kernel_enabled", lambda: True)
    got = jax.jit(jax.grad(loss, argnums=(0, 1)))(x, p)
    leaves = jax.tree_util.tree_leaves
    assert max(gaps(map(np.asarray, leaves(got)),
                    map(np.asarray, leaves(want)))) <= 1e-4


def test_one_trace_a_signature(monkeypatch):
    monkeypatch.setattr(delta_rule, "kernel_enabled", lambda: True)
    x, p, dims = mixer(128, 128, T=64)
    fn = jax.jit(lambda x, p: gated_delta.gated_delta_net(x, p, dims))
    before = counts()
    fn(x, p), fn(x + 1, p), fn(x, p)
    assert moved(before) == {"kernel": 1, "padded": 0, "plain": 0}
    x2, p2, dims2 = mixer(16, 128, T=64)
    fn2 = jax.jit(lambda x, p: gated_delta.gated_delta_net(x, p, dims2))
    fn2(x2, p2), fn2(x2, p2)
    assert moved(before) == {"kernel": 1, "padded": 0, "plain": 1}
    x3, p3, dims3 = mixer(96, 128, T=64)
    fn3 = jax.jit(lambda x, p: gated_delta.gated_delta_net(x, p, dims3))
    fn3(x3, p3), fn3(x3, p3)
    assert moved(before) == {"kernel": 2, "padded": 1, "plain": 1}


def test_monitor_agent_exports_the_two_counts(monkeypatch):
    from horovod_tpu.monitor.agent import MonitorAgent

    class Engine:
        monitor = None

    x, p, dims = mixer(128, 128, T=64)
    x2, p2, dims2 = mixer(96, 192, T=64)
    agent = MonitorAgent(engine=Engine())
    try:
        first = agent.registry.snapshot()
        gated_delta.gated_delta_net(x, p, dims)
        monkeypatch.setattr(delta_rule, "kernel_enabled", lambda: True)
        gated_delta.gated_delta_net(x, p, dims)
        gated_delta.gated_delta_net(x2, p2, dims2)
        second = agent.registry.snapshot()
        text = agent.registry.to_prometheus('rank="0"')
    finally:
        agent.close()

    def value(snap, name):
        return snap[name]["value"] if isinstance(snap[name], dict) \
            else snap[name]

    assert value(second, "hvd_delta_rule_kernel_total") \
        - value(first, "hvd_delta_rule_kernel_total") == 2
    assert value(second, "hvd_delta_rule_padded_total") \
        - value(first, "hvd_delta_rule_padded_total") == 1
    assert value(second, "hvd_delta_rule_plain_total") \
        - value(first, "hvd_delta_rule_plain_total") == 1
    assert "hvd_delta_rule_kernel_total" in text
    assert "hvd_delta_rule_padded_total" in text
    assert "hvd_delta_rule_plain_total" in text


# ------------------------------------------------- who lowers the changed code
def lowered_step(family):
    """The family's training step at test size, lowered for this backend
    (the CPU) from shapes alone."""
    from horovod_tpu.models import llama, nemotron_h, olmo_hybrid, qwen3_next
    # A checkpointed region another file's test traced on this worker is
    # kept, and the counts below move when a region is traced.
    jax.clear_caches()
    module = {"llama": llama, "qwen3_next": qwen3_next,
              "olmo_hybrid": olmo_hybrid, "nemotron_h": nemotron_h}[family]
    cfg = (llama.tiny(dp_axis=None, tp_axis=None, sp_axis=None,
                      sliding_window=32, use_flash=False)
           if family == "llama" else module.tiny())
    opt = optax.adam(1e-3)
    tokens = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    params = jax.eval_shape(lambda k: module.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    return jax.jit(module.make_train_step(cfg, opt)).lower(
        params, jax.eval_shape(opt.init, params), tokens, tokens)


@pytest.mark.parametrize("family", ["llama", "nemotron_h"])
def test_a_step_without_the_rule_never_counts(family):
    before = counts()
    lowered_step(family)
    assert moved(before) == {"kernel": 0, "padded": 0, "plain": 0}


@pytest.mark.parametrize("family", ["qwen3_next", "olmo_hybrid"])
def test_on_the_cpu_a_step_is_plain_and_holds_no_kernel(family):
    """``plain`` alone moves; nothing of Pallas in what the CPU would
    compile."""
    before = counts()
    text = lowered_step(family).as_text()
    # the layers share one traced body where jax caches it: a site at least
    assert moved(before)["kernel"] == 0
    assert 1 <= moved(before)["plain"] <= 3
    assert "tpu_custom_call" not in text and "delta_rule_" not in text
