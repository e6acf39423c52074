"""The wrapped optimizer's update, compiled once per ``DistributedOptimizer``
(``jax/optimizer.py`` ``_InnerUpdate``): entered with concrete gradients
only, traced once per input signature, bitwise ``jax.jit(optimizer.update)``,
never entered under a trace, and falling back for good on a transformation
that cannot be traced.  The span's ``compiled`` id is
``tests/test_trace_spans.py``'s; the multi-process sharded paths' bitwise
agreement is ``tests/data/worker_sharded.py`` / ``worker_fsdp.py``'s.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from horovod_tpu import trace
from horovod_tpu.compat import shard_map
from horovod_tpu.jax import optimizer as opt_mod


def tree(seed, scale=1.0):
    rng = np.random.RandomState(seed)
    return {"w": jnp.asarray(rng.randn(7, 5).astype(np.float32) * scale),
            "b": jnp.asarray(rng.randn(5).astype(np.float32) * scale),
            "s": jnp.asarray(np.float32(rng.randn() * scale))}


def counting(tx):
    """``tx`` whose ``update`` bumps a Python counter each time Python
    runs it: once a trace when compiled, once a call when not."""
    runs = []

    def update(g, s, p=None):
        runs.append(1)
        return tx.update(g, s, p)

    return optax.GradientTransformation(tx.init, update), runs


def counts():
    return dict(trace.inner_update)


def bitwise(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    return all(np.array_equal(np.asarray(x), np.asarray(y)) and
               np.asarray(x).dtype == np.asarray(y).dtype
               for x, y in zip(la, lb))


# ------------------------------------------- (a) traced once a signature
@pytest.mark.parametrize("wrap", [
    dict(), dict(backward_passes_per_step=2), dict(sharded=True),
    dict(sharded="full")], ids=["k1", "k2", "sharded", "full"])
def test_traces_once_per_signature_and_never_in_steady_state(hvd, wrap):
    """Every eager site of the wrapper (the two replicated ones, and the
    sharded modes' single-controller degrade) shares the one callable."""
    tx, runs = counting(optax.sgd(0.1, momentum=0.9))
    opt = hvd.DistributedOptimizer(tx, **wrap)
    params = tree(0)
    state = opt.init(params)
    before = counts()
    for i in range(6):
        updates, state = opt.update(tree(i + 1), state, params)
    applied = 6 // wrap.get("backward_passes_per_step", 1)
    # init's state may differ in weak type from a step's: at most two
    assert 1 <= len(runs) <= 2
    after = counts()
    assert after["traces"] - before["traces"] == len(runs)
    assert after["compiled"] - before["compiled"] == applied
    for i in range(4):                      # steady state: no trace at all
        updates, state = opt.update(tree(i + 7), state, params)
    assert counts()["traces"] == after["traces"]
    # another signature (a leaf's shape) traces once more, then never
    params2 = {"w": jnp.ones((3, 2))}
    state2 = opt.init(params2)
    n = len(runs)
    for i in range(4):
        updates, state2 = opt.update({"w": jnp.full((3, 2), 1.0 + i)},
                                     state2, params2)
    assert 1 <= len(runs) - n <= 2
    assert np.all(np.isfinite(np.asarray(updates["w"])))


def test_one_callable_per_wrapper_built_at_wrap_time(hvd, monkeypatch):
    """``jax.jit`` is called when the optimizer is wrapped and never from
    ``update``: a fresh jit a call would retrace every step."""
    made = []
    real = jax.jit

    def spy(f, *a, **k):
        made.append(getattr(f, "__name__", "?"))
        return real(f, *a, **k)

    monkeypatch.setattr(opt_mod.jax, "jit", spy)
    opt = hvd.DistributedOptimizer(optax.adam(1e-2))
    assert made == ["hvd_inner_update"]
    params = tree(0)
    state = opt.init(params)
    for i in range(3):
        _, state = opt.update(tree(i + 1), state, params)
    assert made == ["hvd_inner_update"]


# --------------------------------- (b) bitwise jax.jit(optimizer.update)
@pytest.mark.parametrize("with_params", [True, False],
                         ids=["params", "params_none"])
@pytest.mark.parametrize("make", [
    lambda: optax.sgd(0.05, momentum=0.9), lambda: optax.adam(1e-2)],
    ids=["sgd_momentum", "adam"])
def test_eager_result_is_bitwise_the_jitted_update(hvd, make, with_params):
    tx = make()
    opt = hvd.DistributedOptimizer(tx)
    reference = jax.jit(tx.update)
    params = tree(0) if with_params else None
    state, ref_state = opt.init(tree(0)), tx.init(tree(0))
    for i in range(4):
        grads = tree(i + 1, scale=3.0)
        updates, state = opt.update(grads, state, params)
        ref_updates, ref_state = reference(grads, ref_state, params)
        assert bitwise(updates, ref_updates)
        assert bitwise(state.inner_state, ref_state)
    assert int(state.counter) == 4


def test_old_state_survives_the_update(hvd):
    """No donation: the caller may hold the state it passed in."""
    opt = hvd.DistributedOptimizer(optax.sgd(0.1, momentum=0.9))
    params = tree(0)
    state = opt.init(params)
    _, state = opt.update(tree(1), state, params)
    held = jax.tree_util.tree_map(np.asarray, state.inner_state)
    _, newer = opt.update(tree(2), state, params)
    assert bitwise(state.inner_state, held)          # still readable, same
    assert not bitwise(newer.inner_state, held)


# ------------------------------------------- (c) never entered under a trace
@pytest.fixture()
def spied(monkeypatch):
    """Every ``_InnerUpdate`` built from here on records the calls into
    its compiled callable."""
    entered = []

    class Spied(opt_mod._InnerUpdate):
        def __init__(self, optimizer):
            super().__init__(optimizer)
            compiled = self._compiled

            def recorded(*a):
                entered.append(1)
                return compiled(*a)

            self._compiled = recorded

    monkeypatch.setattr(opt_mod, "_InnerUpdate", Spied)
    return entered


@pytest.mark.parametrize("how", ["jit", "shard_map", "jit_k2"])
def test_traced_update_never_enters_the_compiled_callable(hvd, spied, how):
    k = 2 if how == "jit_k2" else 1
    opt = hvd.DistributedOptimizer(optax.sgd(0.1, momentum=0.9),
                                   backward_passes_per_step=k)
    params = {"w": jnp.ones((8, 4))}
    state = opt.init(params)

    def update(g, s, p):
        return opt.update(g, s, p)[0]

    before = counts()
    if how == "shard_map":
        fn = jax.jit(shard_map(
            update, mesh=hvd.mesh(), in_specs=(P("hvd"), P(), P("hvd")),
            out_specs=P("hvd"), check_vma=False))
    else:
        fn = jax.jit(update)
    text = fn.lower(params, state, params).as_text()
    jax.block_until_ready(fn(params, state, params))
    assert spied == [] and counts() == before
    assert "hvd_inner_update" not in text
    for _ in range(k):              # the spy does see an eager, applied call
        _, state = opt.update(params, state, params)
    assert spied == [1]


def test_sharded_train_step_program_holds_no_nested_call(hvd, spied):
    """The lowered text of a tiny ``make_sharded_train_step``: the
    optimizer is inlined as before, no call into the compiled callable."""
    from horovod_tpu.models import mnist
    opt = hvd.DistributedOptimizer(optax.sgd(0.1, momentum=0.9))
    params = mnist.init_params(jax.random.PRNGKey(0))
    state = opt.init(params)
    x, y = mnist.synthetic_batch(16, seed=0)
    step = mnist.make_sharded_train_step(opt, hvd.mesh())
    before = counts()
    text = step.lower(params, state, jnp.asarray(x), jnp.asarray(y)).as_text()
    assert "hvd_inner_update" not in text
    assert spied == [] and counts() == before


# ------------------------------------- (d) a transformation that branches
def branching():
    """Flips the sign where the gradient's sum is positive — decided in
    Python, on the value: not traceable."""
    runs = []

    def update(g, s, p=None):
        runs.append(1)
        if float(g["w"].sum()) > 0:
            g = jax.tree_util.tree_map(lambda x: -x, g)
        return g, s

    return optax.GradientTransformation(lambda p: optax.EmptyState(),
                                        update), runs


@pytest.mark.parametrize("error", ["bool", "array", "int"])
def test_untraceable_transformation_falls_back_once(hvd, error):
    needs = {"bool": lambda v: bool(v > 0), "array": lambda v: np.asarray(v),
             "int": lambda v: range(int(v))}[error]
    runs = []

    def update(g, s, p=None):
        runs.append(1)
        needs(g["w"].sum().astype(jnp.int32) if error == "int"
              else g["w"].sum())
        return jax.tree_util.tree_map(lambda x: -2.0 * x, g), s

    tx = optax.GradientTransformation(lambda p: optax.EmptyState(), update)
    opt = hvd.DistributedOptimizer(tx)
    params = {"w": jnp.ones((4,))}
    state = opt.init(params)
    before = counts()
    for i in range(3):
        updates, state = opt.update({"w": jnp.full((4,), 1.0 + i)}, state,
                                    params)
        assert np.array_equal(np.asarray(updates["w"]),
                              np.full((4,), -2.0 * (1.0 + i), np.float32))
    after = counts()
    # the one failed trace, then the direct call each time: no retry
    assert after["traces"] - before["traces"] == 1
    assert after["compiled"] == before["compiled"]
    assert len(runs) == 1 + 3


def test_fallback_yields_the_direct_result_and_compiled_0(hvd):
    tx, runs = branching()
    inner = opt_mod._InnerUpdate(tx)
    seen = []

    class Span:                      # what ProgramSpan is to the helper
        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

        def set(self, **ids):
            seen.append(ids)

    for sign in (1.0, -1.0):
        g = {"w": jnp.full((3,), sign)}
        out, _ = inner(g, optax.EmptyState(), None, Span())
        direct, _ = tx.update(g, optax.EmptyState(), None)
        assert bitwise(out, direct)
        assert float(out["w"][0]) == -1.0
    assert seen == [{"compiled": 0}, {"compiled": 0}]
    assert inner._compiled is None
    assert len(runs) == 1 + 2 + 2    # the failed trace; ours and the direct


def test_other_exceptions_propagate_and_do_not_disable(hvd):
    calls = []

    def update(g, s, p=None):
        calls.append(1)
        if len(calls) == 1:
            raise ValueError("not a concretization error")
        return g, s

    tx = optax.GradientTransformation(lambda p: optax.EmptyState(), update)
    inner = opt_mod._InnerUpdate(tx)
    g = {"w": jnp.ones((2,))}
    with pytest.raises(ValueError, match="not a concretization"):
        inner(g, optax.EmptyState(), None)
    before = counts()
    inner(g, optax.EmptyState(), None)
    assert inner._compiled is not None
    assert counts()["compiled"] == before["compiled"] + 1


# ------------------------------------------------- (e) the exported counts
def test_monitor_agent_exports_the_two_counts(hvd):
    from horovod_tpu.monitor.agent import MonitorAgent

    class Engine:
        monitor = None

    opt = hvd.DistributedOptimizer(optax.sgd(0.1))
    params = {"w": jnp.ones((2,))}
    state = opt.init(params)
    agent = MonitorAgent(engine=Engine())
    try:
        first = agent.registry.snapshot()
        for _ in range(3):
            _, state = opt.update(params, state, params)
        second = agent.registry.snapshot()
    finally:
        agent.close()

    def value(snap, name):
        return snap[name]["value"] if isinstance(snap[name], dict) \
            else snap[name]

    assert value(second, "hvd_inner_update_compiled_total") \
        - value(first, "hvd_inner_update_compiled_total") == 3
    assert 1 <= value(second, "hvd_inner_update_traces_total") \
        - value(first, "hvd_inner_update_traces_total") <= 2
    text = agent.registry.to_prometheus('rank="0"')
    assert "hvd_inner_update_compiled_total" in text
    assert "hvd_inner_update_traces_total" in text
