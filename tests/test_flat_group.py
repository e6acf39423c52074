"""A gradient group as one flat buffer a dtype from staging to the inner
update (``ops/eager.py`` ``_pack_leaves`` / ``_stage_packed`` /
``FlatGroup``, ``jax/optimizer.py`` ``allreduce_gradients`` /
``_InnerUpdate``): one engine item a dtype in order of first appearance;
reduced gradients, updates and new state bitwise those of the path that
takes a leaf an item; each input the flat path cannot take goes a leaf an
item and says so (``packed == 0``); one trace a tree signature for the
pack and for the inner program; the caller's gradients never donated; the
counts' readers.

As in ``tests/test_stage_group.py`` everything runs over a one-rank
process set of the 8-virtual-device CPU mesh with this process forced into
the per-process branch and no controller (``conftest.py``'s
``per_process``).  Two real processes: ``tests/data/worker_flat.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from horovod_tpu import trace
from horovod_tpu.jax import optimizer as opt_mod
from horovod_tpu.jax.compression import Compression
from horovod_tpu.ops import eager
from horovod_tpu.ops.engine import CollectiveType
from horovod_tpu.trace import core
from test_local_array import HalfOnThisThread
from test_trace_spans import fresh_annotation


def counts():
    return dict(trace.stage_group)


def moved(before):
    return {k: v - before[k] for k, v in counts().items()}


def mixed_tree(seed=0, ints=True):
    """float32, bfloat16 and int32 interleaved, with a 0-d, a ``[1]`` and
    an empty leaf.  (A dict flattens by sorted key: the names fix the
    order of first appearance — float32, int32, bfloat16.)"""
    rng = np.random.RandomState(seed)
    tree = {
        "a_w": jnp.asarray(rng.randn(7, 5).astype(np.float32)),
        "b_steps": jnp.asarray(rng.randint(1, 9, (6,)).astype(np.int32)),
        "c_half": jnp.asarray(rng.randn(4, 3, 2), dtype=jnp.bfloat16),
        "d_scalar": jnp.asarray(np.float32(rng.randn())),
        "e_one": jnp.asarray(rng.randn(1).astype(np.float32)),
        "f_empty": jnp.zeros((0, 3), jnp.float32),
        "g_half_vec": jnp.asarray(rng.randn(5), dtype=jnp.bfloat16),
        "h_count": jnp.asarray(np.int32(rng.randint(1, 9))),
    }
    if not ints:
        tree = {k: v for k, v in tree.items() if v.dtype != jnp.int32}
    return tree


def a_leaf_an_item(m):
    """The path every group took before: no group may travel flat."""
    m.setattr(eager, "_all_held", lambda *a: False)


def same_trees(a, b, nearly=False):
    """Leaf for leaf the same array, bitwise; with ``nearly`` floating
    values may differ in their last bits (relative 1e-5, or two units in
    the last place of a shorter dtype)."""
    assert jax.tree_util.tree_structure(a) == jax.tree_util.tree_structure(b)
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        assert type(x) is type(y)
        assert x.shape == y.shape and x.dtype == y.dtype
        assert x.weak_type == y.weak_type
        assert x.sharding == y.sharding
        if nearly and jnp.issubdtype(x.dtype, jnp.floating):
            rtol = max(1e-5, 2 * float(jnp.finfo(x.dtype).eps))
            x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
            bad = np.abs(x - y) > rtol * np.abs(y)
            assert not bad.any(), (x[bad], y[bad])
        else:
            assert np.array_equal(np.asarray(x), np.asarray(y))


def traced(monkeypatch):
    ann = fresh_annotation()
    monkeypatch.setattr(core, "_installed",
                        core.TraceRecorder(annotation=ann))
    return lambda name: [e["ids"] for e in ann.events if e["name"] == name]


# ------------------------------------ (a) what the engine is handed
def test_layout_is_a_function_of_shapes_and_dtypes_in_flatten_order():
    leaves = jax.tree_util.tree_leaves(mixed_tree())
    assert eager._flat_layout(leaves) == (
        (0, 0, (7, 5)), (1, 0, (6,)), (2, 0, (4, 3, 2)), (0, 35, ()),
        (0, 36, (1,)), (0, 37, (0, 3)), (2, 24, (5,)), (1, 6, ()))
    shapes = [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in leaves]
    assert eager._flat_layout(shapes) == eager._flat_layout(leaves)


@pytest.mark.parametrize("k,dtype,total", [
    (0, "float32", 37), (1, "int32", 7), (2, "bfloat16", 29)])
def test_one_engine_item_a_dtype_in_order_of_first_appearance(
        hvd, per_process, k, dtype, total):
    leaves = jax.tree_util.tree_leaves(mixed_tree())
    before = counts()
    gid, items = eager._stage_packed(
        leaves, "named", "test_flat", CollectiveType.ALLREDUCE, per_process,
        8, reduce_op=hvd.Sum, compression=None)
    assert moved(before)["packed"] == moved(before)["compiled"] == 8
    assert [it["name"] for it in items] == [
        "named.flat.0", "named.flat.1", "named.flat.2"]
    item = items[k]
    assert item["tensor"].shape == (1, total)
    assert item["tensor"].dtype == jnp.dtype(dtype)
    want = np.concatenate([np.asarray(x).reshape(-1) for x in leaves
                           if x.dtype == jnp.dtype(dtype)])
    assert np.array_equal(np.asarray(item["tensor"])[0], want)
    assert item["donate"] is True and item["priority"] == 8
    assert item["group_id"] == gid
    assert item["process_set_id"] == per_process.process_set_id
    assert item["reduce_op"] == hvd.Sum and item["compression"] is None
    assert item["tensor"].sharding == eager._as_stacked(
        np.zeros(total, dtype), per_process.process_set_id)[0].sharding


def test_the_group_carries_its_highest_priority_and_its_wire_mode(
        hvd, per_process, monkeypatch):
    seen = []
    real = eager._stage_packed

    def spy(*a, **k):
        gid, items = real(*a, **k)
        seen.extend(items)
        return gid, items

    monkeypatch.setattr(eager, "_stage_packed", spy)
    opt_mod.allreduce_gradients(mixed_tree(), compression=Compression.bf16,
                                process_set=per_process)
    assert [it["name"] for it in seen] == [
        f"allreduce_gradients.flat.{k}" for k in range(3)]
    assert all(it["priority"] == 8 and it["compression"] == "bf16"
               and it["ctype"] == CollectiveType.ALLREDUCE for it in seen)
    assert len({it["group_id"] for it in seen}) == 1


def test_flat_group_is_a_pytree_with_its_layout_static(hvd):
    tree = mixed_tree()
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    layout = eager._flat_layout(leaves)
    flat = eager.FlatGroup(
        [b[0] for b in eager._pack_leaves(leaves)], layout, treedef)
    buffers, aux = jax.tree_util.tree_flatten(flat)
    assert [b.shape for b in buffers] == [(37,), (7,), (29,)]
    again = jax.tree_util.tree_unflatten(aux, buffers)
    assert again.layout == layout and again.treedef == treedef
    same_trees(eager._unpack_group(flat), tree)
    same_trees(jax.jit(lambda f: f.tree())(flat), tree)


# ------------------- (b) bitwise the path that takes a leaf an item
@pytest.mark.parametrize("op", ["Sum", "Average", "Min", "Max", "Product"])
def test_reduced_gradients_are_bitwise_the_per_leaf_ones(
        hvd, per_process, op, monkeypatch):
    g = mixed_tree(1)
    before = counts()
    out = opt_mod.allreduce_gradients(g, op=getattr(hvd, op),
                                      process_set=per_process)
    assert moved(before)["packed"] == 8
    with monkeypatch.context() as m:
        a_leaf_an_item(m)
        before = counts()
        want = opt_mod.allreduce_gradients(g, op=getattr(hvd, op),
                                           process_set=per_process)
        assert moved(before)["packed"] == 0
    same_trees(out, want)
    same_trees(out, g)              # one rank: every reduce is the identity


@pytest.mark.parametrize("how", ["bf16", "fp16_strict"])
def test_wire_compression_rides_the_flat_buffer_as_it_rides_a_leaf(
        hvd, per_process, how, monkeypatch):
    g = mixed_tree(2)
    comp = getattr(Compression, how)
    before = counts()
    out = opt_mod.allreduce_gradients(g, compression=comp,
                                      process_set=per_process)
    assert moved(before)["packed"] == 8
    with monkeypatch.context() as m:
        a_leaf_an_item(m)
        want = opt_mod.allreduce_gradients(g, compression=comp,
                                           process_set=per_process)
    same_trees(out, want)
    wire = jnp.bfloat16 if how == "bf16" else jnp.float16
    assert np.array_equal(
        np.asarray(out["a_w"]),
        np.asarray(g["a_w"].astype(wire).astype(jnp.float32)))
    assert not np.array_equal(np.asarray(out["a_w"]), np.asarray(g["a_w"]))
    assert np.array_equal(np.asarray(out["b_steps"]),
                          np.asarray(g["b_steps"]))     # integers: no cast


OPTIMIZERS = {
    "sgd_momentum": lambda: optax.sgd(0.1, momentum=0.9),
    "adam": lambda: optax.adam(1e-2),
    "clipped_adamw": lambda: optax.chain(optax.clip_by_global_norm(1.0),
                                         optax.adamw(1e-2)),
}


def run_updates(hvd, ps, make, steps=3, **wrap):
    params = jax.tree_util.tree_map(jnp.ones_like, mixed_tree(ints=False))
    opt = hvd.DistributedOptimizer(make(), process_set=ps, **wrap)
    state = opt.init(params)
    outs = []
    for i in range(steps):
        updates, state = opt.update(mixed_tree(10 + i, ints=False), state,
                                    params)
        outs.append(updates)
    return outs, state


@pytest.mark.parametrize("wrap", [dict(), dict(backward_passes_per_step=2),
                                  dict(compression=Compression.bf16)],
                         ids=["k1", "k2", "wire_bf16"])
@pytest.mark.parametrize("name", list(OPTIMIZERS))
def test_updates_and_new_state_are_bitwise_the_per_leaf_ones(
        hvd, per_process, name, wrap, monkeypatch):
    steps = 4
    reduces = steps // wrap.get("backward_passes_per_step", 1)
    before = counts()
    outs, state = run_updates(hvd, per_process, OPTIMIZERS[name], steps,
                              **wrap)
    assert moved(before)["packed"] == 6 * reduces
    with monkeypatch.context() as m:
        a_leaf_an_item(m)
        before = counts()
        want_outs, want_state = run_updates(hvd, per_process,
                                            OPTIMIZERS[name], steps, **wrap)
        assert moved(before)["packed"] == 0
        assert moved(before)["compiled"] == 6 * reduces
    same_trees(outs[0], want_outs[0])
    if name == "sgd_momentum":
        same_trees(outs, want_outs)
        same_trees(state, want_state)
    else:
        # The reduced gradients are bitwise the same (above); the inner
        # update that slices them out of a buffer is another XLA program
        # than the one handed a tree, and the CPU backend contracts Adam's
        # ``b * m + (1 - b) * g`` in the one and not in the other: the
        # last bit of a moment from the second step on, a few more in an
        # update that divides by a small root.
        same_trees(outs, want_outs, nearly=True)
        same_trees(state, want_state, nearly=True)
    assert any(float(jnp.abs(u["a_w"]).sum()) > 0 for u in outs)


def test_the_gradient_tape_hands_back_the_tree(hvd, per_process):
    g = mixed_tree(3)
    tape = hvd.DistributedGradientTape(lambda x: (jnp.float32(1.5), x),
                                       process_set=per_process)
    before = counts()
    value, out = tape(g)
    assert float(value) == 1.5 and moved(before)["packed"] == 8
    same_trees(out, g)


# ------------- (c) what the flat path cannot take goes a leaf an item
def test_every_leaf_on_the_chip_is_what_may_travel_flat(hvd, per_process):
    leaves = jax.tree_util.tree_leaves(mixed_tree())
    assert eager._all_held(leaves, per_process)
    assert not eager._all_held([], per_process)
    assert not eager._all_held(leaves + [np.ones(2, np.float32)],
                               per_process)
    elsewhere = jax.device_put(np.ones(2, np.float32), jax.devices()[3])
    assert not eager._all_held(leaves + [elsewhere], per_process)
    assert not eager._all_held(leaves, None)    # 8 devices, this process's


class Adasum:
    kwargs = dict(op="Adasum")


class NonCastCompressor:
    kwargs = dict(compression=HalfOnThisThread)


class NoneCompressorInstance:
    """Only the class itself is known to leave a leaf as it is."""
    kwargs = dict(compression=Compression.none())


@pytest.mark.parametrize("case", [Adasum, NonCastCompressor,
                                  NoneCompressorInstance],
                         ids=lambda c: c.__name__)
def test_fallback_by_what_the_call_asks_for(hvd, per_process, case,
                                            monkeypatch):
    spans = traced(monkeypatch)
    g = mixed_tree(4, ints=False)
    kwargs = dict(case.kwargs)
    if "op" in kwargs:
        kwargs["op"] = getattr(hvd, kwargs["op"])
    before = counts()
    out = opt_mod.allreduce_gradients(g, process_set=per_process, **kwargs)
    assert moved(before) == {"compiled": 6, "traces": moved(before)["traces"],
                             "packed": 0}
    nbytes = sum(int(v.nbytes) for v in g.values())
    if case is NonCastCompressor:
        nbytes = 2 * sum(int(v.size) for v in g.values())   # as float16
    assert spans("hvd/update/stage") == [
        {"n": 6, "bytes": nbytes, "compiled": 6, "packed": 0, "buffers": 6}]
    if case is NonCastCompressor:
        assert np.array_equal(
            np.asarray(out["a_w"]),
            np.asarray(g["a_w"].astype(jnp.float16).astype(jnp.float32) * 2))
    else:
        same_trees(out, g)


def test_fallback_for_a_numpy_leaf(hvd, per_process, monkeypatch):
    spans = traced(monkeypatch)
    g = dict(mixed_tree(5), z_host=np.arange(4, dtype=np.float32))
    before = counts()
    out = opt_mod.allreduce_gradients(g, process_set=per_process)
    assert moved(before)["packed"] == 0
    assert spans("hvd/update/stage")[0]["packed"] == 0
    assert spans("hvd/update/stage")[0]["buffers"] == 9
    assert isinstance(out["z_host"], jax.Array)
    for k in g:
        assert np.array_equal(np.asarray(out[k]), np.asarray(g[k]))


def test_fallback_for_a_process_that_drives_several_devices(
        hvd, world_size, monkeypatch):
    """Staged and submitted as ``allreduce_gradients`` does it (whose
    unpack is written for one device a process)."""
    from horovod_tpu.common import basics
    monkeypatch.setattr(basics._get_state().config, "controller_addr",
                        "stub:0")
    assert eager.per_process_mode()
    spans = traced(monkeypatch)
    rng = np.random.RandomState(6)
    leaves = [jnp.asarray(rng.randn(world_size, 3).astype(np.float32)),
              jnp.asarray(rng.randn(world_size).astype(np.float32))]
    flat = eager._all_held(leaves, None)
    assert not flat
    before = counts()
    gid, _, handles = opt_mod._stage_submit(
        lambda: leaves, "several", "grouped_allreduce",
        CollectiveType.ALLREDUCE, None, [2, 1], pack=flat, reduce_op=hvd.Sum)
    assert moved(before) == {"compiled": 0, "traces": 0, "packed": 0}
    assert spans("hvd/update/stage") == [
        {"n": 2, "bytes": 4 * 4 * world_size, "compiled": 0, "packed": 0,
         "buffers": 2}]
    for r, x in zip(opt_mod._wait(gid, handles), leaves):
        assert np.allclose(eager.to_local(r), np.asarray(x).sum(0))


def test_single_controller_stages_nothing(hvd, monkeypatch):
    assert not eager.per_process_mode()
    spans = traced(monkeypatch)
    g = mixed_tree(7)
    before = counts()
    out = opt_mod.allreduce_gradients(g)
    assert out is g and moved(before) == {"compiled": 0, "traces": 0,
                                          "packed": 0}
    assert spans("hvd/update/stage") == []


# ---------------------------------- (d) one trace a tree signature
def params_like(shape):
    return {"w": jnp.ones(shape), "b": jnp.zeros(shape[-1:]),
            "h": jnp.ones(shape[-1:], jnp.bfloat16)}


def grads_like(shape, seed):
    rng = np.random.RandomState(seed)
    return {"w": jnp.asarray(rng.randn(*shape).astype(np.float32)),
            "b": jnp.asarray(rng.randn(*shape[-1:]).astype(np.float32)),
            "h": jnp.asarray(rng.randn(*shape[-1:]), dtype=jnp.bfloat16)}


def test_pack_and_inner_program_trace_once_a_signature(hvd, per_process):
    # shapes of this test's own: jit's cache is the process's
    def run(shape, steps):
        params = params_like(shape)
        state = opt.init(params)
        for i in range(steps):
            _, state = opt.update(grads_like(shape, i), state, params)

    opt = hvd.DistributedOptimizer(optax.sgd(0.1, momentum=0.9),
                                   process_set=per_process)
    stage0, inner0 = counts(), dict(trace.inner_update)

    def traces():
        return (counts()["traces"] - stage0["traces"],
                trace.inner_update["traces"] - inner0["traces"])

    run((21, 3), 5)
    assert traces() == (1, 1)
    run((21, 4), 2)                 # another signature: once more each
    assert traces() == (2, 2)
    run((21, 3), 3)                 # the first is still cached
    assert traces() == (2, 2)
    assert moved(stage0)["packed"] == 10 * 3
    assert trace.inner_update["compiled"] - inner0["compiled"] == 10


def test_public_unflatten_traces_once_a_signature(hvd, per_process,
                                                  monkeypatch):
    calls, real = [], eager._unpack_group
    monkeypatch.setattr(eager, "_unpack_group",
                        lambda f: calls.append(1) or real(f))
    size0 = real._cache_size()
    for i in range(4):
        opt_mod.allreduce_gradients(grads_like((22, 3), i),
                                    process_set=per_process)
    assert len(calls) == 4 and real._cache_size() == size0 + 1


def test_the_update_never_unflattens_outside_its_program(hvd, per_process,
                                                         monkeypatch):
    """``DistributedOptimizer.update``: the pack, the fused reduce and the
    inner update are the step's programs; no unflatten program, no
    operation a leaf."""
    params = params_like((23, 3))
    opt = hvd.DistributedOptimizer(optax.sgd(0.1, momentum=0.9),
                                   process_set=per_process)
    state = opt.init(params)
    _, state = opt.update(grads_like((23, 3), 0), state, params)
    called = []
    for mod, name in [(eager, "_unpack_group"), (eager, "_stack_leaves"),
                      (eager, "_as_stacked"), (opt_mod, "_as_leaf")]:
        monkeypatch.setattr(
            mod, name, lambda *a, _n=name, **k: called.append(_n))
    for i in range(2):
        updates, state = opt.update(grads_like((23, 3), i), state, params)
    assert called == []
    assert float(jnp.abs(updates["w"]).sum()) > 0


# --------------------- (e) the caller's gradients are never donated
@pytest.mark.parametrize("through", ["allreduce_gradients", "update"])
def test_callers_gradients_live_on_after_the_donated_buffer_was_reduced(
        hvd, per_process, through):
    g = mixed_tree(8, ints=False)
    kept = {k: np.asarray(v).copy() for k, v in g.items()}
    params = jax.tree_util.tree_map(jnp.ones_like, g)
    opt = hvd.DistributedOptimizer(optax.sgd(0.1), process_set=per_process)
    state = opt.init(params)
    for _ in range(2):          # twice: a donated input fails the second
        if through == "update":
            _, state = opt.update(g, state, params)
        else:
            opt_mod.allreduce_gradients(g, process_set=per_process)
    for k, v in g.items():
        assert not v.is_deleted()
        assert np.array_equal(np.asarray(v), kept[k])
    _, items = eager._stage_packed(
        list(g.values()), None, "test_flat", CollectiveType.ALLREDUCE,
        per_process, 1)
    assert all(it["donate"] is True for it in items)    # the copy is ours
    assert items[0]["tensor"].addressable_shards[0].data \
        .unsafe_buffer_pointer() != g["a_w"].unsafe_buffer_pointer()


# --------------- (f) an inner update that cannot be compiled
def test_inner_fallback_gets_the_tree_from_the_unflatten_program(
        hvd, per_process, monkeypatch):
    runs = []

    def update(g, s, p=None):
        runs.append(g)
        if float(g["w"].sum()) > 0:     # decided on a value: not traceable
            g = jax.tree_util.tree_map(lambda x: -x, g)
        return g, s

    tx = optax.GradientTransformation(lambda p: optax.EmptyState(), update)
    opt = hvd.DistributedOptimizer(tx, process_set=per_process)
    params = params_like((24, 3))
    state = opt.init(params)
    spans = traced(monkeypatch)
    calls, real = [], eager._unpack_group
    monkeypatch.setattr(eager, "_unpack_group",
                        lambda f: calls.append(1) or real(f))
    inner0 = dict(trace.inner_update)
    for i in range(3):
        g = grads_like((24, 3), i)
        updates, state = opt.update(g, state, params)
        sign = -1.0 if float(g["w"].sum()) > 0 else 1.0
        same_trees(updates, jax.tree_util.tree_map(lambda x: sign * x, g))
    # the failed trace saw tracers; each direct call a tree of arrays
    assert len(runs) == 1 + 3 and len(calls) == 3
    assert all(isinstance(r, dict) and set(r) == {"w", "b", "h"}
               for r in runs)
    assert trace.inner_update["traces"] - inner0["traces"] == 1
    assert trace.inner_update["compiled"] == inner0["compiled"]
    assert spans("hvd/update/inner") == [{"compiled": 0}] * 3
    assert [s["packed"] for s in spans("hvd/update/stage")] == [3] * 3


def test_inner_update_takes_a_flat_group_directly(hvd):
    tree = mixed_tree(9, ints=False)
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    flat = eager.FlatGroup([b[0] for b in eager._pack_leaves(leaves)],
                           eager._flat_layout(leaves), treedef)
    tx = optax.sgd(0.1, momentum=0.9)
    inner = opt_mod._InnerUpdate(tx)
    params = jax.tree_util.tree_map(jnp.ones_like, tree)
    state = tx.init(params)
    same_trees(inner(flat, state, params), inner(tree, state, params))


# -------------------------------------------- (g) the counts' readers
def test_stage_and_unpack_spans_of_a_flat_group(hvd, per_process,
                                                monkeypatch):
    spans = traced(monkeypatch)
    g = mixed_tree(11)
    nbytes = sum(int(v.nbytes) for v in g.values())
    opt_mod.allreduce_gradients(g, process_set=per_process)
    assert spans("hvd/update/stage") == [
        {"n": 8, "bytes": nbytes, "compiled": 8, "packed": 8, "buffers": 3}]
    assert spans("hvd/update/unpack") == [
        {"n": 8, "bytes": nbytes, "host": 0}]


def test_unpack_counts_in_leaves_where_a_buffer_took_the_host_path(
        hvd, per_process, monkeypatch):
    spans = traced(monkeypatch)
    g = mixed_tree(12)
    real = eager._local_shard
    monkeypatch.setattr(
        eager, "_local_shard",
        lambda r: None if r.dtype == jnp.bfloat16 else real(r))
    out = opt_mod.allreduce_gradients(g, process_set=per_process)
    assert spans("hvd/update/unpack")[0]["host"] == 2   # the bfloat16 leaves
    for k in g:
        assert np.array_equal(np.asarray(out[k]), np.asarray(g[k]))


def test_monitor_agent_exports_the_packed_count(hvd, per_process):
    from horovod_tpu.monitor.agent import MonitorAgent

    class Engine:
        monitor = None

    agent = MonitorAgent(engine=Engine())
    try:
        first = agent.registry.snapshot()
        for i in range(3):
            opt_mod.allreduce_gradients(grads_like((25, 3), i),
                                        process_set=per_process)
        hvd.grouped_allreduce(list(grads_like((25, 3), 0).values()),
                              process_set=per_process)  # compiled, not packed
        second = agent.registry.snapshot()
    finally:
        agent.close()

    def rose(name):
        def value(snap):
            return snap[name]["value"] if isinstance(snap[name], dict) \
                else snap[name]
        return value(second) - value(first)

    assert rose("hvd_stage_group_packed_total") == 9
    assert rose("hvd_stage_group_compiled_total") == 12
    assert rose("hvd_stage_group_traces_total") == 2
    assert "hvd_stage_group_packed_total" in \
        agent.registry.to_prometheus('rank="0"')
