"""A group's staging (``ops/eager.py`` ``_stage_group``): members that are
device arrays on this process's one chip go into the engine's stacked
layout through one compiled program over the group, and come out as
``_as_stacked`` gives them a leaf at a time; anything else still takes
``_as_stacked``; the counts that say which (``trace.stage_group``, the
span's ``compiled``, the two ``/metrics`` series); the caller's arrays are
never donated.

As in ``tests/test_local_array.py`` the groups run over a one-rank process
set of the 8-virtual-device CPU mesh with this process forced into the
per-process branch and no controller (``torovodrun -np 1``: ``conftest.py``'s
``per_process``).  The span's id
in a traced update is ``tests/test_trace_spans.py``'s; the sharded paths
across processes are ``tests/data/worker_sharded.py`` / ``worker_fsdp.py``'s.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu import trace
from horovod_tpu.jax import optimizer as opt_mod
from horovod_tpu.ops import eager
from horovod_tpu.ops.engine import CollectiveType
from horovod_tpu.trace import core
from test_trace_spans import fresh_annotation


def counts():
    return dict(trace.stage_group)


def moved(before):
    return {k: v - before[k] for k, v in counts().items()}


def a_leaf_at_a_time(tensors, ps_id):
    """What ``_stack_members`` was before the one program."""
    return [eager._as_stacked(t, ps_id) for t in tensors], 0


def stage(tensors, ps):
    return eager._stage_group(tensors, None, "test_stage",
                              CollectiveType.ALLREDUCE, ps)


def same_array(a, b):
    assert type(a) is type(b)
    assert a.shape == b.shape and a.dtype == b.dtype
    assert a.sharding == b.sharding
    assert a.weak_type == b.weak_type
    assert np.array_equal(np.asarray(a), np.asarray(b))


# ------------------------ (a) the same arrays as ``_as_stacked`` a member
def mixed_group(seed=0):
    rng = np.random.RandomState(seed)
    return {
        "float32": jnp.asarray(rng.randn(7, 5).astype(np.float32)),
        "bfloat16": jnp.asarray(rng.randn(4, 3, 2), dtype=jnp.bfloat16),
        "int32": jnp.asarray(rng.randint(-9, 9, (6,)).astype(np.int32)),
        "zero_d": jnp.asarray(np.float32(rng.randn())),
        "one_element": jnp.asarray(rng.randn(1).astype(np.float32)),
        "weak_scalar": jnp.asarray(2.5),
        "empty": jnp.zeros((0, 3), jnp.float32),
    }


@pytest.mark.parametrize("member", list(mixed_group()))
def test_group_comes_out_as_as_stacked_gives_each_member(
        hvd, per_process, member):
    group = mixed_group()
    names, tensors = list(group), list(group.values())
    before = counts()
    gid, items, compiled = stage(tensors, per_process)
    assert compiled == len(tensors) == moved(before)["compiled"]
    assert [it["name"].rsplit(".", 1)[1] for it in items] == \
        [str(i) for i in range(len(tensors))]
    k = names.index(member)
    item, x = items[k], tensors[k]
    want, owned = eager._as_stacked(x, per_process.process_set_id)
    same_array(item["tensor"], want)
    assert item["donate"] is owned is True
    assert item["tensor"].shape == (1,) + x.shape
    assert item["tensor"].sharding == NamedSharding(
        per_process.mesh, P(per_process.axis_name))
    assert item["group_id"] == gid and item["priority"] == 0
    assert item["process_set_id"] == per_process.process_set_id
    assert np.array_equal(np.asarray(item["tensor"])[0], np.asarray(x))


def test_items_keep_their_priorities_and_extras(hvd, per_process):
    tensors = list(mixed_group(1).values())[:3]
    gid, items, _ = eager._stage_group(
        tensors, "named", "test_stage", CollectiveType.ALLGATHER,
        per_process, [5, 4, 3], sharded="full", prefetch=True)
    assert [it["priority"] for it in items] == [5, 4, 3]
    assert [it["name"] for it in items] == ["named.0", "named.1", "named.2"]
    assert all(it["sharded"] == "full" and it["prefetch"] is True
               and it["ctype"] == CollectiveType.ALLGATHER for it in items)
    with pytest.raises(ValueError, match="one entry per tensor"):
        eager._stage_group(tensors, None, "test_stage",
                           CollectiveType.ALLREDUCE, per_process, [1])


def test_one_dispatch_a_group_and_none_a_member(hvd, per_process,
                                                monkeypatch):
    """The three calls a member that the phase was made of are gone, and
    the program is the module's one callable: no ``jax.jit`` a call."""
    tensors = list(mixed_group(2).values())
    stage(tensors, per_process)             # this signature's one trace
    called = []

    def spy(mod, name):
        real = getattr(mod, name)
        monkeypatch.setattr(
            mod, name, lambda *a, **k: called.append(name) or real(*a, **k))

    program = eager._stack_leaves
    spy(eager.jax, "device_put")
    spy(eager.jax, "jit")
    spy(eager.jnp, "expand_dims")
    spy(eager, "_as_stacked")
    spy(eager, "_stack_leaves")
    before = counts()
    for _ in range(3):
        stage(tensors, per_process)
    assert called == ["_stack_leaves"] * 3
    assert moved(before) == {"compiled": 3 * len(tensors), "traces": 0,
                             "packed": 0}
    monkeypatch.undo()
    assert eager._stack_leaves is program


# --------------------------------- (b) one trace a signature, counted
def params_of(shape):
    return {"w": jnp.ones(shape), "b": jnp.zeros(shape[-1:]),
            "s": jnp.asarray(np.float32(1.0))}


def grads_of(shape, seed):
    rng = np.random.RandomState(seed)
    return {"w": jnp.asarray(rng.randn(*shape).astype(np.float32)),
            "b": jnp.asarray(rng.randn(*shape[-1:]).astype(np.float32)),
            "s": jnp.asarray(np.float32(rng.randn()))}


def run_flat(hvd, ps, shape, n):
    for i in range(n):
        out = opt_mod.allreduce_gradients(grads_of(shape, i), process_set=ps)
    return out


def run_update(hvd, ps, shape, n, **wrap):
    params = params_of(shape)
    opt = hvd.DistributedOptimizer(optax.sgd(0.1, momentum=0.9),
                                   process_set=ps, **wrap)
    state = opt.init(params)
    for i in range(n):
        out, state = opt.update(grads_of(shape, i), state, params)
    # the full-sharded update returns no updates: its parameters instead
    return state.gather_params() if out is None else out


def run_grouped(hvd, ps, shape, n):
    for i in range(n):
        out = hvd.grouped_allreduce(
            list(grads_of(shape, i).values()), process_set=ps)
    return out


# each case its own shapes: jit's cache is the process's, and a signature
# another test staged first would read no trace here
SIGNATURES = {"allreduce_gradients": (run_flat, (11, 3), (11, 4)),
              "optimizer_update": (run_update, (12, 3), (12, 4)),
              "grouped_allreduce": (run_grouped, (13, 3), (13, 4))}


@pytest.mark.parametrize("through", list(SIGNATURES))
def test_traced_once_a_signature_and_counted_a_member(hvd, per_process,
                                                      through):
    run, first, second = SIGNATURES[through]
    # a gradient tree travels flat (tests/test_flat_group.py): its leaves
    # count as packed too; a grouped call's members never do
    packed = 0 if through == "grouped_allreduce" else 3
    before = counts()
    run(hvd, per_process, first, 5)
    assert moved(before) == {"compiled": 5 * 3, "traces": 1,
                             "packed": 5 * packed}
    run(hvd, per_process, second, 2)        # another signature: once more
    assert moved(before) == {"compiled": 7 * 3, "traces": 2,
                             "packed": 7 * packed}
    run(hvd, per_process, first, 2)         # the first is still cached
    assert moved(before) == {"compiled": 9 * 3, "traces": 2,
                             "packed": 9 * packed}


# ----------------- (c) what the program cannot take goes a leaf at a time
def group_of(kind):
    """``(tensors, members the one program takes)``."""
    rng = np.random.RandomState(4)
    host = [rng.randn(3, 2).astype(np.float32),
            rng.randn(5).astype(np.float32)]
    if kind == "numpy":
        return host, 0
    if kind == "lists_and_scalars":
        return [[1.0, 2.0, 3.0], 4, 2.5, (1, 2)], 0
    if kind == "mixed":
        return [jnp.asarray(host[0]), host[1], jnp.asarray(host[1]),
                [7.0, 8.0]], 2
    assert kind == "another_device"         # not the set's chip: moved
    other = jax.device_put(host[0], jax.devices()[3])
    return [other, jnp.asarray(host[1])], 1


@pytest.mark.parametrize("kind", ["numpy", "lists_and_scalars", "mixed",
                                  "another_device"])
def test_other_members_take_as_stacked_in_the_same_group(
        hvd, per_process, kind):
    tensors, together = group_of(kind)
    before = counts()
    gid, items, compiled = stage(tensors, per_process)
    assert compiled == together == moved(before)["compiled"]
    want, _ = a_leaf_at_a_time(tensors, per_process.process_set_id)
    assert len(items) == len(tensors)
    for item, (arr, owned) in zip(items, want):
        same_array(item["tensor"], arr)
        assert item["donate"] is owned
    reduced = hvd.grouped_allreduce(tensors, op=hvd.Sum,
                                    process_set=per_process)
    for r, t in zip(reduced, tensors):      # one rank: its sum is itself
        assert np.array_equal(eager.to_local(r), np.asarray(t))


@pytest.mark.parametrize("branch", ["single_controller",
                                    "several_local_devices"])
def test_branches_the_program_never_serves(hvd, world_size, branch,
                                           monkeypatch):
    """Already stacked arrays under a single controller, and a process
    that drives several devices: ``_as_stacked`` for every member."""
    rng = np.random.RandomState(5)
    vals = [rng.randn(world_size, 3).astype(np.float32),
            rng.randn(world_size).astype(np.float32)]
    if branch == "several_local_devices":
        from horovod_tpu.common import basics
        monkeypatch.setattr(basics._get_state().config, "controller_addr",
                            "stub:0")
        assert eager.per_process_mode()
        tensors = [jnp.asarray(v) for v in vals]
    else:
        assert not eager.per_process_mode()
        tensors = [hvd.stack_per_rank(list(v)) for v in vals]
    before = counts()
    gid, items, compiled = stage(tensors, None)
    assert compiled == 0 and moved(before) == {"compiled": 0, "traces": 0,
                                               "packed": 0}
    for item, v in zip(items, vals):
        assert item["tensor"].shape == v.shape
        assert np.array_equal(np.asarray(item["tensor"]), v)
        # a single controller's own stacked array is never the engine's
        assert item["donate"] is (branch == "several_local_devices")


def test_a_global_array_is_refused_as_before(hvd, per_process):
    """A member the process does not hold whole still gets
    ``_as_stacked``'s error or its copy, never the program."""
    whole = hvd.stack_per_rank([np.ones(2, np.float32)] * 8)
    spread = jax.device_put(np.ones((8, 2), np.float32),
                            NamedSharding(hvd.mesh(), P("hvd")))
    assert len(spread.devices()) == 8
    before = counts()
    _, items, compiled = stage([spread, whole], per_process)
    assert compiled == 0 and moved(before)["compiled"] == 0
    assert items[0]["tensor"].shape == (1, 8, 2)


# ------------------------------- (d) the caller's arrays are never donated
@pytest.mark.parametrize("call", ["grouped_allreduce", "grouped_allgather",
                                  "grouped_reducescatter",
                                  "allreduce_gradients"])
def test_callers_leaves_live_on_after_the_fused_program(hvd, per_process,
                                                        call):
    g = grads_of((6, 4), 8)
    kept = {k: np.asarray(v).copy() for k, v in g.items()}
    leaves = [g["w"], g["b"]]
    for _ in range(2):          # twice: a donated input fails the second
        if call == "allreduce_gradients":
            opt_mod.allreduce_gradients(g, process_set=per_process)
        else:
            getattr(hvd, call)(leaves, process_set=per_process)
    _, items, compiled = stage(leaves, per_process)
    assert compiled == 2 and all(it["donate"] is True for it in items)
    for k, v in g.items():
        assert not v.is_deleted()
        assert np.array_equal(np.asarray(v), kept[k])
    # and a staged array is a copy, not a view of the caller's buffer
    assert items[0]["tensor"].addressable_shards[0].data \
        .unsafe_buffer_pointer() != g["w"].unsafe_buffer_pointer()


# ------------- (e) every caller's results: bitwise the leaf-at-a-time ones
def run_gather(hvd, ps, shape, n):
    return hvd.grouped_allgather(list(grads_of(shape, 3).values())[:2],
                                 process_set=ps)


def run_scatter(hvd, ps, shape, n):
    return hvd.grouped_reducescatter(list(grads_of(shape, 3).values())[:2],
                                     op=hvd.Sum, process_set=ps)


CALLERS = {
    "allreduce_gradients": run_flat,
    "grouped_allreduce": run_grouped,
    "grouped_allgather": run_gather,
    "grouped_reducescatter": run_scatter,
    "optimizer_update": run_update,
    "optimizer_update_sharded":
        lambda *a: run_update(*a, sharded=True),
    "optimizer_update_full":
        lambda *a: run_update(*a, sharded="full"),
}


@pytest.mark.parametrize("caller", list(CALLERS))
def test_results_equal_the_leaf_at_a_time_staging(hvd, per_process, caller,
                                                  monkeypatch):
    before = counts()
    out = CALLERS[caller](hvd, per_process, (9, 4), 3)
    assert moved(before)["compiled"] > 0
    with monkeypatch.context() as m:
        m.setattr(eager, "_stack_members", a_leaf_at_a_time)
        m.setattr(eager, "_all_held", lambda *a: False)     # nor packed
        before = counts()
        want = CALLERS[caller](hvd, per_process, (9, 4), 3)
        assert moved(before) == {"compiled": 0, "traces": 0, "packed": 0}
    la, lb = jax.tree_util.tree_leaves(out), jax.tree_util.tree_leaves(want)
    assert len(la) == len(lb) > 0
    for a, b in zip(la, lb):
        a, b = eager.to_local(a), eager.to_local(b)
        assert a.shape == b.shape and a.dtype == b.dtype
        assert np.array_equal(a, b)


# -------------------------------------------------- the counts' readers
def test_stage_span_counts_the_members_the_program_took(hvd, per_process,
                                                        monkeypatch):
    """``compiled`` on ``hvd/update/stage``: ``n`` with every leaf on the
    chip, fewer where leaves came from the host."""
    ann = fresh_annotation()
    rec = core.TraceRecorder(annotation=ann)
    monkeypatch.setattr(core, "_installed", rec)
    g = grads_of((5, 2), 6)
    opt_mod.allreduce_gradients(g, process_set=per_process)
    monkeypatch.setattr(eager, "_all_held", lambda *a: False)
    opt_mod.allreduce_gradients(g, process_set=per_process)
    monkeypatch.setattr(eager, "_stack_members", a_leaf_at_a_time)
    opt_mod.allreduce_gradients(g, process_set=per_process)
    nbytes = sum(int(v.nbytes) for v in g.values())
    seen = [e["ids"] for e in ann.events if e["name"] == "hvd/update/stage"]
    assert seen == [{"n": 3, "bytes": nbytes, "compiled": 3, "packed": 3,
                     "buffers": 1},
                    {"n": 3, "bytes": nbytes, "compiled": 3, "packed": 0,
                     "buffers": 3},
                    {"n": 3, "bytes": nbytes, "compiled": 0, "packed": 0,
                     "buffers": 3}]


def test_monitor_agent_exports_the_two_counts(hvd, per_process):
    from horovod_tpu.monitor.agent import MonitorAgent

    class Engine:
        monitor = None

    agent = MonitorAgent(engine=Engine())
    try:
        first = agent.registry.snapshot()
        run_flat(hvd, per_process, (14, 3), 3)
        second = agent.registry.snapshot()
    finally:
        agent.close()

    def value(snap, name):
        return snap[name]["value"] if isinstance(snap[name], dict) \
            else snap[name]

    assert value(second, "hvd_stage_group_compiled_total") \
        - value(first, "hvd_stage_group_compiled_total") == 9
    assert value(second, "hvd_stage_group_traces_total") \
        - value(first, "hvd_stage_group_traces_total") == 1
    text = agent.registry.to_prometheus('rank="0"')
    assert "hvd_stage_group_compiled_total" in text
    assert "hvd_stage_group_traces_total" in text
