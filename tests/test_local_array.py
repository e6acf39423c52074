"""Engine results stay on the device (``ops/eager.py`` ``local_array``):
what the helper hands back for each layout a result can have, that it is
bitwise ``to_local``'s value, and that the eager update's unpack takes it —
no result of an eager ``allreduce_gradients`` goes through the host.

The updates run over a one-rank process set of the 8-virtual-device CPU
mesh with this process forced into the per-process branch and no
controller (``torovodrun -np 1``: the cycle runs inline; ``conftest.py``'s
``one_rank`` and ``per_process``).  The span's
``host`` id in a traced update is ``tests/test_trace_spans.py``'s; the
sharded paths' sites are driven by ``tests/data/worker_sharded.py`` and
``worker_fsdp.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from horovod_tpu.jax import optimizer as opt_mod
from horovod_tpu.jax.compression import Compression, Compressor
from horovod_tpu.ops import eager
from horovod_tpu.trace import core
from test_trace_spans import fresh_annotation


def values(world, per=6, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randn(per).astype(np.float32) for _ in range(world)]


def result_of(hvd, layout, ps):
    """An engine result (or a host array) of the named layout, with
    whether ``local_array`` can hand it over on the device."""
    if layout == "replicated_one_device":
        return hvd.allreduce(hvd.stack_per_rank(values(1), ps),
                             process_set=ps), True
    if layout == "stacked_one_shard":       # the [1, per] slice
        return hvd.reducescatter(hvd.stack_per_rank(values(1), ps),
                                 process_set=ps), True
    if layout == "replicated_all_local":    # 8 shards, one index
        return hvd.allreduce(hvd.stack_per_rank(values(8, per=8))), True
    if layout == "stacked_several_indices":
        return hvd.reducescatter(hvd.stack_per_rank(values(8, per=8))), False
    assert layout == "numpy"
    return values(1)[0], False


LAYOUTS = ["replicated_one_device", "stacked_one_shard",
           "replicated_all_local", "stacked_several_indices", "numpy"]


@pytest.mark.parametrize("layout", LAYOUTS)
def test_local_array_is_bitwise_to_local(hvd, one_rank, layout):
    result, on_device = result_of(hvd, layout, one_rank)
    want = eager.to_local(result)
    assert isinstance(want, np.ndarray)     # hvd.to_local's contract
    got = eager.local_array(result)
    assert isinstance(got, jax.Array)
    assert got.shape == want.shape and got.dtype == want.dtype
    assert np.array_equal(np.asarray(got), want)
    assert (eager._local_shard(result) is not None) == on_device


@pytest.mark.parametrize("layout", LAYOUTS[:3])
def test_device_path_hands_back_the_buffer_the_process_holds(
        hvd, one_rank, layout, monkeypatch):
    result, _ = result_of(hvd, layout, one_rank)
    shards = len({eager._index_key(s.index)
                  for s in result.addressable_shards})
    assert shards == 1
    monkeypatch.setattr(eager, "to_local", None)   # never asked
    got = eager.local_array(result)
    # a committed single-device array, not the set's NamedSharding: it
    # flows into programs compiled for single-device parameters
    assert isinstance(got.sharding, SingleDeviceSharding)
    assert got.committed
    held = result.addressable_shards[0].data
    assert got.devices() == held.devices()
    assert got.unsafe_buffer_pointer() == held.unsafe_buffer_pointer()


# ----------------------------------------------- the eager update's unpack
class HalfOnThisThread(Compressor):
    """A compressor with no ``wire_mode``: compress and decompress run on
    the calling thread, around the exchange."""

    @staticmethod
    def compress(tensor):
        return tensor.astype(jnp.float16), tensor.dtype

    @staticmethod
    def decompress(tensor, ctx):
        return tensor.astype(ctx) * 2.0


def grads(seed=3):
    rng = np.random.RandomState(seed)
    return {"w": jnp.asarray(rng.randn(7, 5).astype(np.float32)),
            "b": jnp.asarray(rng.randn(5).astype(np.float32)),
            "s": jnp.asarray(np.float32(rng.randn()))}


COMPRESSIONS = {"float32": Compression.none, "wire_bf16": Compression.bf16,
                "on_this_thread": HalfOnThisThread}


@pytest.mark.parametrize("how", list(COMPRESSIONS))
def test_unpack_is_bitwise_the_host_round_trip(hvd, per_process, how,
                                               monkeypatch):
    """``allreduce_gradients`` with every leaf handed over on the device
    against the same call with every leaf through the host, which is what
    the unpack did before results stayed on the device."""
    g = grads()

    def run():
        return opt_mod.allreduce_gradients(
            g, compression=COMPRESSIONS[how], process_set=per_process)

    out = run()
    with monkeypatch.context() as m:
        m.setattr(eager, "_local_shard", lambda result: None)
        through_host = run()
    assert jax.tree_util.tree_structure(out) == \
        jax.tree_util.tree_structure(g)
    for name in g:
        a, b = out[name], through_host[name]
        assert a.shape == b.shape == g[name].shape
        assert a.dtype == b.dtype == g[name].dtype
        assert np.array_equal(np.asarray(a), np.asarray(b))
    if how == "float32":        # one rank: its average is the gradient
        assert all(np.array_equal(np.asarray(out[k]), np.asarray(g[k]))
                   for k in g)
    elif how == "wire_bf16":    # rounded inside the fused program
        assert np.array_equal(
            np.asarray(out["w"]),
            np.asarray(g["w"].astype(jnp.bfloat16).astype(jnp.float32)))
    else:
        assert np.array_equal(
            np.asarray(out["w"]),
            np.asarray(g["w"].astype(jnp.float16).astype(jnp.float32) * 2))


def test_no_result_reaches_the_host_during_an_eager_update(
        hvd, per_process, monkeypatch):
    """The guard that keeps the copy from coming back: an eager
    ``DistributedOptimizer.update`` never asks ``to_local`` for a result,
    and its updates are the chip's own buffers."""
    import optax
    asked = []
    real = eager.to_local
    monkeypatch.setattr(eager, "to_local",
                        lambda r: asked.append(r) or real(r))
    params = grads(0)
    opt = hvd.DistributedOptimizer(optax.sgd(0.1, momentum=0.9),
                                   process_set=per_process)
    state = opt.init(params)
    eng = eager._engine()
    before = eng.pipeline_dispatches
    for i in range(3):
        updates, state = opt.update(grads(i + 1), state, params)
    assert eng.pipeline_dispatches == before + 3    # through the engine
    assert asked == []
    reduced = opt_mod.allreduce_gradients(grads(9), process_set=per_process)
    assert asked == []
    device = per_process.mesh.devices.flat[0]
    for leaf in jax.tree_util.tree_leaves(reduced):
        assert isinstance(leaf.sharding, SingleDeviceSharding)
        assert leaf.devices() == {device}


def test_unpack_counts_the_leaves_that_took_the_host_path(
        hvd, per_process, monkeypatch):
    """``host`` on ``hvd/update/unpack``: 0 with every leaf on the device,
    the number of leaves where none could be."""
    ann = fresh_annotation()
    rec = core.TraceRecorder(annotation=ann)
    monkeypatch.setattr(core, "_installed", rec)
    g = grads()
    opt_mod.allreduce_gradients(g, process_set=per_process)
    monkeypatch.setattr(eager, "_local_shard", lambda result: None)
    opt_mod.allreduce_gradients(g, process_set=per_process)
    nbytes = sum(int(v.nbytes) for v in g.values())
    seen = [e["ids"] for e in ann.events if e["name"] == "hvd/update/unpack"]
    assert seen == [{"n": 3, "bytes": nbytes, "host": 0},
                    {"n": 3, "bytes": nbytes, "host": 3}]


@pytest.mark.parametrize("case", [
    dict(shape=(4, 3), dtype="float32", size=None, same=True),
    dict(shape=(12,), dtype="float32", size=None, same=False),
    dict(shape=(4, 3), dtype="bfloat16", size=None, same=False),
    dict(shape=(3, 3), dtype="float32", size=9, same=False),
], ids=["as_is", "reshape", "astype", "trim"])
def test_as_leaf_touches_only_what_differs(case):
    a = jnp.arange(12, dtype=jnp.float32).reshape(4, 3)
    out = opt_mod._as_leaf(a, case["shape"], case["dtype"], case["size"])
    assert (out is a) == case["same"]
    want = np.arange(12, dtype=np.float32)
    if case["size"] is not None:
        want = want[:case["size"]]
    assert out.shape == case["shape"] and out.dtype == jnp.dtype(case["dtype"])
    assert np.array_equal(np.asarray(out.astype(jnp.float32)),
                          want.reshape(case["shape"]))
