"""The flat gradient path across REAL processes (ISSUE 34): with every
leaf a device array on this process's one device a gradient tree goes
through the engine as one flat buffer a dtype, and what comes back is
bitwise what the path that takes a leaf an engine item gives.

Each rank, on a rank-dependent gradient stream over a mixed tree (float32,
bfloat16, int32, a 0-d, a ``[1]`` and an empty leaf):

- ``allreduce_gradients`` flat against the same call with the flat path
  switched off (``eager._all_held`` patched on every rank alike), for
  AVERAGE, SUM and MAX and under ``Compression.bf16``: bitwise, and the
  exact cross-rank result where that is representable;
- ``DistributedOptimizer.update`` (SGD with momentum) five steps each way:
  updates and state bitwise;
- ``packed`` rises by the leaves of every group, with one trace of the
  pack program and one of the inner update for the five steps.

Launched by test_multiprocess.py::test_torovodrun_flat_gradients with
``torovodrun -np 2``.
"""

import os

os.environ["XLA_FLAGS"] = " ".join(
    f for f in os.environ.get("XLA_FLAGS", "").split()
    if "xla_force_host_platform_device_count" not in f)
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_cpu_collectives_implementation", "gloo")

import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvd
from horovod_tpu import trace
from horovod_tpu.jax import optimizer as opt_mod
from horovod_tpu.jax.compression import Compression
from horovod_tpu.ops import eager

STEPS = 5


def grads(step, rank, ints=True):
    rng = np.random.RandomState(100 * step + rank)
    tree = {
        "a_w": jnp.asarray(rng.randn(33, 7).astype(np.float32)),
        "b_steps": jnp.asarray(rng.randint(1, 9, (6,)).astype(np.int32)),
        "c_half": jnp.asarray(rng.randn(4, 3, 2), dtype=jnp.bfloat16),
        "d_scalar": jnp.asarray(np.float32(rng.randn())),
        "e_one": jnp.asarray(rng.randn(1).astype(np.float32)),
        "f_empty": jnp.zeros((0, 3), jnp.float32),
    }
    if not ints:
        del tree["b_steps"]
    return tree


def bitwise(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb) > 0
    for x, y in zip(la, lb):
        assert x.shape == y.shape and x.dtype == y.dtype, (x, y)
        assert np.array_equal(np.asarray(x), np.asarray(y)), (x, y)


def a_leaf_an_item():
    """Both ways on every rank alike: the names must agree."""
    real = eager._all_held
    eager._all_held = lambda *a: False

    def restore():
        eager._all_held = real
    return restore


def main():
    hvd.init()
    rank, size = hvd.rank(), hvd.size()
    assert size == 2 and eager.per_process_mode()

    # ---- allreduce_gradients, each way
    for kw in (dict(op=hvd.Average), dict(op=hvd.Sum), dict(op=hvd.Max),
               dict(op=hvd.Average, compression=Compression.bf16)):
        g = grads(0, rank)
        before = dict(trace.stage_group)
        flat = opt_mod.allreduce_gradients(g, **kw)
        assert trace.stage_group["packed"] - before["packed"] == len(g)
        restore = a_leaf_an_item()
        before = dict(trace.stage_group)
        leafwise = opt_mod.allreduce_gradients(g, **kw)
        assert trace.stage_group["packed"] == before["packed"]
        restore()
        bitwise(flat, leafwise)
        both = [grads(0, r) for r in range(size)]
        if kw["op"] == hvd.Sum:
            want = np.asarray(both[0]["a_w"]) + np.asarray(both[1]["a_w"])
            assert np.array_equal(np.asarray(flat["a_w"]), want)
            assert np.array_equal(
                np.asarray(flat["b_steps"]),
                np.asarray(both[0]["b_steps"]) + np.asarray(both[1]["b_steps"]))
        if kw["op"] == hvd.Max:
            assert np.array_equal(
                np.asarray(flat["c_half"]),
                np.maximum(np.asarray(both[0]["c_half"]),
                           np.asarray(both[1]["c_half"])))

    # ---- DistributedOptimizer.update, each way
    def train(steps):
        params = jax.tree_util.tree_map(jnp.ones_like, grads(0, 0, False))
        opt = hvd.DistributedOptimizer(optax.sgd(0.1, momentum=0.9))
        state = opt.init(params)
        outs = []
        for s in range(steps):
            updates, state = opt.update(grads(s, rank, False), state, params)
            outs.append(updates)
        return outs, state

    stage0, inner0 = dict(trace.stage_group), dict(trace.inner_update)
    flat = train(STEPS)
    assert trace.stage_group["packed"] - stage0["packed"] == 5 * STEPS
    assert trace.stage_group["traces"] - stage0["traces"] == 1
    assert trace.inner_update["traces"] - inner0["traces"] == 1
    restore = a_leaf_an_item()
    leafwise = train(STEPS)
    restore()
    bitwise(flat, leafwise)
    # every rank holds the same updates: gather one leaf and compare
    mine = np.asarray(flat[0][-1]["a_w"])
    everyone = np.asarray(eager.to_local(hvd.allgather(mine[None])))
    assert np.array_equal(everyone[0], everyone[1])

    print(f"FLAT_OK rank={rank}")
    hvd.shutdown()


if __name__ == "__main__":
    main()
