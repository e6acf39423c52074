"""Coordinator engine unit tests: fusion, cache, stall, error propagation.

Models the reference's single-process tier (``test/single/test_stall.py``,
``test_timeline.py`` — SURVEY.md §4) plus engine-specific invariants.
"""

import os

import numpy as np
import pytest


def _stacked(hvd, world, shape=(4,), dtype=np.float32, seed=0):
    rng = np.random.RandomState(seed)
    return hvd.stack_per_rank(
        [rng.randn(*shape).astype(dtype) for _ in range(world)])


def test_mixed_dtype_group_atomic(hvd, world_size):
    """Grouped ops with mixed dtypes must fuse into ONE batch (N13 parity)."""
    import horovod_tpu.ops.eager as eager
    from horovod_tpu.ops.engine import CollectiveType

    eng = eager._engine()
    executed_batches = []
    orig = eng._perform_operation

    def spy(batch):
        executed_batches.append([e.name for e in batch])
        return orig(batch)

    eng._perform_operation = spy
    try:
        a = _stacked(hvd, world_size, dtype=np.float32, seed=1)
        b = _stacked(hvd, world_size, dtype=np.float16, seed=2)
        outs = hvd.grouped_allreduce([a, b], name="mix", op=hvd.Sum)
    finally:
        eng._perform_operation = orig
    np.testing.assert_allclose(np.asarray(outs[0]),
                               np.sum(np.asarray(a), 0), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(outs[1]).astype(np.float32),
                               np.sum(np.asarray(b).astype(np.float32), 0),
                               rtol=2e-2)
    group_batches = [b for b in executed_batches if any("mix" in n for n in b)]
    assert len(group_batches) == 1, f"group split across {group_batches}"
    assert sorted(group_batches[0]) == ["mix.0", "mix.1"]


def test_inline_cycle_latency_guard(hvd, world_size):
    """Inline-dispatch fast path, mechanism + regression guard (VERDICT r4
    weak #3): (a) with no controller the coordinator cycle really runs on
    the submitting thread (no cycle-thread handoff on the blocking
    critical path), (b) 4KB p50 dispatch latency stays sane on the CPU
    mesh (generous bound for contended CI hosts; catches a regression to
    sleep-polling dispatch)."""
    import statistics
    import threading
    import time

    import horovod_tpu.ops.eager as eager

    eng = eager._engine()
    assert eng.controller is None, "single-controller fixture expected"

    # (a) the cycle executes on the calling thread.
    tids = []
    orig = eng.run_loop_once

    def spy():
        tids.append(threading.get_ident())
        return orig()

    eng.run_loop_once = spy
    try:
        x = _stacked(hvd, world_size, shape=(1024,))  # 4KB per rank
        hvd.allreduce(x, name="inline_guard_sem", op=hvd.Sum)
    finally:
        eng.run_loop_once = orig
    assert threading.get_ident() in tids, \
        "blocking single-controller op did not run the cycle inline"

    # (b) p50 latency bound.
    for _ in range(5):
        r = hvd.allreduce(x, name="inline_guard_warm", op=hvd.Sum)
    import jax
    jax.block_until_ready(r)
    ts = []
    for _ in range(30):
        t0 = time.perf_counter()
        r = hvd.allreduce(x, name="inline_guard_lat", op=hvd.Sum)
        jax.block_until_ready(r)
        ts.append(time.perf_counter() - t0)
    p50_ms = statistics.median(ts) * 1e3
    assert p50_ms <= 50.0, \
        f"inline 4KB allreduce p50 {p50_ms:.2f}ms (was ~0.5ms at capture)"


def test_cache_capacity_zero(hvd, world_size):
    """HOROVOD_CACHE_CAPACITY=0 disables caching without crashing."""
    from horovod_tpu.ops.engine import FusedProgramCache
    c = FusedProgramCache(0)
    assert c.get_or_build(("k",), lambda: "v1") == "v1"
    assert c.get_or_build(("k",), lambda: "v2") == "v2"  # rebuilt, no cache
    assert c.misses == 2 and c.hits == 0


def test_planning_error_fails_entries_not_hangs(hvd, world_size):
    """An exception during negotiation/planning must propagate to waiters
    (not strand them) — the stall-shutdown abort path in particular."""
    import horovod_tpu.ops.eager as eager

    eng = eager._engine()
    orig = eng._compute_response_list

    def boom(entries):
        raise RuntimeError("negotiation exploded")

    eng._compute_response_list = boom
    try:
        h = hvd.allreduce_async(_stacked(hvd, world_size), name="doomed")
        with pytest.raises(RuntimeError, match="negotiation exploded"):
            hvd.synchronize(h)
    finally:
        eng._compute_response_list = orig
    # Engine still healthy afterwards:
    out = hvd.allreduce(_stacked(hvd, world_size, seed=3), op=hvd.Sum)
    assert np.asarray(out).shape == (4,)


def test_reducescatter_min_max(hvd, world_size):
    vals = [np.random.RandomState(r).randn(world_size * 2, 3).astype(np.float32)
            for r in range(world_size)]
    out = np.asarray(hvd.reducescatter(hvd.stack_per_rank(vals), op=hvd.Min))
    full_min = np.min(np.stack(vals), axis=0)
    for r in range(world_size):
        np.testing.assert_allclose(out[r], full_min[2 * r:2 * r + 2], rtol=1e-6)
    out = np.asarray(hvd.reducescatter(hvd.stack_per_rank(vals), op=hvd.Max))
    full_max = np.max(np.stack(vals), axis=0)
    for r in range(world_size):
        np.testing.assert_allclose(out[r], full_max[2 * r:2 * r + 2], rtol=1e-6)


def test_reducescatter_bad_op(hvd, world_size):
    with pytest.raises(ValueError):
        hvd.reducescatter(_stacked(hvd, world_size, shape=(world_size, 2)),
                          op=hvd.Adasum)


def test_fusion_splits_at_threshold(hvd, world_size):
    """Batches split when exceeding HOROVOD_FUSION_THRESHOLD."""
    import horovod_tpu.ops.eager as eager
    eng = eager._engine()
    old_threshold = eng.fusion_threshold
    executed = []
    orig = eng._perform_operation

    def spy(batch):
        executed.append(len(batch))
        return orig(batch)

    eng.fusion_threshold = 4 * world_size * 10  # fits ~1 tensor of 10 floats
    eng._perform_operation = spy
    try:
        hs = [hvd.allreduce_async(_stacked(hvd, world_size, shape=(10,),
                                           seed=i), name=f"split{i}",
                                  op=hvd.Sum)
              for i in range(4)]
        hvd.synchronize(hs)
    finally:
        eng._perform_operation = orig
        eng.fusion_threshold = old_threshold
    assert max(executed) <= 2  # nothing fused beyond the tiny threshold


def test_stall_inspector_warns():
    from horovod_tpu.ops.engine import StallInspector, TensorTableEntry, \
        CollectiveType
    from horovod_tpu.utils.logging import get_logger
    import logging
    import time

    records = []

    class Capture(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    handler = Capture()
    logger = get_logger()
    logger.addHandler(handler)
    try:
        si = StallInspector(warn_after_s=0.0, shutdown_after_s=0.0)
        e = TensorTableEntry(handle=1, name="slow",
                             ctype=CollectiveType.ALLREDUCE, tensor=None)
        e.enqueue_time = time.monotonic() - 5
        si.check([e], missing_ranks={"slow": [2, 3]})
    finally:
        logger.removeHandler(handler)
    assert any("Stall detected" in m for m in records)
    assert any("[2, 3]" in m for m in records)


def test_stall_shutdown_raises():
    from horovod_tpu.ops.engine import StallInspector, TensorTableEntry, \
        CollectiveType
    import time
    si = StallInspector(warn_after_s=0.0, shutdown_after_s=0.001)
    e = TensorTableEntry(handle=1, name="dead", ctype=CollectiveType.ALLREDUCE,
                         tensor=None)
    e.enqueue_time = time.monotonic() - 5
    with pytest.raises(RuntimeError, match="stalled"):
        si.check([e])


def test_timeline_written(tmp_path, hvd, world_size):
    import json
    import horovod_tpu as _hvd
    f = tmp_path / "tl.json"
    _hvd.start_timeline(str(f))
    hvd.allreduce(_stacked(hvd, world_size, seed=9), name="tl_tensor")
    _hvd.stop_timeline()
    events = json.loads(f.read_text())
    names = {e.get("name") for e in events}
    assert "QUEUE" in names and "NEGOTIATE_ALLREDUCE" in names \
        and "XLA_ALLREDUCE" in names
    # per-tensor lane metadata exists
    lanes = [e for e in events if e.get("name") == "thread_name"]
    assert any(e["args"]["name"] == "tl_tensor" for e in lanes)


def test_requeue_preserves_entries(hvd, world_size):
    """Controller-filtered (not ready) entries execute on a later cycle."""
    import horovod_tpu.ops.eager as eager

    eng = eager._engine()

    class HoldFirstCycle:
        def __init__(self):
            self.calls = 0

        def negotiate(self, entries):
            self.calls += 1
            if self.calls == 1:
                return [], []  # nothing ready yet
            return entries, []

    eng.controller = HoldFirstCycle()
    try:
        h = hvd.allreduce_async(_stacked(hvd, world_size, seed=4),
                                name="held", op=hvd.Sum)
        out = hvd.synchronize(h, )
        assert np.asarray(out).shape == (4,)
        assert eng.controller.calls >= 2
    finally:
        eng.controller = None


class TestHierarchicalAllreduce:
    """HOROVOD_HIERARCHICAL_ALLREDUCE must change the executed program to
    the RS(local)→AR(cross)→AG(local) three-phase (reference N17 parity) and
    keep numerics identical to the flat path."""

    def _reinit(self, **env):
        import horovod_tpu as hvd
        hvd.shutdown()
        for k, v in env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        hvd.init()
        return hvd

    def _lower_allreduce(self, eng, x):
        from horovod_tpu.ops import collectives as C
        from horovod_tpu.ops.engine import CollectiveType, TensorTableEntry
        proto = TensorTableEntry(handle=0, name="h",
                                 ctype=CollectiveType.ALLREDUCE, tensor=None,
                                 reduce_op=C.ReduceOp.SUM)
        mesh, axis, world = eng._mesh_axis(0)
        fn = eng._build_program(proto, (tuple(x.shape),), (str(x.dtype),),
                                mesh, axis, world)
        return fn.lower(x).as_text()

    def test_flag_changes_program_and_numerics(self, world_size):
        import horovod_tpu.ops.eager as eager
        local = 4 if world_size % 4 == 0 else 2
        hvd = self._reinit(HOROVOD_HIERARCHICAL_ALLREDUCE="1",
                           HOROVOD_HIERARCHICAL_LOCAL_SIZE=str(local))
        try:
            eng = eager._engine()
            hmesh = eng._hier_mesh(0)
            assert hmesh is not None
            assert hmesh.devices.shape == (world_size // local, local)

            x = _stacked(hvd, world_size, shape=(7,), seed=11)
            hlo = self._lower_allreduce(eng, x)
            assert "reduce_scatter" in hlo, "no RS phase in hierarchical HLO"
            assert "all_gather" in hlo, "no AG phase in hierarchical HLO"

            out = hvd.allreduce(x, op=hvd.Average)
            np.testing.assert_allclose(np.asarray(out),
                                       np.mean(np.asarray(x), 0), rtol=1e-5)
            out = hvd.allreduce(x, op=hvd.Sum)
            np.testing.assert_allclose(np.asarray(out),
                                       np.sum(np.asarray(x), 0), rtol=1e-5)
            # allgather stays flat unless its own flag is set; result parity:
            g = hvd.allgather(_stacked(hvd, world_size, shape=(3,), seed=12))
            assert np.asarray(g).shape == (world_size * 3,)
        finally:
            hvd = self._reinit(HOROVOD_HIERARCHICAL_ALLREDUCE=None,
                               HOROVOD_HIERARCHICAL_LOCAL_SIZE=None)

    def test_flat_path_has_no_reduce_scatter(self, hvd, world_size):
        import horovod_tpu.ops.eager as eager
        eng = eager._engine()
        assert eng._hier_mesh(0) is None  # single process, no override
        x = _stacked(hvd, world_size, shape=(7,), seed=11)
        hlo = self._lower_allreduce(eng, x)
        assert "reduce_scatter" not in hlo

    def test_hierarchical_allgather(self, world_size):
        import horovod_tpu.ops.eager as eager
        local = 4 if world_size % 4 == 0 else 2
        hvd = self._reinit(HOROVOD_HIERARCHICAL_ALLGATHER="1",
                           HOROVOD_HIERARCHICAL_LOCAL_SIZE=str(local))
        try:
            eng = eager._engine()
            x = _stacked(hvd, world_size, shape=(3, 2), seed=13)
            out = hvd.allgather(x)
            np.testing.assert_allclose(
                np.asarray(out),
                np.concatenate(list(np.asarray(x)), axis=0), rtol=1e-6)
        finally:
            hvd = self._reinit(HOROVOD_HIERARCHICAL_ALLGATHER=None,
                               HOROVOD_HIERARCHICAL_LOCAL_SIZE=None)


class TestAdasumEngine:
    """The engine's ADASUM program must lower to halving-doubling
    (collective-permute, no all-gather) on power-of-two worlds and match
    the gather tree numerically (VERDICT r2 #3 'done' criteria)."""

    def _lower_adasum(self, eng, x):
        from horovod_tpu.ops import collectives as C
        from horovod_tpu.ops.engine import CollectiveType, TensorTableEntry
        proto = TensorTableEntry(handle=0, name="ad",
                                 ctype=CollectiveType.ALLREDUCE, tensor=None,
                                 reduce_op=C.ReduceOp.ADASUM)
        mesh, axis, world = eng._mesh_axis(0)
        fn = eng._build_program(proto, (tuple(x.shape),), (str(x.dtype),),
                                mesh, axis, world)
        return fn.lower(x).as_text()

    def test_hlo_is_collective_permute_not_allgather(self, hvd, world_size):
        import horovod_tpu.ops.eager as eager
        if world_size & (world_size - 1):
            pytest.skip("needs power-of-two world")
        eng = eager._engine()
        x = _stacked(hvd, world_size, shape=(9,), seed=21)
        hlo = self._lower_adasum(eng, x).replace("-", "_")
        assert "collective_permute" in hlo, "ADASUM not lowered to VHDD"
        assert "all_gather" not in hlo, \
            "ADASUM still uses the O(n)-bandwidth gather path"

    def test_engine_adasum_matches_tree(self, hvd, world_size):
        from horovod_tpu.parallel.adasum import _tree_reduce
        if world_size & (world_size - 1):
            pytest.skip("needs power-of-two world")
        vals = np.random.RandomState(23).randn(
            world_size, 11).astype(np.float32)
        out = hvd.allreduce(hvd.stack_per_rank(list(vals[:, None])),
                            op=hvd.Adasum)
        import jax.numpy as jnp
        expected = np.asarray(_tree_reduce(jnp.asarray(vals), world_size))
        np.testing.assert_allclose(np.asarray(out).reshape(-1),
                                   expected.reshape(-1),
                                   rtol=1e-4, atol=1e-5)


# ============================================== steady-state fast path (PR 2)
def test_fused_program_cache_lru_eviction():
    """LRU, not FIFO: a hit refreshes an entry's recency, so an A/B working
    set one entry over capacity evicts the stale key, not the hot one."""
    from horovod_tpu.ops.engine import FusedProgramCache

    c = FusedProgramCache(capacity=2)
    assert c.get_or_build(("A",), lambda: "fa") == "fa"
    assert c.get_or_build(("B",), lambda: "fb") == "fb"
    assert c.get_or_build(("A",), lambda: "WRONG") == "fa"   # hit: A is MRU
    assert c.get_or_build(("C",), lambda: "fc") == "fc"      # evicts B (LRU)
    assert c.evictions == 1
    assert c.get_or_build(("A",), lambda: "WRONG") == "fa"   # survived
    misses0 = c.misses
    assert c.get_or_build(("B",), lambda: "fb2") == "fb2"    # B was evicted
    assert c.misses == misses0 + 1
    assert len(c) == 2


def test_tensor_queue_requeue_ordering_under_interleaved_push():
    """Requeued (drained-but-not-ready) entries must come back BEFORE pushes
    that landed while they were out: negotiation order across cycles stays
    the submission order, which every rank's batching depends on."""
    from horovod_tpu.ops.engine import (CollectiveType, TensorQueue,
                                        TensorTableEntry)

    def mk(name, h):
        return TensorTableEntry(handle=h, name=name,
                                ctype=CollectiveType.BARRIER, tensor=None)

    q = TensorQueue()
    a, b = mk("a", 1), mk("b", 2)
    q.push_many([a, b])
    assert [e.name for e in q.drain()] == ["a", "b"]
    q.push(mk("c", 3))                   # lands while a, b are in flight
    q.requeue([a, b])
    assert [e.name for e in q.drain()] == ["a", "b", "c"]
    # Names of requeued entries stay registered: resubmission is rejected
    # until mark_done, exactly like a still-pending entry.
    q.requeue([a])
    with pytest.raises(ValueError):
        q.push(mk("a", 9))
    assert [e.name for e in q.drain()] == ["a"]
    q.mark_done(a)
    q.push(mk("a", 10))                  # completed name is reusable
    assert [e.name for e in q.drain()] == ["a"]
    assert q.pending_count() == 0


def test_allreduce_wire_compression_matches_fp32(hvd, world_size):
    """compression="bf16"/"fp16" halves the wire dtype INSIDE the fused
    program: result matches the fp32 reduce within cast tolerance, comes
    back as fp32, and the compressed program caches separately and is
    reused across steps."""
    from horovod_tpu.common import basics

    eng = basics._get_state().engine
    x = _stacked(hvd, world_size, shape=(257,), seed=31)
    base = np.asarray(hvd.allreduce(x, name="wc32", op=hvd.Sum))
    for mode, tol in (("bf16", 3e-2), ("fp16", 5e-3)):
        out = np.asarray(hvd.allreduce(x, name=f"wc_{mode}", op=hvd.Sum,
                                       compression=mode))
        assert out.dtype == np.float32
        np.testing.assert_allclose(out, base, rtol=tol, atol=tol)
        # And NOT bit-identical: the wire cast must actually have happened.
        assert not np.array_equal(out, base), mode
    # Program reuse: a second compressed submission with the same shape
    # signature must be a cache hit (single cached program).
    misses0, hits0 = eng.cache.misses, eng.cache.hits
    out2 = np.asarray(hvd.allreduce(x, name="wc_bf16_2", op=hvd.Sum,
                                    compression="bf16"))
    assert eng.cache.misses == misses0 and eng.cache.hits == hits0 + 1
    np.testing.assert_allclose(out2, base, rtol=3e-2, atol=3e-2)


def test_grouped_wire_compression_mixed_dtypes(hvd, world_size):
    """Wire compression only touches floating leaves: an int32 member of
    the same atomic group reduces exactly."""
    a = _stacked(hvd, world_size, shape=(16,), seed=32)
    b = hvd.stack_per_rank(
        [np.full((8,), r + 1, np.int32) for r in range(world_size)])
    outs = hvd.grouped_allreduce([a, b], name="wcg", op=hvd.Sum,
                                 compression="bf16")
    np.testing.assert_allclose(np.asarray(outs[0]), np.sum(np.asarray(a), 0),
                               rtol=3e-2, atol=3e-2)
    np.testing.assert_array_equal(
        np.asarray(outs[1]),
        np.full((8,), sum(range(1, world_size + 1)), np.int32))


def test_wire_compression_average_and_scale(hvd, world_size):
    """AVERAGE + pre/postscale compose with the wire cast (prescale in the
    original dtype, cast, reduce, cast up, postscale)."""
    x = _stacked(hvd, world_size, shape=(64,), seed=33)
    base = np.asarray(hvd.allreduce(x, name="was32", prescale_factor=0.5,
                                    postscale_factor=2.0))
    out = np.asarray(hvd.allreduce(x, name="was_c", prescale_factor=0.5,
                                   postscale_factor=2.0,
                                   compression="bf16"))
    np.testing.assert_allclose(out, base, rtol=3e-2, atol=3e-2)


def test_wire_compression_rejects_unknown_mode(hvd, world_size):
    x = _stacked(hvd, world_size)
    with pytest.raises(ValueError, match="compression"):
        hvd.allreduce(x, name="wbad", compression="int8")


def test_wire_compression_accepts_compressor_classes(hvd, world_size):
    """Upstream calling convention: compression=Compression.fp16 (a class)
    routes through the fused wire path via its wire_mode attribute."""
    from horovod_tpu.jax.compression import Compression

    x = _stacked(hvd, world_size, shape=(32,), seed=41)
    base = np.asarray(hvd.allreduce(x, name="cc32", op=hvd.Sum))
    out = np.asarray(hvd.allreduce(x, name="cc_cls", op=hvd.Sum,
                                   compression=Compression.fp16))
    np.testing.assert_allclose(out, base, rtol=3e-2, atol=3e-2)
    # NoneCompressor maps to off (exact).
    out2 = np.asarray(hvd.allreduce(x, name="cc_none", op=hvd.Sum,
                                    compression=Compression.none))
    np.testing.assert_array_equal(out2, base)
