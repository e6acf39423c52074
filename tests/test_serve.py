"""Serving plane: continuous batcher, front door, readiness, percentiles,
serving autoscale signals (tier-1, no jax, no process spawns).

Covers the jax-free halves of the data-parallel serving plane (ISSUE 19,
``docs/serving.md``): ``serve/batcher.ContinuousBatcher`` admission /
deadline / padded-bucket / backpressure semantics under a scripted clock,
the ``serve/frontdoor.FrontDoor`` HTTP status mapping (200/429/503/504),
the monitor's ``/ready``-vs-``/health`` split, ``Histogram.percentile``
plus the p50/p99 Prometheus export, the aggregator's fleet
``request_rate``/``latency_p99_ms`` gauges, and the ``ScalePolicy``
request-rate / latency-target / serving-idle decisions.  The jax-backed
replica half has one single-process test at the end (batched-vs-sequential
parity, the recompile pin, drain with in-flight work); its two-process
form (broadcast fan-out, rolling update) lives in
``tests/data/worker_serve.py`` via ``test_multiprocess.py``.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import pytest

from horovod_tpu.elastic.autoscale import (
    HOLD, SCALE_IN, SCALE_OUT, ScalePolicy,
)
from horovod_tpu.monitor.agent import MonitorAgent
from horovod_tpu.monitor.aggregator import (
    EwmaTrend, RankAggregator, merged_percentile,
)
from horovod_tpu.monitor.http import MonitorHTTPServer
from horovod_tpu.monitor.registry import Histogram, MetricRegistry
from horovod_tpu.serve.batcher import (
    Batch, ContinuousBatcher, DeadlineExceeded, Draining, QueueFull,
    parse_buckets,
)
from horovod_tpu.serve.frontdoor import FrontDoor


def _clocked(**kw):
    """Batcher on a scripted clock; returns (batcher, tick)."""
    clock = [0.0]
    b = ContinuousBatcher(clock=lambda: clock[0], **kw)

    def tick(dt):
        clock[0] += dt
    return b, tick


# ----------------------------------------------------------------- batcher
def test_batcher_admission_and_positional_routing():
    b, _ = _clocked(max_batch=8)
    reqs = [b.submit([i]) for i in range(3)]
    batch = b.next_batch(timeout=0.0)
    assert batch.size == 3
    assert [r.id for r in batch.requests] == [r.id for r in reqs]
    b.complete(batch, [[i * 10] for i in range(3)])
    assert [r.wait(0.0) for r in reqs] == [[0], [10], [20]]


def test_batcher_padded_bucket_shapes():
    """Batch sizes snap UP to the bucket menu — the replica compiles one
    program per bucket, never one per ragged size."""
    b, _ = _clocked(max_batch=8)
    assert b.buckets == (1, 2, 4, 8)
    for n, want in ((1, 1), (2, 2), (3, 4), (5, 8), (8, 8)):
        assert b.bucket_for(n) == want, n
    for _ in range(5):
        b.submit([0])
    batch = b.next_batch(timeout=0.0)
    assert (batch.size, batch.bucket) == (5, 8)
    assert b.stats()["padding_rows_total"] == 3


def test_batcher_explicit_bucket_menu():
    b, _ = _clocked(max_batch=6, buckets=(2, 6))
    assert b.buckets == (2, 6)
    assert b.bucket_for(1) == 2 and b.bucket_for(3) == 6
    assert parse_buckets("1,3,9", 6) == (1, 3, 6)   # 9 > max dropped
    assert parse_buckets("", 8) == (1, 2, 4, 8)


def test_batcher_inflight_window_blocks_dispatch():
    """HOROVOD_MAX_INFLIGHT semantics: at most ``max_inflight`` batches
    dispatched-but-unsettled; settling reopens the window."""
    b, _ = _clocked(max_batch=2, max_inflight=1)
    for i in range(4):
        b.submit([i])
    first = b.next_batch(timeout=0.0)
    assert first is not None
    assert b.next_batch(timeout=0.0) is None        # window full
    b.complete(first, [[0], [0]])
    second = b.next_batch(timeout=0.0)
    assert second is not None and second.size == 2
    b.complete(second, [[0], [0]])


def test_batcher_deadline_expires_queued_requests():
    b, tick = _clocked(max_batch=4, deadline_ms=100.0)
    stale = b.submit([1])
    tick(0.2)                                       # past 100ms
    fresh = b.submit([2], deadline_ms=1000.0)
    batch = b.next_batch(timeout=0.0)
    assert [r.id for r in batch.requests] == [fresh.id]
    with pytest.raises(DeadlineExceeded):
        stale.wait(0.0)
    assert b.stats()["expired_total"] == 1
    b.complete(batch, [[2]])


def test_batcher_backpressure_and_drain():
    b, _ = _clocked(max_batch=4, queue_depth=2)
    b.submit([1])
    b.submit([2])
    with pytest.raises(QueueFull):
        b.submit([3])
    assert b.stats()["rejected_total"] == 1
    b.drain()
    with pytest.raises(Draining):
        b.submit([4])
    # The drain contract: queued work still dispatches and settles.
    batch = b.next_batch(timeout=0.0)
    assert batch.size == 2
    b.complete(batch, [[1], [2]])
    assert b.next_batch(timeout=0.0) is None        # drained + empty
    assert b.pending() == 0


def test_batcher_fail_routes_error_to_callers():
    b, _ = _clocked(max_batch=2)
    r = b.submit([1])
    batch = b.next_batch(timeout=0.0)
    b.fail(batch, RuntimeError("forward blew up"))
    with pytest.raises(RuntimeError, match="forward blew up"):
        r.wait(0.0)
    # The window slot was returned: new work still dispatches.
    b.submit([2])
    assert b.next_batch(timeout=0.0) is not None


# -------------------------------------------------------------- front door
def _door():
    b = ContinuousBatcher(max_batch=4, deadline_ms=2000.0, queue_depth=4)
    fd = FrontDoor(b).start()
    return b, fd


def _worker(b, stop, fn=lambda v: [x * 2 for x in v]):
    def loop():
        while not stop.is_set():
            batch = b.next_batch(timeout=0.02)
            if batch is not None:
                b.complete(batch, [fn(r.inputs) for r in batch.requests])
    t = threading.Thread(target=loop, daemon=True)
    t.start()
    return t


def _post(port, body, path="/v1/infer"):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    return json.loads(urllib.request.urlopen(req, timeout=10).read())


def test_frontdoor_http_roundtrip_and_stats():
    b, fd = _door()
    stop = threading.Event()
    t = _worker(b, stop)
    try:
        out = _post(fd.port, {"inputs": [1, 2, 3]})
        assert out["outputs"] == [2, 4, 6]
        assert out["latency_ms"] >= 0
        stats = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{fd.port}/v1/stats", timeout=10).read())
        assert stats["requests_total"] == 1
        assert stats["batches_total"] == 1
    finally:
        stop.set()
        t.join(2)
        fd.stop()


def test_frontdoor_maps_overload_to_429_and_drain_to_503():
    b, fd = _door()
    try:
        for i in range(4):                          # fill, no worker
            b.submit([i])
        with pytest.raises(urllib.error.HTTPError) as exc:
            _post(fd.port, {"inputs": [9]})
        assert exc.value.code == 429
        body = json.loads(exc.value.read())
        assert body["queue_depth"] == 4             # the autoscale signal
        assert exc.value.headers["Retry-After"]
        fd.drain()
        with pytest.raises(urllib.error.HTTPError) as exc:
            _post(fd.port, {"inputs": [9]})
        assert exc.value.code == 503
    finally:
        fd.stop()


def test_frontdoor_maps_deadline_to_504_and_bad_input_to_400():
    b, fd = _door()
    try:
        with pytest.raises(urllib.error.HTTPError) as exc:
            _post(fd.port, {"inputs": [1], "deadline_ms": 30})  # no worker
        assert exc.value.code == 504
        with pytest.raises(urllib.error.HTTPError) as exc:
            _post(fd.port, {"nope": 1})
        assert exc.value.code == 400
    finally:
        fd.stop()


# ------------------------------------------------------ readiness vs health
def test_ready_endpoint_splits_from_health():
    agent = MonitorAgent(rank=0, world=1)
    srv = MonitorHTTPServer(agent, port=0).start()
    base = f"http://127.0.0.1:{srv.port}"
    try:
        ready = json.loads(urllib.request.urlopen(
            base + "/ready", timeout=10).read())
        assert ready["ready"] is True
        agent.set_ready(False, "draining: driver cordon ping received")
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(base + "/ready", timeout=10)
        assert exc.value.code == 503
        body = json.loads(exc.value.read())
        assert "draining" in body["reason"]
        # /health stays truthful liveness: a draining replica is healthy.
        health = json.loads(urllib.request.urlopen(
            base + "/health", timeout=10).read())
        assert health["status"] == "ok"
        assert health["ready"] is False
        agent.set_ready(True)
        ready = json.loads(urllib.request.urlopen(
            base + "/ready", timeout=10).read())
        assert ready["ready"] is True
    finally:
        srv.stop()
        agent.close()


def test_peer_failure_forces_not_ready():
    agent = MonitorAgent(rank=0, world=2)
    agent._peer_failure = {"reason": "rank 1 died", "dead_ranks": [1]}
    r = agent.readiness()
    assert r["ready"] is False and "rank 1" in r["reason"]
    agent.close()


# ------------------------------------------------------------- percentiles
def test_histogram_percentile_interpolates_and_clamps():
    h = Histogram("lat", buckets=(10.0, 100.0, 1000.0))
    assert h.percentile(0.5) is None                # empty: no estimate
    for v in (5.0,) * 50 + (50.0,) * 40 + (500.0,) * 10:
        h.observe(v)
    assert h.percentile(0.5) == 10.0                # crossing at bucket edge
    assert 10.0 < h.percentile(0.9) <= 100.0
    assert 100.0 < h.percentile(0.99) <= 1000.0
    h.observe(1e9)                                  # +Inf overflow
    assert h.percentile(1.0) == 1000.0              # clamped to last bound
    with pytest.raises(ValueError):
        h.percentile(1.5)


def test_prometheus_export_includes_p50_p99():
    reg = MetricRegistry()
    h = reg.histogram("hvd_serve_latency_ms", buckets=(1.0, 10.0, 100.0))
    for v in (0.5, 5.0, 50.0, 5.0):
        h.observe(v)
    text = reg.to_prometheus(extra_label='rank="0"')
    assert 'hvd_serve_latency_ms_p50{rank="0"}' in text
    assert 'hvd_serve_latency_ms_p99{rank="0"}' in text
    empty = MetricRegistry()
    empty.histogram("h", buckets=(1.0,))
    assert "_p50" not in empty.to_prometheus()      # no data, no estimate


def test_merged_percentile_across_rank_histograms():
    a = Histogram("h", buckets=(10.0, 100.0))
    b = Histogram("h", buckets=(10.0, 100.0))
    for _ in range(90):
        a.observe(5.0)
    for _ in range(10):
        b.observe(50.0)
    p99 = merged_percentile(
        [a.snapshot_value(), b.snapshot_value()], 0.99)
    assert 10.0 < p99 <= 100.0                      # tail lives in rank b
    assert merged_percentile([], 0.99) is None


# --------------------------------------------------- serving fleet summary
def _serve_snap(total, hist):
    return {"rank": 0, "cycle_us_avg": 100.0,
            "metrics": {"hvd_serve_requests_total": total,
                        "hvd_serve_latency_ms": hist}}


def test_aggregator_fleet_request_rate_and_latency():
    agg = RankAggregator(world=1)
    h = Histogram("hvd_serve_latency_ms", buckets=(10.0, 100.0))
    for _ in range(100):
        h.observe(50.0)
    snap = h.snapshot_value()
    t0 = time.monotonic()
    # Rate needs a baseline first, then deltas; trends fill at 3 samples.
    for i, total in enumerate((0, 100, 200, 300, 400)):
        agg.update(0, _serve_snap(float(total), snap))
        if i < 4:
            time.sleep(0.02)
    s = agg.summary()
    assert s["request_rate"] is not None and s["request_rate"] > 0
    assert s["latency_p99_ms"] is not None
    assert 10.0 < s["latency_p99_ms"] <= 100.0
    agg.flush()                                     # world resize: reset
    assert agg.summary().get("request_rate") is None


def test_aggregator_without_serving_metrics_stays_null():
    agg = RankAggregator(world=1)
    for _ in range(6):
        agg.update(0, {"rank": 0, "cycle_us_avg": 100.0, "metrics": {}})
    s = agg.summary()
    assert s.get("request_rate") is None
    assert s.get("latency_p99_ms") is None


def test_ewma_level_null_until_filled():
    t = EwmaTrend(min_samples=3)
    t.update(10.0)
    t.update(20.0)
    assert t.level is None
    t.update(30.0)
    assert t.level is not None and t.level > 10.0


# ---------------------------------------------------- serving-mode policy
def _pol(**kw):
    kw.setdefault("min_np", 1)
    kw.setdefault("max_np", 8)
    kw.setdefault("persistence", 2)
    kw.setdefault("cooldown_s", 0.0)
    kw.setdefault("idle_s", 30.0)
    return ScalePolicy(**kw)


def test_policy_request_rate_triggers_scale_out():
    pol = _pol(rate_high=100.0)
    mk = lambda r: {"request_rate": r, "queue_depth": 0}   # noqa: E731
    assert pol.observe(mk(150.0), size=2, now=0.0).action == HOLD  # 75/rep
    assert pol.observe(mk(300.0), size=2, now=1.0).action == HOLD  # hit 1
    d = pol.observe(mk(300.0), size=2, now=2.0)                    # hit 2
    assert d.action == SCALE_OUT and d.target_size == 3
    assert "request_rate" in d.reason


def test_policy_latency_target_triggers_scale_out():
    pol = _pol(latency_target_ms=50.0)
    mk = lambda p: {"request_rate": 10.0, "latency_p99_ms": p,  # noqa: E731
                    "queue_depth": 0}
    assert pol.observe(mk(20.0), size=2, now=0.0).action == HOLD
    assert pol.observe(mk(80.0), size=2, now=1.0).action == HOLD
    d = pol.observe(mk(80.0), size=2, now=2.0)
    assert d.action == SCALE_OUT
    assert "p99" in d.reason


def test_policy_nulls_never_scale_serving():
    pol = _pol(rate_high=100.0, latency_target_ms=50.0)
    for i in range(5):
        d = pol.observe({"request_rate": None, "latency_p99_ms": None,
                         "queue_depth": 0}, size=2, now=float(i))
        assert d.action == HOLD


def test_policy_serving_idle_scales_in_on_low_qps():
    """With ``idle_qps`` set, idleness is rate-below-floor — training
    progress is irrelevant to a serving fleet."""
    pol = _pol(idle_qps=5.0, idle_s=10.0)
    mk = lambda r: {"request_rate": r, "queue_depth": 0,   # noqa: E731
                    "progress_total": 42.0}                # never moves
    assert pol.observe(mk(50.0), size=2, now=0.0).action == HOLD
    assert pol.observe(mk(1.0), size=2, now=5.0).action == HOLD
    d = pol.observe(mk(1.0), size=2, now=16.0)             # 11s below floor
    assert d.action == SCALE_IN and d.target_size == 1
    # Busy fleet: the timer must never accrue, even with zero progress.
    pol2 = _pol(idle_qps=5.0, idle_s=10.0)
    for i in range(5):
        assert pol2.observe(mk(50.0), size=2,
                            now=float(i * 10)).action == HOLD


def test_policy_training_idle_unaffected_without_idle_qps():
    """Serving knobs off: the progress-based idle test is untouched —
    a summary with request_rate present but idle_qps unset behaves
    exactly as before ISSUE 19."""
    pol = _pol(idle_s=10.0)
    mk = {"request_rate": 0.0, "queue_depth": 0, "progress_total": 1.0}
    # First sight of progress_total counts as progress (None -> 1.0), so
    # the idle timer starts at the SECOND unchanged observation.
    assert pol.observe(dict(mk), size=2, now=0.0).action == HOLD
    assert pol.observe(dict(mk), size=2, now=5.0).action == HOLD
    assert pol.observe(dict(mk), size=2, now=20.0).action == SCALE_IN


# ----------------------------------------------------------- replica (jax)
def test_replica_rows_independent_compiles_bounded_and_drains(hvd):
    """The serving invariant on the jitted padded-bucket forward: a
    request's result depends only on its own row (bitwise, same bucket
    program), batch-size churn compiles at most the bucket menu, and a
    drained batcher's queued work completes through ``serve_loop`` while
    new work is refused."""
    import numpy as np

    from horovod_tpu.serve.replica import Replica

    rng = np.random.RandomState(7)
    rep = Replica(lambda params, x: x @ params["w"])
    assert rep.load({"w": rng.randn(16, 8).astype(np.float32)}, version=1)
    assert rep.load({"w": np.zeros((16, 8), np.float32)}, version=1) is False
    x = rng.randn(8, 16).astype(np.float32)

    batched = rep.forward(x)
    alone = []
    for i in range(8):
        only = np.zeros_like(x)
        only[0] = x[i]                    # row i alone, position 0
        alone.append(rep.forward(only)[0])
    np.testing.assert_array_equal(batched, np.stack(alone))     # bitwise

    misses = rep.cache.misses
    for n in (3, 5, 7, 8, 2, 6):          # churn across the bucket menu
        rep.forward(x[:n])
    assert rep.cache.misses - misses <= 2  # buckets 2 and 4; 8 is compiled

    b = ContinuousBatcher(max_batch=4, deadline_ms=10000.0, max_inflight=2)
    inflight = [b.submit(x[i]) for i in range(8)]
    b.drain()
    with pytest.raises(Draining):
        b.submit(x[0])
    assert rep.serve_loop(b) == 2         # 4 + 4, then drained and empty
    got = np.stack([r.wait(0.0) for r in inflight])
    np.testing.assert_array_equal(
        got, np.concatenate([rep.forward(x[:4]), rep.forward(x[4:8])]))

