"""Serving-plane fault tolerance (ISSUE 20) — tier-1, jax-free.

Covers the hard invariant's jax-free machinery: the circuit breaker
state machine (closed → open → half-open → closed, trip/probe
thresholds, fast-fail within one request of tripping), deadline-bounded
retry/backoff at the front door, idempotent re-submission through the
batcher's resident map, the poisoned-request quarantine, tail-latency
hedging, the retryable replica-fault path (queued requests preserved
with original deadlines), the drain satellites (Retry-After, prompt
dead-on-arrival expiry) and the empty-histogram percentile contract the
hedging delay reads at startup.  The cross-process kill-mid-batch
acceptance lives in ``tests/test_multiprocess.py``
(``worker_serve_faults.py``).
"""

import threading
import time

import pytest

from horovod_tpu.monitor.aggregator import merged_percentile
from horovod_tpu.monitor.registry import Histogram
from horovod_tpu.serve.batcher import (
    LATENCY_MS_BUCKETS, Cancelled, ContinuousBatcher, DeadlineExceeded,
    ForwardFailed, ReplicaFaulted, RequestQuarantined,
)
from horovod_tpu.serve.frontdoor import FrontDoor
from horovod_tpu.serve.resilience import (
    CLOSED, HALF_OPEN, OPEN, CircuitBreaker,
)


class _Clock:
    """Scripted monotonic clock."""

    def __init__(self, t: float = 0.0):
        self.t = float(t)

    def __call__(self) -> float:
        return self.t

    def tick(self, dt: float) -> None:
        self.t += dt


# ---------------------------------------------------------------- breaker


def test_breaker_trips_after_threshold_and_fast_fails():
    clk = _Clock()
    br = CircuitBreaker(threshold=3, reset_s=5.0, probes=2, clock=clk)
    assert br.state == CLOSED and br.allow()
    br.record_failure()
    br.record_failure()
    assert br.state == CLOSED and br.allow()     # below threshold
    br.record_failure()                          # 3rd consecutive: trips
    assert br.state == OPEN and br.trips == 1
    # Fast-fail within ONE request of tripping: the very next allow()
    # refuses, and Retry-After knows the remaining window.
    assert not br.allow()
    assert br.retry_after_s() == pytest.approx(5.0)
    clk.tick(2.0)
    assert br.retry_after_s() == pytest.approx(3.0)
    assert not br.allow()


def test_breaker_success_resets_the_streak():
    br = CircuitBreaker(threshold=3, clock=_Clock())
    br.record_failure()
    br.record_failure()
    br.record_success()                          # streak broken
    br.record_failure()
    br.record_failure()
    assert br.state == CLOSED                    # never 3 CONSECUTIVE


def test_breaker_half_opens_then_closes_on_probe_successes():
    clk = _Clock()
    br = CircuitBreaker(threshold=1, reset_s=2.0, probes=2, clock=clk)
    br.record_failure()
    assert br.state == OPEN
    clk.tick(2.0)                                # window over: half-open
    assert br.state == HALF_OPEN
    # At most `probes` unresolved probes at a time.
    assert br.allow() and br.allow()
    assert not br.allow()
    br.record_success()
    assert br.state == HALF_OPEN                 # one good probe: not yet
    assert br.allow()                            # slot freed
    br.record_success()
    assert br.state == CLOSED and br.retry_after_s() == 0.0


def test_breaker_half_open_failure_reopens_fresh_window():
    clk = _Clock()
    br = CircuitBreaker(threshold=1, reset_s=2.0, probes=1, clock=clk)
    br.record_failure()
    clk.tick(2.0)
    assert br.allow()                            # the probe
    br.record_failure()                          # probe failed: re-trip
    assert br.state == OPEN and br.trips == 2
    assert br.retry_after_s() == pytest.approx(2.0)


def test_breaker_release_probe_frees_the_slot():
    """A probe that ends with NEITHER verdict (deadline, queue full,
    drain, quarantine) must give its slot back — otherwise `probes` such
    outcomes wedge the breaker half-open with allow() refusing forever."""
    clk = _Clock()
    br = CircuitBreaker(threshold=1, reset_s=2.0, probes=2, clock=clk)
    br.record_failure()
    clk.tick(2.0)
    assert br.allow() and br.allow()             # both probe slots out
    assert not br.allow()
    br.release_probe()                           # e.g. probe hit its 504
    assert br.state == HALF_OPEN
    assert br.allow()                            # slot usable again
    br.release_probe()
    br.release_probe()                           # extra releases: clamped
    assert br.allow() and br.allow()
    assert not br.allow()
    # While closed, release_probe is a no-op.
    br2 = CircuitBreaker(threshold=3, clock=_Clock())
    br2.release_probe()
    assert br2.state == CLOSED and br2.allow()


def test_breaker_abandoned_probes_reclaimed_by_clock():
    """Backstop: even if a probe holder dies without releasing, slots
    idle past reset_s are reclaimed — there is a time-based escape from
    half-open, never a permanent wedge."""
    clk = _Clock()
    br = CircuitBreaker(threshold=1, reset_s=2.0, probes=1, clock=clk)
    br.record_failure()
    clk.tick(2.0)
    assert br.allow()                            # probe out, never resolved
    assert not br.allow()
    clk.tick(2.0)                                # slot idle for reset_s
    assert br.state == HALF_OPEN
    assert br.allow()                            # reclaimed, not wedged


# ------------------------------------------------------- batcher fault API


def test_idempotent_resubmission_joins_resident_request():
    b = ContinuousBatcher(max_batch=4, deadline_ms=60000.0)
    r1 = b.submit(1.0, request_id="req-a")
    r2 = b.submit(1.0, request_id="req-a")       # joins, never forks
    assert r1 is r2
    assert b.stats()["resubmitted_total"] == 1
    assert b.stats()["requests_total"] == 1      # admitted ONCE
    # Still idempotent while dispatched-but-unsettled.
    batch = b.next_batch(timeout=0.1)
    assert b.submit(1.0, request_id="req-a") is r1
    b.complete(batch, [2.0])
    # Settled: the id is free again — a NEW request under the old id.
    r3 = b.submit(1.0, request_id="req-a")
    assert r3 is not r1


def test_quarantine_nth_consecutive_failure_is_terminal():
    b = ContinuousBatcher(max_batch=1, deadline_ms=60000.0,
                          quarantine_after=3)
    boom = RuntimeError("forward blew up")
    for expect in (ForwardFailed, ForwardFailed, RequestQuarantined):
        r = b.submit(1.0, request_id="poison")
        batch = b.next_batch(timeout=0.1)
        b.fail(batch, boom)
        assert isinstance(r.error, expect), r.error
        assert r.error.__cause__ is boom
        with pytest.raises(RuntimeError, match="forward blew up"):
            r.wait(0)
    assert b.stats()["quarantined_total"] == 1
    # Retryable wrappers read as Retryable; quarantine does NOT.
    assert not isinstance(RequestQuarantined("x"), ForwardFailed)


def test_quarantine_success_resets_the_count():
    b = ContinuousBatcher(max_batch=1, deadline_ms=60000.0,
                          quarantine_after=2)
    for _ in range(2):
        b.submit(1.0, request_id="flaky")
        b.fail(b.next_batch(timeout=0.1), RuntimeError("transient"))
        b.submit(1.0, request_id="flaky")
        b.complete(b.next_batch(timeout=0.1), [2.0])   # success: reset
    assert b.stats()["quarantined_total"] == 0


def test_quarantine_count_survives_unrelated_traffic_under_bound():
    """The _fail_counts size bound evicts least-recently-UPDATED entries:
    a poisoned request actively being retried keeps its streak even when
    unrelated failing traffic churns the table past the bound."""
    b = ContinuousBatcher(max_batch=1, deadline_ms=60000.0,
                          quarantine_after=3, queue_depth=1)  # bound = 4

    def _fail_once(rid):
        r = b.submit(1.0, request_id=rid)
        b.fail(b.next_batch(timeout=0.1), RuntimeError("boom"))
        return r

    _fail_once("poison")                         # count 1, oldest inserted
    _fail_once("u1")
    _fail_once("poison")                         # count 2, moved to end
    for rid in ("u2", "u3", "u4"):               # churn past the bound
        _fail_once(rid)
    r = _fail_once("poison")                     # 3rd consecutive: terminal
    assert isinstance(r.error, RequestQuarantined), r.error
    assert b.stats()["quarantined_total"] == 1


def test_fail_retryable_preserves_queue_with_original_deadlines():
    clk = _Clock()
    b = ContinuousBatcher(max_batch=2, deadline_ms=1000.0, clock=clk)
    dispatched = [b.submit(1.0), b.submit(2.0)]
    queued = b.submit(3.0)
    original_deadline = queued.deadline
    batch = b.next_batch(timeout=0.0)
    assert [r.id for r in batch.requests] == [r.id for r in dispatched]
    b.fail_retryable(batch, RuntimeError("peer 1 died"))
    for r in dispatched:
        assert isinstance(r.error, ReplicaFaulted)
        with pytest.raises(ReplicaFaulted, match="peer 1 died"):
            r.wait(0)
    # The untouched queued request rides on, deadline UNCHANGED.
    assert not queued.done()
    assert queued.deadline == original_deadline
    s = b.stats()
    assert s["replica_faults_total"] == 1
    assert s["requeued_total"] == 1
    assert s["quarantined_total"] == 0           # world's fault, not theirs
    assert s["inflight"] == 0                    # window slot released


def test_cancel_only_while_queued():
    b = ContinuousBatcher(max_batch=1, deadline_ms=60000.0, max_inflight=1)
    r1 = b.submit(1.0)
    r2 = b.submit(2.0)
    batch = b.next_batch(timeout=0.1)            # r1 in flight
    assert not b.cancel(r1)                      # dispatched: too late
    assert b.cancel(r2)                          # queued: cancelled
    assert isinstance(r2.error, Cancelled)
    assert b.stats()["cancelled_total"] == 1
    b.complete(batch, [2.0])
    assert not b.cancel(r1)                      # settled: no-op


def test_drain_promptly_fails_dead_on_arrival_requests():
    clk = _Clock()
    b = ContinuousBatcher(max_batch=4, deadline_ms=100.0, clock=clk)
    dead = b.submit(1.0)
    clk.tick(0.2)                                # 200ms: past its deadline
    live = b.submit(2.0)
    b.drain()
    # The expired request was failed AT drain time, not left to ride to
    # dispatch-time rejection; the live one still completes.
    assert dead.done() and isinstance(dead.error, DeadlineExceeded)
    assert not live.done()
    assert b.stats()["expired_total"] == 1
    b.complete(b.next_batch(timeout=0.0), [4.0])
    assert live.wait(0) == 4.0


# -------------------------------------------------- front door: retries


def _door(batcher, **kw):
    kw.setdefault("retries", 2)
    kw.setdefault("hedge_ms", 0.0)
    kw.setdefault("breaker", CircuitBreaker(threshold=100))
    door = FrontDoor(batcher, port=0, **kw)
    return door


def _consume(batcher, script):
    """Background consumer: ``script(batch, n)`` decides each batch's
    fate (n is the 1-based dispatch count)."""
    stop = threading.Event()

    def run():
        n = 0
        while not stop.is_set():
            batch = batcher.next_batch(timeout=0.02)
            if batch is None:
                continue
            n += 1
            script(batch, n)

    t = threading.Thread(target=run, daemon=True)
    t.start()
    return stop


@pytest.mark.parametrize("clients,fault_at", [(1, 1), (32, 3)])
def test_front_door_retries_replica_fault_to_success(clients, fault_at):
    """A replica fault mid-batch is retried to success under the same
    request id.  With 32 concurrent clients and the third dispatched batch
    failing: zero accepted requests lost — every one gets exactly one
    terminal 200 with its own row's result."""
    b = ContinuousBatcher(max_batch=4, deadline_ms=10000.0,
                          queue_depth=2 * clients)
    door = _door(b, retries=4, breaker=CircuitBreaker(threshold=10000))

    def script(batch, n):
        if n == fault_at:
            b.fail_retryable(batch, RuntimeError("peer died mid-batch"))
        else:
            b.complete(batch, [r.inputs * 2 for r in batch.requests])

    stop = _consume(b, script)
    outcomes = [None] * clients

    def client(i):
        outcomes[i] = door.infer_detailed(
            21.0 + i, request_id=f"fault-{i}", deadline_ms=10000.0)

    try:
        threads = [threading.Thread(target=client, args=(i,), daemon=True)
                   for i in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert all(o is not None for o in outcomes), "a request was lost"
        assert [o["_code"] for o in outcomes] == [200] * clients, outcomes
        assert [o["outputs"] for o in outcomes] == \
            [2 * (21.0 + i) for i in range(clients)]
        retried = [o for o in outcomes if o["attempts"] > 1]
        assert retried and all(o["attempts"] == 2 for o in retried)
        s = door.stats()
        assert s["retries_total"] == len(retried)
        assert s["replica_faults_total"] == 1
        assert s["quarantined_total"] == 0
        assert s["availability"] == 1.0          # terminal outcomes all OK
    finally:
        stop.set()
        door.stop()


def test_front_door_retry_backoff_never_outlives_deadline():
    """The acceptance bound: with every attempt failing retryably, the
    terminal response lands within the request's own deadline plus one
    dispatch interval — backoff that would overshoot is abandoned."""
    b = ContinuousBatcher(max_batch=4, deadline_ms=5000.0)
    door = _door(b, retries=50)                  # deadline binds, not count

    stop = _consume(b, lambda batch, n: b.fail_retryable(
        batch, RuntimeError("world is down")))
    try:
        deadline_s = 0.25
        t0 = time.monotonic()
        out = door.infer_detailed(1.0, deadline_ms=deadline_s * 1000)
        elapsed = time.monotonic() - t0
        assert out["_code"] in (503, 504), out
        assert out.get("retryable") or "deadline" in out["error"], out
        # One dispatch interval of slack (the consumer polls at 20ms) +
        # scheduling noise; far below what even one extra backoff at the
        # cap (1s) would add.
        assert elapsed < deadline_s + 0.5, elapsed
    finally:
        stop.set()
        door.stop()


def test_front_door_quarantine_is_terminal_not_retried_forever():
    b = ContinuousBatcher(max_batch=4, deadline_ms=5000.0,
                          quarantine_after=2)
    door = _door(b, retries=10)
    stop = _consume(b, lambda batch, n: b.fail(
        batch, RuntimeError("poisoned input")))
    try:
        out = door.infer_detailed(1.0)
        assert out["_code"] == 500 and out.get("quarantined"), out
        assert out["request_id"]
        assert b.stats()["quarantined_total"] == 1
        # Exactly quarantine_after attempts were executed — the terminal
        # verdict stopped the retry budget (10) from being burned.
        assert b.stats()["requests_total"] == 2
    finally:
        stop.set()
        door.stop()


def test_front_door_breaker_trips_and_fast_fails_then_heals():
    b = ContinuousBatcher(max_batch=4, deadline_ms=2000.0)
    breaker = CircuitBreaker(threshold=2, reset_s=0.05, probes=1)
    door = _door(b, retries=0, breaker=breaker)
    healed = threading.Event()

    def script(batch, n):
        if healed.is_set():
            b.complete(batch, [r.inputs for r in batch.requests])
        else:
            b.fail_retryable(batch, RuntimeError("replica faulted"))

    stop = _consume(b, script)
    try:
        for _ in range(2):                       # trip the breaker
            assert door.infer_detailed(1.0)["_code"] == 503
        # Fast-fail within one request of tripping: no admission, just a
        # 503 with Retry-After and the breaker named.
        before = b.stats()["requests_total"]
        out = door.infer_detailed(1.0)
        assert out["_code"] == 503 and out["breaker"] == "open", out
        assert out["_retry_after"] >= 1
        assert b.stats()["requests_total"] == before   # never admitted
        assert door.stats()["breaker_state"] == "open"
        assert door.stats()["breaker_trips"] == 1
        # Heal: the reset window elapses, the probe succeeds, it closes.
        healed.set()
        time.sleep(0.06)
        assert door.infer_detailed(5.0)["_code"] == 200
        assert door.stats()["breaker_state"] == "closed"
        assert door.stats()["availability"] < 1.0      # errors were counted
    finally:
        stop.set()
        door.stop()


def test_front_door_probe_504_releases_slot_and_breaker_still_heals():
    """The common heal race: half-open probes time out to 504 while the
    replica is still re-rendezvousing.  Those probes carry no breaker
    verdict — their slots must be RELEASED, so once the replica is back
    the next requests are admitted as probes and close the breaker,
    instead of allow() refusing forever."""
    b = ContinuousBatcher(max_batch=4, deadline_ms=2000.0)
    breaker = CircuitBreaker(threshold=1, reset_s=0.05, probes=2)
    door = _door(b, retries=0, breaker=breaker)
    stop = _consume(b, lambda batch, n: b.fail_retryable(
        batch, RuntimeError("replica faulted")))
    try:
        assert door.infer_detailed(1.0)["_code"] == 503   # trips (thr=1)
        stop.set()                               # replica gone: no consumer
        time.sleep(0.06)                         # window over: half-open
        # Both probe slots burn out as 504s (nobody serves the queue).
        for _ in range(2):
            out = door.infer_detailed(1.0, deadline_ms=30.0)
            assert out["_code"] == 504, out
        assert door.stats()["breaker_state"] == "half_open"
        # Healed: probes must be admitted (slots were released) and
        # close the breaker — the wedge would 503 here forever.
        stop = _consume(b, lambda batch, n: b.complete(
            batch, [r.inputs for r in batch.requests]))
        for _ in range(2):
            assert door.infer_detailed(7.0)["_code"] == 200
        assert door.stats()["breaker_state"] == "closed"
    finally:
        stop.set()
        door.stop()


def test_timed_out_request_is_cancelled_not_left_resident():
    """A 504'd request must not stay resident: a client retry under the
    same id with fresh deadline budget gets a FRESH request, not a join
    onto the doomed expired one."""
    b = ContinuousBatcher(max_batch=4, deadline_ms=2000.0)
    door = _door(b, retries=0)
    # Phase 1: nobody consumes — the request times out to 504 and is
    # cancelled out of the queue (not left resident).
    out = door.infer_detailed(1.0, deadline_ms=40.0, request_id="rid-x")
    assert out["_code"] == 504, out
    assert b.stats()["queue_depth"] == 0         # cancelled, not resident
    # Phase 2: replica serves again — the SAME id with fresh deadline
    # budget succeeds instead of joining the expired resident entry.
    stop = _consume(b, lambda batch, n: b.complete(
        batch, [r.inputs * 2 for r in batch.requests]))
    try:
        out = door.infer_detailed(4.0, deadline_ms=2000.0,
                                  request_id="rid-x")
        assert out["_code"] == 200 and out["outputs"] == 8.0, out
    finally:
        stop.set()
        door.stop()


def test_hedge_timeout_cancels_both_twins():
    """On overall hedge timeout the PRIMARY is cancelled along with the
    hedge twin, releasing the resident entry for re-submission."""
    b = ContinuousBatcher(max_batch=1, deadline_ms=2000.0, max_inflight=4)
    door = _door(b, retries=0, hedge_ms=15.0)
    out = door.infer_detailed(3.0, deadline_ms=80.0, request_id="rid-h")
    assert out["_code"] == 504, out
    s = b.stats()
    assert s["queue_depth"] == 0, s              # neither twin left queued
    assert s["cancelled_total"] == 2, s          # primary AND hedge
    door.stop()


def test_front_door_drain_503_carries_retry_after_and_stats_flag():
    b = ContinuousBatcher(max_batch=4, deadline_ms=1000.0)
    door = _door(b)
    door.drain()
    out = door.infer_detailed(1.0)
    assert out["_code"] == 503 and out.get("draining"), out
    assert out["_retry_after"] >= 1              # drain is transient
    assert door.stats()["draining"] is True
    # Drain is NOT a service error: availability untouched.
    assert door.stats()["availability"] == 1.0
    door.stop()


# ---------------------------------------------------- front door: hedging


def test_hedging_duplicates_slow_primary_and_first_response_wins():
    b = ContinuousBatcher(max_batch=1, deadline_ms=5000.0, max_inflight=4)
    door = _door(b, hedge_ms=40.0)

    def script(batch, n):
        def work():
            if n == 1:
                time.sleep(0.3)                  # the straggler primary
            b.complete(batch, [r.inputs * 2 for r in batch.requests])

        threading.Thread(target=work, daemon=True).start()

    stop = _consume(b, script)
    try:
        out = door.infer_detailed(10.0)
        assert out["_code"] == 200 and out["outputs"] == 20.0
        s = door.stats()
        assert s["hedges_total"] == 1
        assert s["hedge_wins_total"] == 1        # the twin finished first
    finally:
        stop.set()
        door.stop()


def test_hedge_delay_falls_back_to_knob_before_any_traffic():
    """Satellite: the p99 read is None on an empty histogram, so the
    delay must come from HOROVOD_SERVE_HEDGE_MS — not crash, not 0."""
    b = ContinuousBatcher(max_batch=4, deadline_ms=1000.0)
    door = _door(b, hedge_ms=50.0)
    assert b.latency_percentile(0.99) is None
    assert door._hedge_delay_s(1.0) == pytest.approx(0.05)
    # Once traffic exists, the OBSERVED p99 drives the delay.
    for _ in range(20):
        b._m_latency.observe(8.0)
    p99 = b.latency_percentile(0.99)
    assert p99 is not None
    assert door._hedge_delay_s(1.0) == pytest.approx(p99 / 1000.0)
    # And no deadline room left means no hedge at all.
    assert door._hedge_delay_s(0.001) is None
    door.stop()


# ------------------------------------------- empty-percentile consistency


def test_percentile_empty_is_none_in_local_and_merged_paths():
    """Satellite audit: every empty shape returns None through BOTH the
    local registry path and the cross-rank merged path."""
    h = Histogram("lat", buckets=LATENCY_MS_BUCKETS)
    assert h.percentile(0.5) is None
    assert h.percentile(0.99) is None
    snap = h.snapshot_value()
    assert merged_percentile([], 0.99) is None
    assert merged_percentile([None, {}], 0.99) is None
    assert merged_percentile([snap], 0.99) is None
    assert merged_percentile([snap, snap], 0.5) is None
    # Degenerate: observations but NO finite buckets — both paths still
    # agree on None (nothing to interpolate inside).
    h0 = Histogram("nobuckets", buckets=())
    h0.observe(5.0)
    assert h0.percentile(0.99) is None
    assert merged_percentile([h0.snapshot_value()], 0.99) is None
    # Non-empty stays non-None through both.
    h.observe(3.0)
    assert h.percentile(0.5) is not None
    assert merged_percentile([h.snapshot_value()], 0.5) is not None
