"""Cross-rank telemetry & health subsystem (tier-1, no jax in the core).

Covers the jax-free monitor package (registry, aggregator, agent, HTTP
exporter, CLI), the coordinator monitor side-channel end-to-end through
the real native server, the steady-state frame guard WITH monitoring
enabled (metrics frames must never ride the per-tensor metadata path),
the sanitizer content-hash mode, HVD302 peer-ledger enrichment, and the
fast-tier purity guard: ``horovod_tpu/monitor`` and ``ops/scheduler``
import with jax blocked.
"""

import json
import logging
import os
import socket
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from horovod_tpu.common.controller import TCPController
from horovod_tpu.monitor import (
    Counter, Gauge, Histogram, MetricRegistry, MonitorAgent, RankAggregator,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------- registry
def test_registry_counter_gauge_histogram():
    reg = MetricRegistry()
    c = reg.counter("hvd_things_total", "things")
    c.inc()
    c.inc(4)
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("hvd_depth")
    g.set(7)
    g.dec(2)
    h = reg.histogram("hvd_lat_us", buckets=(10.0, 100.0))
    for v in (5, 50, 500):
        h.observe(v)
    snap = reg.snapshot()
    assert snap["hvd_things_total"] == 5
    assert snap["hvd_depth"] == 5
    assert snap["hvd_lat_us"]["count"] == 3
    assert snap["hvd_lat_us"]["sum"] == 555
    assert snap["hvd_lat_us"]["buckets"] == {10.0: 1, 100.0: 2}
    # Same name returns the same handle; a kind conflict raises.
    assert reg.counter("hvd_things_total") is c
    with pytest.raises(TypeError):
        reg.gauge("hvd_things_total")


def test_registry_counter_set_total_never_regresses():
    c = MetricRegistry().counter("x")
    c.set_total(10)
    c.set_total(7)          # mirrored external totals never move backwards
    assert c.value == 10


def test_registry_prometheus_rendering():
    reg = MetricRegistry()
    reg.counter("hvd_cycles_total", "cycles run").inc(3)
    reg.gauge("weird name-with.chars").set(1.5)
    reg.histogram("hvd_lat_us", buckets=(10.0,)).observe(4)
    text = reg.to_prometheus('rank="2"')
    assert '# TYPE hvd_cycles_total counter' in text
    assert 'hvd_cycles_total{rank="2"} 3' in text
    assert 'weird_name_with_chars{rank="2"} 1.5' in text
    assert 'hvd_lat_us_bucket{rank="2",le="10"} 1' in text
    assert 'hvd_lat_us_count{rank="2"} 1' in text
    # Unlabelled rendering stays valid exposition format too.
    assert "hvd_cycles_total 3" in reg.to_prometheus()


def test_registry_collectors_run_at_snapshot_and_never_raise():
    reg = MetricRegistry()
    reg.register_collector(lambda r: r.gauge("live").set(42))

    def bad(r):
        raise RuntimeError("collector bug")
    reg.register_collector(bad)
    assert reg.snapshot()["live"] == 42


# -------------------------------------------------------------- aggregator
def test_aggregator_skew_and_health():
    agg = RankAggregator(world=3)
    agg.update(0, {"cycle_us_avg": 100.0, "cycle": 10,
                   "last_cycle_age_s": 0.1, "stalled": []})
    agg.update(1, {"cycle_us_avg": 900.0, "cycle": 10,
                   "last_cycle_age_s": 0.1, "stalled": ["grad.3"],
                   "ledger": ["#7 grad.3 [...] at train.py:12"]})
    skew = agg.skew()
    assert skew["slowest_rank"] == 1
    assert skew["cycle_us_spread"] == 800.0
    health = agg.health(interval_s=5.0)
    assert health["status"] == "stalled"          # rank 1 reports a stall
    assert health["ranks"]["1"]["stalled"] == ["grad.3"]
    assert health["ranks"]["2"]["alive"] is False  # never reported
    tails = agg.peer_ledger_tails(exclude_rank=0)
    assert 1 in tails and "grad.3" in tails[1][0]
    agg.flush()
    assert agg.ranks() == [] and agg.flushes == 1


def test_aggregator_health_ok_and_degraded():
    agg = RankAggregator(world=2)
    agg.update(0, {"stalled": []})
    assert agg.health(5.0)["status"] == "degraded"   # rank 1 missing
    agg.update(1, {"stalled": []})
    assert agg.health(5.0)["status"] == "ok"


# ---------------------------------------------------- controller side-channel
def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


class E:
    def __init__(self, name, shape=(4,)):
        self.name = name
        self.tensor = np.zeros((2,) + tuple(shape), np.float32)


class FakeEngine:
    """Duck-typed engine surface the MonitorAgent collectors read."""

    def __init__(self, cycle_us_avg=100.0):
        self.cycle_count = 10
        self.cycle_us_total = cycle_us_avg * 10
        self.last_cycle_ts = time.time()
        self._cycle_index = 10
        self.negotiation_us_total = 0.0
        self.negotiation_cycles = 0
        self.pipeline_chunks_total = 0
        self.pipeline_dispatches = 0
        self.monitor = None


def _pair(fn, cache_capacity=2048):
    port = _free_port()
    results, errors = {}, {}
    peer_done = threading.Event()

    def worker(rank):
        ctl = TCPController("127.0.0.1", port, rank=rank, world=2,
                            stall_warn_s=60.0,
                            cache_capacity=cache_capacity)
        try:
            results[rank] = fn(ctl, rank)
        except Exception as exc:  # noqa: BLE001 - surfaced by the assert
            errors[rank] = exc
        finally:
            if rank == 1:
                peer_done.set()
                ctl.shutdown()
            else:
                peer_done.wait(timeout=20)
                ctl.shutdown()

    t1 = threading.Thread(target=worker, args=(1,), daemon=True)
    t1.start()
    worker(0)
    t1.join(timeout=20)
    assert not errors, errors
    assert set(results) == {0, 1}, results
    return results


def _steps(ctl, make_entries, n_steps, max_rounds=20):
    orders = []
    for _ in range(n_steps):
        entries = list(make_entries())
        got = []
        for _round in range(max_rounds):
            if not entries:
                break
            ready, errs = ctl.negotiate(entries)
            assert not errs, errs
            got += [e.name for e in ready]
            entries = [e for e in entries if e.name not in set(got)]
        assert not entries, f"never ready: {[e.name for e in entries]}"
        orders.append(tuple(got))
    return orders


def test_monitor_frames_aggregate_across_ranks():
    """The tentpole wire path, no jax: two ranks' agents ship snapshots
    through the native coordinator; every rank's aggregation table ends up
    holding both ranks, and skew attribution names the slower one."""
    names = [f"grad.{i}" for i in range(6)]

    def fn(ctl, rank):
        eng = FakeEngine(cycle_us_avg=100.0 if rank == 0 else 900.0)
        agent = MonitorAgent(engine=eng, controller=ctl, rank=rank,
                             world=2, interval_s=0.05)
        mk = lambda: [E(n) for n in names]           # noqa: E731
        _steps(ctl, mk, 2)
        deadline = time.monotonic() + 10
        while (len(agent.aggregator.ranks()) < 2
               and time.monotonic() < deadline):
            time.sleep(0.06)
            _steps(ctl, mk, 1)
        assert agent.aggregator.ranks() == [0, 1], agent.aggregator.table()
        skew = agent.aggregator.skew()
        assert skew["slowest_rank"] == 1, skew
        assert skew["cycle_us_spread"] == 800.0, skew
        assert ctl.peer_monitor_proto
        assert ctl.monitor_bytes_sent > 0
        assert agent.frames_received >= 2
        return True

    _pair(fn)


def _serve_traffic(stop):
    """A batcher under load until ``stop``: an echo worker and one client
    (jax-free).  Its registry is what a serving rank's agent reports."""
    from horovod_tpu.serve.batcher import ContinuousBatcher
    sb = ContinuousBatcher(max_batch=4, deadline_ms=1000.0, max_inflight=2)

    def echo_worker():
        while not stop.is_set():
            batch = sb.next_batch(timeout=0.01)
            if batch is not None:
                sb.complete(batch, [np.asarray(r.inputs) * 2
                                    for r in batch.requests])

    def client():
        while not stop.is_set():
            try:
                sb.submit(np.ones(4, np.float32)).wait(1.0)
            except Exception:  # noqa: BLE001 - load generator only
                pass

    for target in (echo_worker, client):
        threading.Thread(target=target, daemon=True).start()
    return sb


@pytest.mark.parametrize("serving", [False, True])
def test_frame_guard_holds_with_monitoring_enabled(serving):
    """Acceptance guard: with a MonitorAgent attached, steady-state cycles
    still send ZERO per-tensor metadata, and the negotiation-critical
    bytes (total minus the separately-accounted monitor frames) stay the
    same fixed handful per cycle as with monitoring off.  ``serving``: the
    same with serve traffic hammering a batcher whose registry rides the
    monitor side-channel."""
    names = [f"grad.{i}.with.a.long.parameter.path" for i in range(12)]

    def fn(ctl, rank):
        stop = threading.Event()
        sb = _serve_traffic(stop) if serving else None
        agent = MonitorAgent(engine=FakeEngine(), controller=ctl, rank=rank,
                             world=2, interval_s=0.05,
                             registry=sb.registry if serving else None)
        try:
            mk = lambda: [E(n) for n in names]       # noqa: E731
            _steps(ctl, mk, 2)                       # warm-up: learn slots
            time.sleep(0.06)                         # arm the frame interval
            st = ctl.cache_stats
            full_before = st.full_announces
            bytes_before = ctl.bytes_sent
            mon_before = ctl.monitor_bytes_sent
            orders = _steps(ctl, mk, 5)
            assert st.full_announces == full_before, (
                "monitoring pushed steady-state cycles off the bitvector "
                "path")
            assert st.bit_announces >= 5 * len(names)
            mon_bytes = ctl.monitor_bytes_sent - mon_before
            assert mon_bytes > 0, "no monitor frame rode the measured window"
            per_cycle = (ctl.bytes_sent - bytes_before - mon_bytes) / 5
            assert per_cycle <= 16, per_cycle
            if serving:
                assert sb.stats()["requests_total"] > 0
        finally:
            stop.set()
            agent.close()
        return orders

    res = _pair(fn)
    assert res[0] == res[1]


def test_monitor_source_errors_never_fail_negotiation():
    def fn(ctl, rank):
        def bomb():
            raise RuntimeError("telemetry bug")
        ctl.monitor_source = bomb
        orders = _steps(ctl, lambda: [E("t")], 3)
        return orders

    res = _pair(fn)
    assert res[0] == res[1]


# ------------------------------------------------------------ HTTP exporter
def test_http_exporter_metrics_health_snapshot():
    eng = FakeEngine()
    agent = MonitorAgent(engine=eng, rank=0, world=1, interval_s=0.1)
    srv = agent.serve_http(0)           # ephemeral port
    try:
        base = f"http://127.0.0.1:{srv.port}"
        text = urllib.request.urlopen(base + "/metrics").read().decode()
        assert 'hvd_cycles_total{rank="0"} 10' in text
        assert "hvd_rank_alive" in text
        health = json.loads(urllib.request.urlopen(base + "/health").read())
        assert health["status"] == "ok" and health["world"] == 1
        assert health["ranks"]["0"]["alive"] is True
        snap = json.loads(urllib.request.urlopen(base + "/snapshot").read())
        assert "0" in snap["table"]
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + "/nope")
        assert ei.value.code == 404
    finally:
        agent.close()


def test_http_exporter_carries_idle_backoff_series():
    """The cycle thread's idle back-off (ops/engine.py): how many cycles
    did nothing, and the wait in force between cycles."""
    eng = FakeEngine()
    eng.idle_cycles, eng.idle_wait_s = 7, 0.016
    agent = MonitorAgent(engine=eng, rank=0, world=1, interval_s=0.1)
    srv = agent.serve_http(0)
    try:
        url = f"http://127.0.0.1:{srv.port}/metrics"
        text = urllib.request.urlopen(url).read().decode()
        assert "# TYPE hvd_idle_cycles_total counter" in text
        assert 'hvd_idle_cycles_total{rank="0"} 7' in text
        assert "# TYPE hvd_idle_wait_s gauge" in text
        assert 'hvd_idle_wait_s{rank="0"} 0.016' in text
        eng.idle_cycles, eng.idle_wait_s = 9, 0.001     # work came
        text = urllib.request.urlopen(url).read().decode()
        assert 'hvd_idle_cycles_total{rank="0"} 9' in text
        assert 'hvd_idle_wait_s{rank="0"} 0.001' in text
    finally:
        agent.close()


def test_http_exporter_carries_zero_rtt_counters():
    """ISSUE 11 observability: with a real controller attached, /metrics
    exports the speculation outcome counters and the in-flight round
    gauges alongside the response-cache family."""

    def fn(ctl, rank):
        _steps(ctl, lambda: [E("t")], 3)
        if rank != 0:
            return True
        agent = MonitorAgent(engine=FakeEngine(), controller=ctl,
                             rank=0, world=2, interval_s=0.1)
        srv = agent.serve_http(0)
        try:
            base = f"http://127.0.0.1:{srv.port}"
            text = urllib.request.urlopen(base + "/metrics").read().decode()
            for name in ("hvd_spec_hits_total", "hvd_spec_mispredicts_total",
                         "hvd_spec_rounds_total", "hvd_inflight_rounds",
                         "hvd_inflight_rounds_high_water",
                         "hvd_response_cache_hits_total"):
                assert name in text, name
        finally:
            agent.close()
        return True

    _pair(fn)


def test_http_health_returns_503_when_stalled():
    # The stall is on a PEER rank: the agent refreshes its own entry on
    # every /health render, so self-seeded state would be overwritten.
    agent = MonitorAgent(rank=0, world=2, interval_s=0.1)
    agent.aggregator.update(1, {"stalled": ["grad.0"]})
    srv = agent.serve_http(0)
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/health")
        assert ei.value.code == 503
        body = json.loads(ei.value.read())
        assert body["status"] == "stalled"
    finally:
        agent.close()


# --------------------------------------------------------------------- CLI
def test_cli_renders_dump(tmp_path, capsys):
    from horovod_tpu.monitor.__main__ import main
    dump = {
        "rank": 0, "world": 2,
        "health": {"status": "stalled", "world": 2,
                   "monitor_interval_s": 5.0, "slowest_rank": 1,
                   "cycle_us_spread": 800.0,
                   "ranks": {"0": {"alive": True, "last_seen_s": 0.2,
                                   "cycle": 12, "last_cycle_age_s": 0.1,
                                   "stalled": ["grad.0"]},
                             "1": {"alive": False, "last_seen_s": None,
                                   "cycle": None, "last_cycle_age_s": None,
                                   "stalled": []}}},
        "table": {"1": {"ledger": ["#7 grad.0 [...] at train.py:12"],
                        "metrics": {"hvd_stalled_collectives": 0}}},
    }
    path = tmp_path / "snap.json"
    path.write_text(json.dumps(dump))
    assert main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "fleet status: STALLED" in out
    assert "slowest rank 1" in out
    assert "grad.0" in out and "train.py:12" in out
    # Raw mode round-trips the JSON.
    assert main([str(path), "--json"]) == 0
    assert json.loads(capsys.readouterr().out) == dump


def test_cli_rejects_bad_usage(tmp_path):
    from horovod_tpu.monitor.__main__ import main
    with pytest.raises(SystemExit):
        main([])                        # neither file nor --url
    assert main([str(tmp_path / "missing.json")]) == 1


# ---------------------------------------------------- sanitizer hash mode
def test_sanitizer_content_hash_tags():
    from horovod_tpu.analysis.runtime_sanitizer import CollectiveSanitizer

    class Entry:
        def __init__(self, name, value):
            self.name = name
            self.tensor = np.full((4,), value, np.float32)
            self.process_set_id = 0

    a0 = Entry("t", 1.0)
    a1 = Entry("t", 1.0)
    b = Entry("t", 2.0)
    san = CollectiveSanitizer(content_hash=True)
    san.observe([a0], site="train.py:10")
    san.observe([a1], site="train.py:10")
    san.observe([b], site="train.py:10")
    h0 = a0.sanitizer_tag.split(";h=")[1]
    h1 = a1.sanitizer_tag.split(";h=")[1]
    hb = b.sanitizer_tag.split(";h=")[1]
    assert h0 == h1, "identical content must hash identically"
    assert h0 != hb, "divergent content must hash differently"
    # Barriers (no tensor) carry no hash field but still tag seq/site.
    class Barrier:
        name = "b"
        tensor = None
        process_set_id = 0
    bar = Barrier()
    san.observe([bar], site="train.py:11")
    assert ";h=" not in bar.sanitizer_tag
    assert bar.sanitizer_tag.startswith("seq=0:3")


def test_sanitizer_hash_mode_rollback_still_works():
    from horovod_tpu.analysis.runtime_sanitizer import CollectiveSanitizer

    class Entry:
        def __init__(self, name):
            self.name = name
            self.tensor = np.ones((2,), np.float32)
            self.process_set_id = 0

    san = CollectiveSanitizer(content_hash=True)
    e = Entry("dup")
    san.observe([e], site="train.py:10")
    assert san._seq[0] == 1
    san.rollback([e])
    assert san._seq[0] == 0 and len(san.ledger) == 0


def test_mode_parsing(monkeypatch):
    from horovod_tpu.analysis import runtime_sanitizer as rts
    monkeypatch.delenv("HVD_TPU_SANITIZER", raising=False)
    assert rts.mode() is None and not rts.enabled()
    monkeypatch.setenv("HVD_TPU_SANITIZER", "1")
    assert rts.mode() == "tag" and rts.enabled()
    monkeypatch.setenv("HVD_TPU_SANITIZER", "hash")
    assert rts.mode() == "hash" and rts.enabled()
    monkeypatch.setenv("HVD_TPU_SANITIZER", "0")
    assert rts.mode() is None


# ------------------------------------------------- HVD302 peer-ledger path
def test_hvd302_report_includes_peer_ledger_tail():
    from horovod_tpu.analysis.runtime_sanitizer import (
        CollectiveSanitizer, SanitizerStallInspector)
    from horovod_tpu.ops.scheduler import StallInspector
    from horovod_tpu.utils.logging import get_logger

    inner = StallInspector(warn_after_s=0.01, shutdown_after_s=0)
    san = CollectiveSanitizer()
    insp = SanitizerStallInspector(inner, san, warn_after_s=0.01)
    agent = MonitorAgent(rank=0, world=2, interval_s=0.1)
    agent.aggregator.update(
        1, {"ledger": ["#41 grad.7 [allreduce|float32|(4,)|SUM] "
                       "at laggard.py:99"]})
    insp.peer_ledger_source = agent.peer_ledger_report

    class W:
        name = "stuck.t"
        enqueue_time = time.monotonic() - 1.0
        sanitizer_tag = "seq=0:5;site=train.py:30"

    records = []
    handler = logging.Handler()
    handler.emit = lambda rec: records.append(rec.getMessage())
    log = get_logger()
    log.addHandler(handler)
    try:
        insp.check([W()])
    finally:
        log.removeHandler(handler)
    msgs = [m for m in records if "HVD302" in m]
    assert msgs, records
    assert "peer ledgers" in msgs[0], msgs[0]
    assert "rank 1 last submissions" in msgs[0]
    assert "laggard.py:99" in msgs[0]
    # Live stall state (the /health export) reflects and then clears.
    assert "stuck.t" in insp.stalled
    insp.progressed("stuck.t")
    assert "stuck.t" not in insp.stalled


# ------------------------------------------------------------ purity guard
_PURITY_SRC = r"""
import importlib, os, sys, types

class BlockJax:
    def find_spec(self, name, path=None, target=None):
        if name.split('.')[0] in ('jax', 'jaxlib'):
            raise ImportError('tier-1 purity: %s must not import jax'
                              % name)
        return None

sys.meta_path.insert(0, BlockJax())
root = sys.argv[1]
# Shell parent packages: real submodules load from disk, but the real
# horovod_tpu/__init__.py (which imports jax) never runs.
for name, sub in (('horovod_tpu', ''), ('horovod_tpu.ops', 'ops'),
                  ('horovod_tpu.utils', 'utils'),
                  ('horovod_tpu.common', 'common'),
                  ('horovod_tpu.analysis', 'analysis'),
                  ('horovod_tpu.parallel', 'parallel')):
    m = types.ModuleType(name)
    m.__path__ = [os.path.join(root, sub)] if sub else [root]
    sys.modules[name] = m
importlib.import_module('horovod_tpu.ops.scheduler')
importlib.import_module('horovod_tpu.monitor')
importlib.import_module('horovod_tpu.monitor.__main__')
importlib.import_module('horovod_tpu.monitor.http')
importlib.import_module('horovod_tpu.analysis.findings')
# Slice topology (ISSUE 17): derives the two-level (cross, local) mesh
# structure for the engine but is itself pure Python — the analyzer and
# bench model wire bytes with it from the jax-free tier.
topo = importlib.import_module('horovod_tpu.parallel.topology')
st = topo.slice_topology(None, world=8, slice_map='4')
assert st.num_slices == 2 and st.leaders == (0, 4), st
assert topo.hier_bit_orders(4, 2) == ([0, 1], [0])
legs = topo.modeled_leg_bytes(1 << 20, 8, 4)
assert legs['cross'] <= legs['flat'] / 4, legs
# Per-process-set sanitizer namespace (ISSUE 16): the ledger recorder
# must import AND keep per-set books correctly with jax hard-blocked —
# it runs in launcher-adjacent tooling and the jax-free test tier.
rs = importlib.import_module('horovod_tpu.analysis.runtime_sanitizer')
san = rs.CollectiveSanitizer(capacity=4)
class _E:
    def __init__(self, name, ps):
        self.name = name
        self.tensor = None
        self.process_set_id = ps
a, b, c = _E('w', 0), _E('t', 7), _E('w2', 0)
san.observe([a], site='x.py:1')
san.observe([b], site='x.py:2')
san.observe([c], site='x.py:3')
assert a.sanitizer_tag.startswith('seq=0:0;'), a.sanitizer_tag
assert b.sanitizer_tag.startswith('seq=7:0;'), b.sanitizer_tag
assert c.sanitizer_tag.startswith('seq=0:1;'), c.sanitizer_tag
assert [e.name for e in san.tail(process_set=7)] == ['t']
assert [e.name for e in san.tail()] == ['w', 't', 'w2']
assert 'process set 7' in san.render_tail(process_set=7)
# Distributed tracing: the span core, the merge/analyze halves and the CLI
# must run standalone (operators merge traces on machines without jax).
importlib.import_module('horovod_tpu.trace')
importlib.import_module('horovod_tpu.trace.merge')
importlib.import_module('horovod_tpu.trace.analyze')
importlib.import_module('horovod_tpu.trace.__main__')
# Control-plane fault tolerance: the harness and the typed error taxonomy
# carry the jax-free fault tests and the acceptance workers' arming path.
importlib.import_module('horovod_tpu.testing')
importlib.import_module('horovod_tpu.testing.faults')
# Churn-scenario runner (ISSUE 12): drives simulated worlds + HostAgents
# against the native server from the jax-free test tier and the bench.
importlib.import_module('horovod_tpu.testing.churn')
importlib.import_module('horovod_tpu.common.exceptions')
importlib.import_module('horovod_tpu.common.net')
# Hierarchical control plane: the per-host aggregation agent runs in
# launcher-adjacent processes and the jax-free negotiation test tier.
importlib.import_module('horovod_tpu.common.host_agent')
# Closed-loop autoscaling: the REAL elastic package surface (state objects
# load lazily via PEP 562), the policy engine, the elastic driver (which
# hosts it) and the worker notification layer all run in the LAUNCHER
# process and the synthetic-load acceptance workers — none may drag jax
# in.  NB: horovod_tpu.elastic is imported for real, not shelled — the
# lazy __init__ IS the thing under test.
importlib.import_module('horovod_tpu.elastic')
importlib.import_module('horovod_tpu.elastic.autoscale')
importlib.import_module('horovod_tpu.elastic.driver')
importlib.import_module('horovod_tpu.elastic.worker')
importlib.import_module('horovod_tpu.elastic.rendezvous')
# Resilient state plane (ISSUE 14): sharded checkpoint writes + the
# peer-to-peer restore path run in the jax-free acceptance workers, the
# churn runner and the bench — and the chunk items it hands the engine
# come from the (already covered) jax-free ops/scheduler.
importlib.import_module('horovod_tpu.elastic.stateplane')
# Serving plane (ISSUE 19): the REAL serve package surface (the Replica
# loads lazily via PEP 562 — the lazy __init__ IS the thing under test),
# plus a behavioral pass through the continuous batcher: admission,
# padded-bucket formation, deadline expiry, backpressure.
serve = importlib.import_module('horovod_tpu.serve')
importlib.import_module('horovod_tpu.serve.batcher')
importlib.import_module('horovod_tpu.serve.frontdoor')
clock = [0.0]
bt = serve.ContinuousBatcher(max_batch=4, deadline_ms=100.0,
                             max_inflight=1, queue_depth=3,
                             clock=lambda: clock[0])
r1 = bt.submit([1]); r2 = bt.submit([2]); r3 = bt.submit([3])
try:
    bt.submit([4])
    raise AssertionError('queue_depth=3 admitted a 4th request')
except serve.QueueFull:
    pass
batch = bt.next_batch(timeout=0.0)
assert batch.size == 3 and batch.bucket == 4, (batch.size, batch.bucket)
assert bt.next_batch(timeout=0.0) is None      # in-flight window full
bt.complete(batch, [[10], [20], [30]])
assert r1.wait(0.0) == [10] and r3.wait(0.0) == [30]
r4 = bt.submit([5])
clock[0] = 1.0                                  # past the 100ms deadline
assert bt.next_batch(timeout=0.0) is None
try:
    r4.wait(0.0)
    raise AssertionError('expired request returned a result')
except serve.DeadlineExceeded:
    pass
assert serve.parse_buckets('2,4', 8) == (2, 4, 8)
# Serving fault tolerance (ISSUE 20): the resilience module and the
# retry/breaker/quarantine surface are all front-door-side — jax-free by
# construction — and the behavioral pass walks the breaker state machine
# plus the retryable/terminal error taxonomy.
importlib.import_module('horovod_tpu.serve.resilience')
br = serve.CircuitBreaker(threshold=2, reset_s=5.0, probes=1,
                          clock=lambda: clock[0])
assert br.allow() and br.state == 'closed'
br.record_failure(); br.record_failure()
assert br.state == 'open' and not br.allow()
clock[0] += 5.0
assert br.allow() and br.state == 'half_open'
br.record_success()
assert br.state == 'closed'
assert issubclass(serve.ReplicaFaulted, serve.Retryable)
assert issubclass(serve.ForwardFailed, serve.Retryable)
assert not issubclass(serve.RequestQuarantined, serve.Retryable)
bq = serve.ContinuousBatcher(max_batch=1, deadline_ms=1000.0,
                             quarantine_after=2, clock=lambda: clock[0])
assert bq.submit([1], request_id='a') is bq.submit([1], request_id='a')
bq.fail(bq.next_batch(timeout=0.0), RuntimeError('x'))
bq.submit([1], request_id='a')
bq.fail(bq.next_batch(timeout=0.0), RuntimeError('x'))
assert bq.stats()['quarantined_total'] == 1
print('PURITY_OK')
"""


def test_monitor_and_scheduler_import_without_jax():
    """Fast-tier purity: the monitor package, ops/scheduler.py, the trace
    package, the fault-injection harness (horovod_tpu/testing) and the
    control-plane exception taxonomy must be importable with jax imports
    hard-blocked — they carry the jax-free unit-test tier and the
    standalone CLIs."""
    res = subprocess.run(
        [sys.executable, "-c", _PURITY_SRC,
         os.path.join(REPO, "horovod_tpu")],
        capture_output=True, text=True, timeout=120)
    assert res.returncode == 0 and "PURITY_OK" in res.stdout, (
        f"rc={res.returncode}\nstdout:\n{res.stdout}\n"
        f"stderr:\n{res.stderr}")
