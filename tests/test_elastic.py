"""Elastic driver tests.

Unit tier mirrors the reference's ``test/single/test_elastic_driver.py``
pattern (fake discovery from temp files, assert on rank assignment /
blacklist / rendezvous logic with no real training); the integration tier
(``test_elastic_integration``) runs a REAL elastic job on localhost whose
discovery output mutates mid-run, like ``test/integration/
test_elastic_torch.py`` (SURVEY.md §4).
"""

import json
import os
import stat
import subprocess
import sys
import tempfile
import time

import pytest

from horovod_tpu.elastic.discovery import (
    DiscoveredHost, FixedHostDiscovery, HostDiscoveryScript)
from horovod_tpu.elastic.driver import ElasticDriver
from horovod_tpu.elastic.registration import WorkerStateRegistry
from horovod_tpu.elastic.rendezvous import (
    RendezvousServer, fetch_assignment, register_notification_port)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------------ discovery
def test_discovery_parse():
    d = HostDiscoveryScript("true", default_slots=2)
    hosts = d.parse("a:4\nb\n# comment\n\nc:1 # tail\na:9\n")
    assert hosts == [DiscoveredHost("a", 4), DiscoveredHost("b", 2),
                     DiscoveredHost("c", 1)]


def test_discovery_script_execution(tmp_path):
    hostfile = tmp_path / "hosts.txt"
    hostfile.write_text("localhost:2\nnode1:4\n")
    script = tmp_path / "discover.sh"
    script.write_text(f"#!/bin/sh\ncat {hostfile}\n")
    script.chmod(script.stat().st_mode | stat.S_IEXEC)
    d = HostDiscoveryScript(str(script))
    assert d.find_available_hosts_and_slots() == [
        DiscoveredHost("localhost", 2), DiscoveredHost("node1", 4)]
    # Mutating the file changes the next poll (the elastic contract).
    hostfile.write_text("localhost:2\n")
    assert d.find_available_hosts_and_slots() == [
        DiscoveredHost("localhost", 2)]


def test_discovery_script_failure():
    d = HostDiscoveryScript("exit 3")
    with pytest.raises(RuntimeError):
        d.find_available_hosts_and_slots()


# ----------------------------------------------------------------- registry
def test_registry_blacklist():
    r = WorkerStateRegistry()
    r.record_ready("a:0")
    r.record_failure("a:0")
    assert r.is_blacklisted("a")
    assert not r.is_blacklisted("b")
    assert r.failure_count("a") == 1
    r.record_success("b:0")
    assert r.success_count() == 1


# -------------------------------------------------------------- assignments
def _driver(min_np=1, max_np=None):
    return ElasticDriver(FixedHostDiscovery([]), ["true"], min_np=min_np,
                         max_np=max_np)


def test_compute_assignments_order_and_shape():
    d = _driver(min_np=2)
    try:
        a = d.compute_assignments([DiscoveredHost("h0", 2),
                                   DiscoveredHost("h1", 1)])
        assert set(a) == {"h0:0", "h0:1", "h1:0"}
        assert a["h0:0"]["rank"] == 0
        assert a["h0:1"]["rank"] == 1
        assert a["h1:0"]["rank"] == 2
        assert all(v["size"] == 3 for v in a.values())
        assert a["h1:0"]["cross_rank"] == 1
        assert a["h0:1"]["local_size"] == 2
        assert all(v["controller_addr"] == "h0" for v in a.values())
    finally:
        d.rendezvous.stop()


def test_compute_assignments_max_np_cap_and_min_np():
    d = _driver(min_np=2, max_np=2)
    try:
        a = d.compute_assignments([DiscoveredHost("h0", 4)])
        assert set(a) == {"h0:0", "h0:1"}
        assert all(v["size"] == 2 for v in a.values())
        assert d.compute_assignments([DiscoveredHost("h0", 1)]) == {}
    finally:
        d.rendezvous.stop()


def test_blacklisted_host_excluded():
    d = _driver(min_np=1)
    try:
        d.registry.record_failure("bad:0")
        hosts = d.active_hosts([DiscoveredHost("bad", 2),
                                DiscoveredHost("good", 1)])
        assert hosts == [DiscoveredHost("good", 1)]
    finally:
        d.rendezvous.stop()


# --------------------------------------------------------------- rendezvous
def test_rendezvous_publish_fetch_versioning():
    s = RendezvousServer()
    try:
        v1 = s.publish({"h:0": {"rank": 0, "size": 1}})
        assert v1 == 1
        a = fetch_assignment("127.0.0.1", s.port, "h:0", timeout_s=5)
        assert a["rank"] == 0 and a["version"] == 1
        # min_version gating: nothing at version 2 yet.
        with pytest.raises(TimeoutError):
            fetch_assignment("127.0.0.1", s.port, "h:0", min_version=2,
                             timeout_s=1.0)
        v2 = s.publish({"h:0": {"rank": 0, "size": 2}})
        a = fetch_assignment("127.0.0.1", s.port, "h:0", min_version=2,
                             timeout_s=5)
        assert a["size"] == 2 and a["version"] == v2
        # Unknown identity stays pending.
        with pytest.raises(TimeoutError):
            fetch_assignment("127.0.0.1", s.port, "nope:0", timeout_s=1.0)
        register_notification_port("127.0.0.1", s.port, "h:0", 12345)
        assert s.notification_ports() == {"h:0": 12345}
    finally:
        s.stop()


def test_rendezvous_rollback_to_surviving_host_set():
    """The PeerFailureError recovery path's rendezvous half: a worker that
    reset after a dead peer long-polls for a STRICTLY newer generation and
    lands in the shrunk world — never re-joins the stale one, and a dead
    identity gets nothing from the new table."""
    s = RendezvousServer()
    try:
        s.publish({"a:0": {"rank": 0, "size": 2},
                   "b:0": {"rank": 1, "size": 2}})
        a = fetch_assignment("127.0.0.1", s.port, "a:0", timeout_s=5)
        assert a["size"] == 2 and a["version"] == 1
        # b:0 died; the driver republished over the survivors only.
        v2 = s.publish({"a:0": {"rank": 0, "size": 1}})
        a = fetch_assignment("127.0.0.1", s.port, "a:0",
                             min_version=a["version"] + 1, timeout_s=5)
        assert a["size"] == 1 and a["rank"] == 0 and a["version"] == v2
        # The dead identity is gone from the new generation.
        with pytest.raises(TimeoutError):
            fetch_assignment("127.0.0.1", s.port, "b:0", min_version=v2,
                             timeout_s=1.0)
    finally:
        s.stop()


# -------------------------------------------- state restore/rollback paths
def _identity_bcast(obj, root_rank=0):
    return obj


def test_object_state_restore_after_peer_failure_byte_identical():
    """State.restore() after a simulated PeerFailureError must roll every
    registered attribute back to the last commit, byte-identically — the
    half of elastic recovery that runs before re-rendezvous."""
    import pickle

    from horovod_tpu.common.exceptions import PeerFailureError
    from horovod_tpu.elastic.state import ObjectState

    state = ObjectState(bcast_object=_identity_bcast,
                        epoch=3, batch=7,
                        table={"w": [1.0, 2.0], "meta": {"k": (1, 2)}})
    state.commit()
    committed = pickle.dumps((state.epoch, state.batch, state.table))
    # Mutate mid-epoch (including a nested structure), then fail.
    state.epoch = 4
    state.batch = 0
    state.table["w"].append(3.0)
    state.table["meta"]["k"] = (9,)
    try:
        raise PeerFailureError("HVD303 peer died", dead_ranks=[1])
    except PeerFailureError:
        state.restore()
    assert pickle.dumps((state.epoch, state.batch, state.table)) == committed
    # Restore hands back COPIES: mutating post-restore state must not
    # corrupt the saved snapshot a second restore depends on.
    state.table["w"].append(99.0)
    state.restore()
    assert pickle.dumps((state.epoch, state.batch, state.table)) == committed


def test_jax_state_restore_after_peer_failure_byte_identical():
    """JaxState: pytree leaves committed to host memory restore to device
    byte-identically after a control-plane fault."""
    import numpy as np

    from horovod_tpu.common.exceptions import PeerFailureError
    from horovod_tpu.elastic.state import JaxState

    params = {"w": np.arange(8, dtype=np.float32).reshape(2, 4),
              "b": np.float32(0.5)}
    state = JaxState(bcast_object=_identity_bcast, params=params, step=11)
    state.commit()
    committed = {k: np.asarray(v).tobytes()
                 for k, v in state.params.items()}
    state.params = {"w": state.params["w"] * 2.0,
                    "b": state.params["b"] + 1.0}
    state.step = 12
    try:
        raise PeerFailureError("HVD303 peer died", dead_ranks=[0])
    except PeerFailureError:
        state.restore()
    assert state.step == 11
    for k, blob in committed.items():
        assert np.asarray(state.params[k]).tobytes() == blob, k


def test_state_should_commit_consumes_driver_commit_request():
    """Checkpoint pacing (ISSUE 12): ``state.should_commit()`` reads the
    notification manager's one-shot COMMIT flag — True exactly once per
    driver ping, False with no manager attached (non-elastic runs)."""
    from horovod_tpu.elastic.state import ObjectState

    state = ObjectState(bcast_object=_identity_bcast, epoch=0)
    assert state.should_commit() is False      # no manager attached

    class _Mgr:
        def __init__(self):
            self.pending = True

        def consume_commit_request(self):
            p, self.pending = self.pending, False
            return p

    state._notification_manager = _Mgr()
    assert state.should_commit() is True
    assert state.should_commit() is False      # one-shot


def test_run_wrapper_resets_on_peer_failure(monkeypatch):
    """@hvd.elastic.run over a step that hits a PeerFailureError once:
    restore-to-commit, runtime reset, retry — and completion on the second
    attempt (the re-rendezvous itself is covered by the integration
    tier)."""
    from horovod_tpu.common import basics
    from horovod_tpu.common.exceptions import PeerFailureError
    from horovod_tpu.elastic.state import ObjectState, run

    resets = []
    monkeypatch.setattr(basics, "shutdown", lambda: resets.append("down"))
    monkeypatch.setattr(basics, "init", lambda: resets.append("up"))

    attempts = []

    @run
    def train(state):
        attempts.append(state.epoch)
        if len(attempts) == 1:
            state.epoch = 99          # uncommitted progress, must roll back
            raise PeerFailureError("HVD303 peer died", dead_ranks=[1])
        return state.epoch

    state = ObjectState(bcast_object=_identity_bcast, epoch=5)
    state.commit()
    assert train(state) == 5
    assert attempts == [5, 5], "restore did not roll back to the commit"
    assert resets == ["down", "up"], "runtime was not reset between tries"


def test_run_wrapper_peer_restore_only_when_stale(monkeypatch):
    """Review fix: the wrapper's peer-first restore runs only while this
    rank's live state is STALE — a fresh process, or right after a fault
    rolled it back to its last commit.  A survivor re-entering on a clean
    HostsUpdatedInterrupt holds the fleet's current state (its plane
    epoch may lag a peer's on skewed commit cadence), and pulling that
    peer's older commit would roll live training backwards fleet-wide."""
    from horovod_tpu.common import basics
    from horovod_tpu.common.exceptions import (
        HostsUpdatedInterrupt, PeerFailureError,
    )
    from horovod_tpu.elastic import stateplane as spl
    from horovod_tpu.elastic.state import ObjectState, run

    monkeypatch.setattr(basics, "shutdown", lambda: None)
    monkeypatch.setattr(basics, "init", lambda: None)
    plane = object()
    monkeypatch.setattr(spl, "attach", lambda state, p=None: plane)
    restores = []
    attempts = []
    monkeypatch.setattr(spl, "maybe_restore",
                        lambda state, p: restores.append(len(attempts)))

    @run
    def train(state):
        attempts.append(1)
        if len(attempts) == 1:
            raise HostsUpdatedInterrupt(skip_sync=False)   # clean change
        if len(attempts) == 2:
            raise PeerFailureError("HVD303 peer died", dead_ranks=[1])
        return "done"

    state = ObjectState(bcast_object=_identity_bcast, epoch=5)
    state.commit()
    assert train(state) == "done"
    # Restored on the fresh entry (before attempt 1) and after the fault
    # rollback (before attempt 3) — NOT on the clean re-entry (a restore
    # before attempt 2 would record a 1 here).
    assert restores == [0, 2], restores


# ------------------------------------------------- driver process lifecycle
@pytest.mark.slow
def test_driver_success_on_worker_exit_zero():
    d = ElasticDriver(
        FixedHostDiscovery([DiscoveredHost("localhost", 2)]),
        [sys.executable, "-c", "pass"], min_np=2, start_timeout_s=30)
    assert d.run() == 0
    assert d.registry.success_count() >= 1


@pytest.mark.slow
def test_driver_gives_up_below_min_np():
    d = ElasticDriver(FixedHostDiscovery([DiscoveredHost("localhost", 1)]),
                      [sys.executable, "-c", "pass"], min_np=4,
                      start_timeout_s=2, discovery_interval_s=0.2)
    assert d.run() == 1


@pytest.mark.slow
def test_driver_failure_blacklists_and_aborts():
    # Workers always fail; localhost gets blacklisted; below min_np -> abort
    # with the worker's rc.
    d = ElasticDriver(
        FixedHostDiscovery([DiscoveredHost("localhost", 2)]),
        [sys.executable, "-c", "import sys; sys.exit(7)"],
        min_np=2, start_timeout_s=30)
    rc = d.run()
    assert rc == 7
    assert d.registry.is_blacklisted("localhost")


# ------------------------------------------------------------- integration
WORKER = os.path.join(REPO, "tests", "data", "worker_elastic.py")


@pytest.mark.slow
@pytest.mark.parametrize("mode", ["grow", "shrink"])
def test_elastic_integration(tmp_path, mode):
    """Real elastic run on localhost: discovery output mutates mid-run."""
    hostfile = tmp_path / "hosts.txt"
    start, end = (("localhost:1", "localhost:2") if mode == "grow"
                  else ("localhost:2", "localhost:1"))
    hostfile.write_text(start + "\n")
    marker = tmp_path / "epoch_marker"
    result = tmp_path / "result"

    env = dict(os.environ)
    other_paths = [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                   if p]
    env["PYTHONPATH"] = os.pathsep.join([REPO] + other_paths)
    env["JAX_PLATFORMS"] = "cpu"
    env["ELASTIC_TEST_MARKER"] = str(marker)
    env["ELASTIC_TEST_RESULT"] = str(result)
    env["ELASTIC_TEST_EPOCHS"] = "6"
    env.pop("HOROVOD_TIMELINE", None)

    cmd = [sys.executable, "-m", "horovod_tpu.runner.launch",
           "--host-discovery-script", f"cat {hostfile}",
           "--min-np", "1", "--max-np", "2",
           sys.executable, WORKER]
    proc = subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)
    try:
        # Wait for a worker to pass epoch 2, then mutate the host set.
        deadline = time.time() + 120
        while not marker.exists() and time.time() < deadline:
            time.sleep(0.2)
        assert marker.exists(), "worker never reached the marker epoch"
        hostfile.write_text(end + "\n")
        out, _ = proc.communicate(timeout=180)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert proc.returncode == 0, out[-4000:]
    assert result.exists(), out[-4000:]
    res = json.loads(result.read_text())
    assert res["epochs"] == 6
    final_size = 2 if mode == "grow" else 1
    assert res["final_size"] == final_size, (res, out[-4000:])
    assert res["resets"] >= 1, (res, out[-4000:])


@pytest.mark.slow
@pytest.mark.parametrize("mode", ["grow", "shrink"])
def test_elastic_integration_hierarchical(tmp_path, mode):
    """ISSUE 12 — elastic × hierarchical, real jax workers: the SAME
    grow/shrink run with ``--hierarchical-controller`` armed.
    ``run_elastic`` honors the knob (no silent flat fallback): the driver
    allocates a stable per-host agent port, every generation's rendezvous
    assignment carries it, and the surviving local_rank-0 process's
    HostAgent serves BOTH generations via new_generation while the rank
    set changes under it."""
    hostfile = tmp_path / "hosts.txt"
    start, end = (("localhost:1", "localhost:2") if mode == "grow"
                  else ("localhost:2", "localhost:1"))
    hostfile.write_text(start + "\n")
    marker = tmp_path / "epoch_marker"
    result = tmp_path / "result"

    env = dict(os.environ)
    other_paths = [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                   if p]
    env["PYTHONPATH"] = os.pathsep.join([REPO] + other_paths)
    env["JAX_PLATFORMS"] = "cpu"
    env["ELASTIC_TEST_MARKER"] = str(marker)
    env["ELASTIC_TEST_RESULT"] = str(result)
    env["ELASTIC_TEST_EPOCHS"] = "6"
    env.pop("HOROVOD_TIMELINE", None)

    logs = tmp_path / "logs"
    cmd = [sys.executable, "-m", "horovod_tpu.runner.launch",
           "--host-discovery-script", f"cat {hostfile}",
           "--min-np", "1", "--max-np", "2",
           "--hierarchical-controller",
           "--output-filename", str(logs),
           sys.executable, WORKER]
    proc = subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)
    try:
        deadline = time.time() + 120
        while not marker.exists() and time.time() < deadline:
            time.sleep(0.2)
        assert marker.exists(), "worker never reached the marker epoch"
        hostfile.write_text(end + "\n")
        out, _ = proc.communicate(timeout=180)
    finally:
        if proc.poll() is None:
            proc.kill()
    def _logs():
        return "\n\n".join(f"--- {p} ---\n" + p.read_text()[-2500:]
                           for p in sorted(logs.glob("*/std*"))
                           if p.exists())

    assert proc.returncode == 0, out[-3000:] + _logs()
    assert result.exists(), out[-3000:] + _logs()
    res = json.loads(result.read_text())
    assert res["epochs"] == 6
    final_size = 2 if mode == "grow" else 1
    assert res["final_size"] == final_size, (res, out[-4000:])
    assert res["resets"] >= 1, (res, out[-4000:])


# ------------------------------------------------- TPU metadata discovery
class _FakeMetadataServer:
    """Minimal GCE-metadata-shaped HTTP server whose attribute map the test
    mutates mid-run (VERDICT r2 #6: fake HTTP server drops a host)."""

    def __init__(self):
        import http.server
        import threading
        server = self

        class Handler(http.server.BaseHTTPRequestHandler):
            def do_GET(self):
                if self.headers.get("Metadata-Flavor") != "Google":
                    self.send_response(403)
                    self.end_headers()
                    return
                key = self.path.lstrip("/")
                if key in server.attributes:
                    body = server.attributes[key].encode()
                    self.send_response(200)
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                else:
                    self.send_response(404)
                    self.end_headers()

            def log_message(self, *a):  # quiet
                pass

        self.attributes = {}
        self._httpd = http.server.ThreadingHTTPServer(("127.0.0.1", 0),
                                                      Handler)
        self.url = f"http://127.0.0.1:{self._httpd.server_port}"
        self._thread = threading.Thread(target=self._httpd.serve_forever,
                                        daemon=True)
        self._thread.start()

    def stop(self):
        self._httpd.shutdown()


def test_tpu_metadata_discovery_membership_and_preemption():
    from horovod_tpu.elastic.discovery import TPUMetadataDiscovery
    srv = _FakeMetadataServer()
    try:
        srv.attributes["instance/attributes/worker-network-endpoints"] = (
            "uid0:8470:10.0.0.1, uid1:8470:10.0.0.2,10.0.0.3")
        d = TPUMetadataDiscovery(base_url=srv.url, slots_per_host=4)
        assert d.find_available_hosts_and_slots() == [
            DiscoveredHost("10.0.0.1", 4), DiscoveredHost("10.0.0.2", 4),
            DiscoveredHost("10.0.0.3", 4)]   # record formats + 404 notices

        # A preemption notice KEEPS the worker in the membership (the
        # hardware is still up) and surfaces it through
        # preemption_notices() instead — the driver's cue to DRAIN it
        # proactively (ISSUE 12) rather than dropping it into a crash.
        srv.attributes["instance/attributes/preempted-workers"] = "10.0.0.2"
        assert d.find_available_hosts_and_slots() == [
            DiscoveredHost("10.0.0.1", 4), DiscoveredHost("10.0.0.2", 4),
            DiscoveredHost("10.0.0.3", 4)]
        assert d.preemption_notices() == {"10.0.0.2"}

        # Membership change (a worker vanishes from the slice): a notice
        # for a host no longer in the membership clears with it.
        srv.attributes["instance/attributes/worker-network-endpoints"] = (
            "uid0:8470:10.0.0.1")
        assert d.find_available_hosts_and_slots() == [
            DiscoveredHost("10.0.0.1", 4)]
        assert d.preemption_notices() == set()
    finally:
        srv.stop()


def test_tpu_metadata_discovery_missing_endpoint_raises():
    from horovod_tpu.elastic.discovery import TPUMetadataDiscovery
    srv = _FakeMetadataServer()
    try:
        d = TPUMetadataDiscovery(base_url=srv.url)
        with pytest.raises(Exception):
            d.find_available_hosts_and_slots()   # membership must exist
    finally:
        srv.stop()


@pytest.mark.slow
def test_elastic_integration_tpu_metadata_preemption(tmp_path):
    """Full elastic run driven by the metadata source: the fake server
    posts a preemption notice for one worker mid-run and training resumes
    at reduced world — the metadata twin of test_elastic_integration."""
    from horovod_tpu.elastic.discovery import TPUMetadataDiscovery

    srv = _FakeMetadataServer()
    srv.attributes["instance/attributes/worker-network-endpoints"] = (
        "localhost,127.0.0.1")
    marker = tmp_path / "epoch_marker"
    result = tmp_path / "result"

    other_paths = [p for p in os.environ.get("PYTHONPATH",
                                             "").split(os.pathsep)
                   if p]
    env = {"ELASTIC_TEST_MARKER": str(marker),
           "ELASTIC_TEST_RESULT": str(result),
           "ELASTIC_TEST_EPOCHS": "6",
           "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.pathsep.join([REPO] + other_paths)}
    d = ElasticDriver(
        TPUMetadataDiscovery(base_url=srv.url, slots_per_host=1),
        [sys.executable, WORKER], min_np=1, max_np=2, env=env,
        discovery_interval_s=0.2, start_timeout_s=60)

    import threading
    rc = {}
    t = threading.Thread(target=lambda: rc.setdefault("rc", d.run()),
                         daemon=True)
    t.start()
    try:
        deadline = time.time() + 120
        while not marker.exists() and time.time() < deadline:
            time.sleep(0.2)
        assert marker.exists(), "worker never reached the marker epoch"
        # Preemption notice for the second worker.
        srv.attributes["instance/attributes/preempted-workers"] = (
            "127.0.0.1")
        t.join(timeout=180)
        assert not t.is_alive(), "elastic driver did not finish"
    finally:
        srv.stop()
        if t.is_alive():
            d._shutdown_workers()
    assert rc.get("rc") == 0, rc
    res = json.loads(result.read_text())
    assert res["epochs"] == 6
    assert res["final_size"] == 1, res
    assert res["resets"] >= 1, res


def test_discovery_parse_malformed_line_skipped():
    """ADVICE: a garbled slots field degrades to a warning, not a crash."""
    d = HostDiscoveryScript("true")
    hosts = d.parse("hostA:4\nhostB:oops\nhostC\n")
    assert [(h.hostname, h.slots) for h in hosts] == [
        ("hostA", 4), ("hostC", 1)]


def test_is_local_host_fqdn_and_ip():
    """ADVICE: FQDN / resolved-IP references to this machine are local."""
    import socket
    from horovod_tpu.common.net import is_local_host, routable_addr
    assert is_local_host("localhost")
    assert is_local_host("127.0.0.1")
    assert is_local_host(socket.gethostname())
    assert is_local_host(socket.getfqdn())
    addr = routable_addr()
    if addr and addr[0].isdigit():
        assert is_local_host(addr)
    assert not is_local_host("definitely-not-this-host.invalid")


def test_elastic_rendezvous_addr_routable_for_remote_hosts(monkeypatch):
    """ADVICE (medium): with any remote worker, the published rendezvous
    address must be a routable driver address, not 127.0.0.1."""
    drv = ElasticDriver(HostDiscoveryScript("true"),
                        [sys.executable, "-c", "pass"], min_np=1)
    monkeypatch.setattr(drv, "_spawn", lambda *a, **k: None)
    monkeypatch.setattr(drv, "_notify_workers", lambda *a, **k: None)
    try:
        assert drv._new_generation([DiscoveredHost("localhost", 2)])
        assert drv._rdv_addr == "127.0.0.1"
        assert drv._new_generation(
            [DiscoveredHost("localhost", 1),
             DiscoveredHost("remote-worker-1", 1)])
        assert drv._rdv_addr != "127.0.0.1"
        # explicit address always wins
        drv2 = ElasticDriver(HostDiscoveryScript("true"),
                             [sys.executable, "-c", "pass"], min_np=1,
                             rendezvous_addr="10.0.0.7")
        monkeypatch.setattr(drv2, "_spawn", lambda *a, **k: None)
        monkeypatch.setattr(drv2, "_notify_workers", lambda *a, **k: None)
        assert drv2._new_generation([DiscoveredHost("remote-worker-1", 2)])
        assert drv2._rdv_addr == "10.0.0.7"
        drv2.rendezvous.stop()
    finally:
        drv.rendezvous.stop()


# ------------------------------------------------- post-fault exit guard
def _run_guarded(tail: str) -> subprocess.CompletedProcess:
    src = (
        "import atexit, sys\n"
        "atexit.register(lambda: print('EARLY_HOOK_RAN', flush=True))\n"
        "from horovod_tpu.elastic import worker\n"
        "worker._install_exit_guard()\n"
        + tail)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO] + env.get("PYTHONPATH", "").split(os.pathsep))
    return subprocess.run([sys.executable, "-c", src], env=env,
                          capture_output=True, text=True, timeout=120)


def test_exit_guard_preserves_exit_codes_and_early_atexit_hooks():
    """The post-fault exit guard ends the process via os._exit (parked
    jax worlds must not reach interpreter finalization), but it must not
    LAUNDER failures into successes: the elastic driver judges workers
    by exit code.  Uncaught SystemExit never reaches sys.excepthook, so
    sys.exit(3) needs the guard's sys.exit wrap to survive; and atexit
    hooks registered before the fault (coverage writers...) still run."""
    res = _run_guarded("sys.exit(3)")
    assert res.returncode == 3, (res.returncode, res.stdout, res.stderr)
    assert "EARLY_HOOK_RAN" in res.stdout, (res.stdout, res.stderr)

    res = _run_guarded("raise RuntimeError('worker failed')")
    assert res.returncode == 1, (res.returncode, res.stdout, res.stderr)
    assert "EARLY_HOOK_RAN" in res.stdout, (res.stdout, res.stderr)

    res = _run_guarded("print('work done', flush=True)")
    assert res.returncode == 0, (res.returncode, res.stdout, res.stderr)
    assert "EARLY_HOOK_RAN" in res.stdout, (res.stdout, res.stderr)
