"""Launcher tests: arg parsing, hostfiles, env injection, ssh command
generation — multi-node logic tested with no cluster by asserting on the
generated commands, exactly like the reference's ``test/single/test_run.py``
(SURVEY.md §4).
"""

import os

import pytest

from horovod_tpu.runner.run import (
    HostSpec, parse_args, parse_hostfile, parse_hosts, placement,
    ssh_command, worker_envs,
)


def test_parse_hosts():
    specs = parse_hosts("a:4,b:2,c")
    assert [(s.hostname, s.slots) for s in specs] == [("a", 4), ("b", 2), ("c", 1)]


def test_parse_hostfile(tmp_path):
    f = tmp_path / "hosts"
    f.write_text("# comment\nnode1 slots=4\nnode2 slots=2  # trailing\n\nnode3\n")
    specs = parse_hostfile(str(f))
    assert [(s.hostname, s.slots) for s in specs] == [
        ("node1", 4), ("node2", 2), ("node3", 1)]


def test_parse_args_basic():
    args = parse_args(["-np", "4", "python", "train.py", "--lr", "0.1"])
    assert args.np == 4
    assert args.command == ["python", "train.py", "--lr", "0.1"]


def test_parse_args_requires_np():
    with pytest.raises(SystemExit):
        parse_args(["python", "train.py"])


def test_parse_args_requires_command():
    with pytest.raises(SystemExit):
        parse_args(["-np", "2"])


def test_config_file(tmp_path):
    cfg = tmp_path / "cfg.yaml"
    cfg.write_text("fusion-threshold-mb: 32\ncycle-time-ms: 2.5\n"
                   "autotune: true\n")
    args = parse_args(["-np", "2", "--config-file", str(cfg),
                       "python", "t.py"])
    assert args.fusion_threshold_mb == 32
    assert args.cycle_time_ms == 2.5
    assert args.autotune is True


def test_placement_overflow():
    args = parse_args(["-np", "8", "-H", "a:2,b:2", "python", "t.py"])
    with pytest.raises(ValueError, match="only 4 slots"):
        placement(args)


def test_worker_envs():
    args = parse_args(["-np", "4", "-H", "a:2,b:2",
                       "--fusion-threshold-mb", "16",
                       "--timeline-filename", "/tmp/tl",
                       "python", "t.py"])
    hosts = placement(args)
    envs = worker_envs(args, hosts, ("1.2.3.4", 5555, 5556))
    assert len(envs) == 4
    assert envs[0]["HOROVOD_RANK"] == "0"
    assert envs[3]["HOROVOD_RANK"] == "3"
    assert envs[2]["HOROVOD_LOCAL_RANK"] == "0"
    assert envs[2]["HOROVOD_CROSS_RANK"] == "1"
    assert all(e["HOROVOD_SIZE"] == "4" for e in envs)
    assert all(e["HOROVOD_CONTROLLER_ADDR"] == "1.2.3.4" for e in envs)
    assert envs[0]["HOROVOD_FUSION_THRESHOLD"] == str(16 * 1024 * 1024)
    assert envs[1]["HOROVOD_TIMELINE"] == "/tmp/tl.1"
    # Flat mode injects no agent endpoint.
    assert all("HOROVOD_AGENT_PORT" not in e for e in envs)


def test_worker_envs_hierarchical_controller():
    """ISSUE 9 launch path: --hierarchical-controller forwards the knob
    through tuning_env (shared by every backend, so it can't drift) and
    injects ONE agent port per host — every process on a host must agree
    where its aggregation agent listens."""
    from horovod_tpu.runner.run import tuning_env
    args = parse_args(["-np", "4", "-H", "a:2,b:2",
                       "--hierarchical-controller", "python", "t.py"])
    assert tuning_env(args)["HOROVOD_HIERARCHICAL_CONTROLLER"] == "1"
    hosts = placement(args)
    envs = worker_envs(args, hosts, ("1.2.3.4", 5555, 5556),
                       agent_ports=[7001, 7002])
    assert [e["HOROVOD_AGENT_PORT"] for e in envs] == \
        ["7001", "7001", "7002", "7002"]
    assert all(e["HOROVOD_HIERARCHICAL_CONTROLLER"] == "1" for e in envs)


def test_sharded_flag_forwards_fleet_uniform_env(monkeypatch):
    """ISSUE 15 launch path: --sharded forwards HOROVOD_SHARDED_OPTIMIZER=1
    through tuning_env to EVERY rank (the flag rides the negotiation
    digest — per-rank divergence is exactly the HVD110 bug), and the env
    round-trips into Config where DistributedOptimizer reads its
    default."""
    from horovod_tpu.common.config import Config
    from horovod_tpu.runner.run import tuning_env
    args = parse_args(["-np", "2", "--sharded", "python", "t.py"])
    assert tuning_env(args)["HOROVOD_SHARDED_OPTIMIZER"] == "1"
    args = parse_args(["-np", "2", "python", "t.py"])
    assert "HOROVOD_SHARDED_OPTIMIZER" not in tuning_env(args)
    monkeypatch.setenv("HOROVOD_SHARDED_OPTIMIZER", "1")
    assert Config.from_env().sharded_optimizer is True
    monkeypatch.delenv("HOROVOD_SHARDED_OPTIMIZER")
    assert Config.from_env().sharded_optimizer is False


def test_platform_worker_env_cpu_hygiene():
    """CPU launches get gloo collectives + a single-device XLA_FLAGS injected
    by the LAUNCHER, so user scripts need no platform preamble; TPU launches
    are untouched."""
    from horovod_tpu.runner.run import platform_worker_env
    base = {"JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": ("--xla_force_host_platform_device_count=8 "
                          "--xla_dump_to=/tmp/d")}
    env = platform_worker_env(base)
    assert env["JAX_CPU_COLLECTIVES_IMPLEMENTATION"] == "gloo"
    assert "device_count" not in env["XLA_FLAGS"]
    assert "--xla_dump_to=/tmp/d" in env["XLA_FLAGS"]
    # explicit user choice wins
    assert platform_worker_env(
        {"JAX_PLATFORMS": "cpu", "JAX_CPU_COLLECTIVES_IMPLEMENTATION": "mpi"}
    )["JAX_CPU_COLLECTIVES_IMPLEMENTATION"] == "mpi"
    assert platform_worker_env({}) == {}


# --- one process per chip on a TPU host (ISSUE 22) ------------------------

def _fake_pci(root, devices):
    for i, (vendor, device) in enumerate(devices):
        d = root / f"0000:00:{i:02x}.0"
        d.mkdir()
        (d / "vendor").write_text(vendor + "\n")
        (d / "device").write_text(device + "\n")
    return str(root)


def test_local_tpu_chips_counts_tpu_pci_functions_only(tmp_path):
    """The launcher tells a TPU host from sysfs alone: Google-vendor TPU
    device ids count; the same vendor's NIC and other vendors do not."""
    from horovod_tpu.runner.run import local_tpu_chips
    sysfs = _fake_pci(tmp_path, [("0x1ae0", "0x0063")] * 4
                      + [("0x1ae0", "0x0042"), ("0x8086", "0x0063")])
    assert local_tpu_chips(sysfs) == 4
    assert local_tpu_chips(str(tmp_path / "missing")) == 0


def test_tpu_worker_envs_bind_one_chip_per_worker():
    """Four workers on a four-chip host: each sees ONE chip, all share the
    2x2 process grid and the address list, each has its own port/task id
    (the variables libtpu 0.0.34 reads; verified on a v5e 2x2 host)."""
    from horovod_tpu.runner.run import tpu_worker_envs
    envs = tpu_worker_envs(4, 4, base={})
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    assert [e["CLOUD_TPU_TASK_ID"] for e in envs] == ["0", "1", "2", "3"]
    assert {e["TPU_CHIPS_PER_PROCESS_BOUNDS"] for e in envs} == {"1,1,1"}
    assert {e["TPU_PROCESS_BOUNDS"] for e in envs} == {"2,2,1"}
    (addresses,) = {e["TPU_PROCESS_ADDRESSES"] for e in envs}
    ports = [a.rsplit(":", 1)[1] for a in addresses.split(",")]
    assert len(set(ports)) == 4
    assert [e["TPU_PROCESS_PORT"] for e in envs] == ports
    # the image's own host bounds win over the chip-count table
    envs = tpu_worker_envs(8, 8, base={"TPU_CHIPS_PER_HOST_BOUNDS": "2,4,1"})
    assert {e["TPU_PROCESS_BOUNDS"] for e in envs} == {"2,4,1"}


@pytest.mark.parametrize("local_size,chips,base", [
    pytest.param(4, 0, {}, id="no-chips"),
    pytest.param(4, 4, {"JAX_PLATFORMS": "cpu"}, id="cpu-launch"),
    pytest.param(1, 4, {}, id="one-worker-drives-all-chips"),
    pytest.param(4, 4, {"TPU_VISIBLE_CHIPS": "0,1"}, id="caller-bound"),
    pytest.param(4, 4, {"TPU_PROCESS_BOUNDS": "1,1,1"}, id="caller-grid"),
])
def test_tpu_worker_envs_leave_other_launches_untouched(local_size, chips,
                                                        base):
    from horovod_tpu.runner.run import tpu_worker_envs
    assert tpu_worker_envs(local_size, chips, base=base) == []


@pytest.mark.parametrize("local_size,chips", [(2, 4), (3, 4), (8, 4), (2, 2)])
def test_tpu_worker_envs_refuse_splits_they_cannot_bind(local_size, chips):
    """N workers that do not tile the host fail AT ONCE with the supported
    modes named, instead of queueing on the chips' lock."""
    from horovod_tpu.runner.run import tpu_worker_envs
    with pytest.raises(ValueError, match="HOROVOD_ONE_PROC_PER_HOST"):
        tpu_worker_envs(local_size, chips, base={})


def test_worker_envs_tpu_host_binding(monkeypatch):
    """worker_envs on a (simulated) four-chip host: -np 4 binds rank r to
    chip r next to the HOROVOD_* block; the CPU launch is exactly as it
    was; a multi-host per-chip launch is refused."""
    from horovod_tpu.runner.run import TPU_BINDING_VARS
    for v in TPU_BINDING_VARS + ("TPU_CHIPS_PER_HOST_BOUNDS",):
        monkeypatch.delenv(v, raising=False)
    args = parse_args(["-np", "4", "python", "t.py"])
    hosts = placement(args)
    coord = ("127.0.0.1", 1234, 1235)

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    cpu = worker_envs(args, hosts, coord, tpu_chips=4)
    assert cpu == worker_envs(args, hosts, coord, tpu_chips=0)
    assert not any(v in e for e in cpu for v in TPU_BINDING_VARS)

    monkeypatch.delenv("JAX_PLATFORMS")
    tpu = worker_envs(args, hosts, coord, tpu_chips=4)
    assert [e["TPU_VISIBLE_CHIPS"] for e in tpu] == ["0", "1", "2", "3"]
    assert [e["HOROVOD_LOCAL_RANK"] for e in tpu] == ["0", "1", "2", "3"]
    assert "JAX_CPU_COLLECTIVES_IMPLEMENTATION" not in tpu[0]

    two_hosts = [HostSpec("localhost", 4), HostSpec("otherhost", 4)]
    args8 = parse_args(["-np", "8", "python", "t.py"])
    with pytest.raises(ValueError, match="single TPU host"):
        worker_envs(args8, two_hosts, coord, tpu_chips=4)


def test_launcher_modules_open_no_jax_backend():
    """The launcher (and the elastic driver) may import jax but must never
    initialize a backend: on a TPU host the chips belong to the workers,
    one process each, and a launcher that held them would hang every
    worker on the chips' lock.  Checked in a fresh interpreter, through
    the env computation a launch performs."""
    import subprocess
    import sys
    code = (
        "import sys\n"
        "import horovod_tpu.runner.launch, horovod_tpu.runner.run\n"
        "import horovod_tpu.runner.task_probe, horovod_tpu.runner.bootstrap\n"
        "import horovod_tpu.runner.tpu_vm, horovod_tpu.elastic.driver\n"
        "from horovod_tpu.runner.run import parse_args, placement, "
        "worker_envs\n"
        "a = parse_args(['-np', '4', 'python', 't.py'])\n"
        "assert len(worker_envs(a, placement(a), ('127.0.0.1', 1, 2))) == 4\n"
        "if 'jax' in sys.modules:\n"
        "    from jax._src import xla_bridge\n"
        "    assert not xla_bridge._backends, xla_bridge._backends\n"
        "print('NO_BACKEND')\n")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=repo)
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0 and "NO_BACKEND" in r.stdout, r.stderr[-2000:]


def test_ssh_command_generation():
    env = {"HOROVOD_RANK": "3", "HOROVOD_SIZE": "4"}
    cmd = ssh_command("node2", env, ["python", "train.py"], ssh_port=2222,
                      identity_file="/id")
    assert cmd[0] == "ssh"
    assert "-p" in cmd and "2222" in cmd
    assert "-i" in cmd and "/id" in cmd
    assert cmd[-2] == "node2"
    remote = cmd[-1]
    assert "HOROVOD_RANK=3" in remote and "python train.py" in remote
    assert os.getcwd() in remote


def test_local_launch_end_to_end(tmp_path):
    """Actually spawn 2 local worker processes and check injected env."""
    from horovod_tpu.runner.run import main
    out = tmp_path / "o"
    script = tmp_path / "w.py"
    script.write_text(
        "import os\n"
        "print(os.environ['HOROVOD_RANK'], os.environ['HOROVOD_SIZE'])\n")
    rc = main(["-np", "2", "--output-filename", str(out),
               "python", str(script)])
    assert rc == 0
    assert (out / "rank.0" / "stdout").read_text().strip() == "0 2"
    assert (out / "rank.1" / "stdout").read_text().strip() == "1 2"


def test_local_launch_propagates_failure(tmp_path):
    from horovod_tpu.runner.run import main
    script = tmp_path / "bad.py"
    script.write_text("import sys; sys.exit(3)\n")
    rc = main(["-np", "2", "python", str(script)])
    assert rc == 3


# ---------------------------------------------------------------- bootstrap
class TestBootstrap:
    """Host bootstrap services (reference P8: driver/task probe services,
    NIC discovery, mutual connectivity matrix) — tested without a cluster
    by running real probes on localhost, like test_run.py's style."""

    def test_list_nics_has_loopback(self):
        from horovod_tpu.runner.bootstrap import list_nics
        nics = list_nics()
        assert nics.get("lo") == "127.0.0.1", nics

    def _probe_thread(self, port, label, nic=None):
        import threading
        from horovod_tpu.runner.bootstrap import probe_main
        rc = {}
        t = threading.Thread(
            target=lambda: rc.setdefault(
                "rc", probe_main("127.0.0.1", port, label, nic)),
            daemon=True)
        t.start()
        return t, rc

    def test_register_and_matrix_ok(self):
        from horovod_tpu.runner.bootstrap import DriverService
        svc = DriverService(["localhost"], timeout_s=20)
        t, rc = self._probe_thread(svc.port, "localhost")
        try:
            addrs = svc.run()
        finally:
            svc.close()
        t.join(timeout=10)
        assert addrs == {"localhost": "127.0.0.1"} and rc.get("rc") == 0

    def test_nic_selection_and_missing_nic(self):
        from horovod_tpu.runner.bootstrap import DriverService
        svc = DriverService(["localhost"], nic="lo", timeout_s=20)
        t, _ = self._probe_thread(svc.port, "localhost", nic="lo")
        try:
            addrs = svc.run()
        finally:
            svc.close()
        t.join(timeout=10)
        assert addrs == {"localhost": "127.0.0.1"}

        svc = DriverService(["localhost"], nic="no_such_nic0", timeout_s=20)
        t, _ = self._probe_thread(svc.port, "localhost", nic="no_such_nic0")
        try:
            with pytest.raises(RuntimeError, match="no interface named"):
                svc.run()
        finally:
            svc.close()
        t.join(timeout=10)

    def test_connectivity_failure_names_pair(self):
        """A fake peer registers with a dead listen port: the launch must
        refuse naming exactly (real host, fake host)."""
        import json
        import socket
        import threading
        from horovod_tpu.runner.bootstrap import DriverService

        # A port with nothing listening:
        dead = socket.socket()
        dead.bind(("127.0.0.1", 0))
        dead_port = dead.getsockname()[1]
        dead.close()

        svc = DriverService(["localhost", "ghost"], timeout_s=30)
        t, _ = self._probe_thread(svc.port, "localhost")

        def fake_ghost():
            s = socket.create_connection(("127.0.0.1", svc.port), timeout=10)
            s.sendall((json.dumps(
                {"type": "register", "host": "ghost", "nics": {},
                 "addr": None, "listen_port": dead_port, "slots": 1,
                 "nic_found": True}) + "\n").encode())
            fh = s.makefile()
            fh.readline()                      # check request
            s.sendall((json.dumps(
                {"type": "result", "host": "ghost",
                 "reachable": {"localhost": True}}) + "\n").encode())
            fh.readline()
            s.close()

        g = threading.Thread(target=fake_ghost, daemon=True)
        g.start()
        try:
            with pytest.raises(RuntimeError,
                               match="'localhost' cannot reach .*'ghost'"):
                svc.run()
        finally:
            svc.close()
        t.join(timeout=15)
        g.join(timeout=15)

    def test_timeout_names_missing_host(self):
        from horovod_tpu.runner.bootstrap import DriverService
        svc = DriverService(["localhost", "never-shows-up"], timeout_s=2)
        t, _ = self._probe_thread(svc.port, "localhost")
        try:
            with pytest.raises(RuntimeError, match="never-shows-up"):
                svc.run()
        finally:
            svc.close()
        t.join(timeout=15)


class TestTPUVMBackend:
    """Cluster-scheduler backends (reference P7's jsrun/mpirun analogues):
    tested by asserting on the GENERATED commands/manifests, no cluster
    needed — the reference's own test_run.py pattern."""

    def _describe_json(self, n=4):
        import json
        return json.dumps({
            "networkEndpoints": [{"ipAddress": f"10.0.0.{i + 1}"}
                                 for i in range(n)],
            "state": "READY"})

    def _fake_runner(self, n=4):
        import subprocess

        calls = []

        def runner(cmd, **kw):
            calls.append(cmd)
            return subprocess.CompletedProcess(cmd, 0,
                                               stdout=self._describe_json(n),
                                               stderr="")
        return runner, calls

    def test_describe_and_ssh_commands(self):
        from horovod_tpu.runner.run import parse_args
        from horovod_tpu.runner import tpu_vm

        runner, calls = self._fake_runner(n=4)
        args = parse_args(["--tpu", "myslice", "--zone", "us-central2-b",
                           "--project", "proj", "python", "train.py"])
        eps = tpu_vm.describe_tpu(args.tpu, args.zone, args.project,
                                  runner=runner)
        assert [e.internal_ip for e in eps] == [
            "10.0.0.1", "10.0.0.2", "10.0.0.3", "10.0.0.4"]
        assert calls[0][:6] == ["gcloud", "compute", "tpus", "tpu-vm",
                                "describe", "myslice"]

        cmds = tpu_vm.tpu_vm_ssh_commands(args, eps, ports=(29400, 29401))
        assert len(cmds) == 4
        for wid, cmd in enumerate(cmds):
            assert cmd[:6] == ["gcloud", "compute", "tpus", "tpu-vm",
                               "ssh", "myslice"]
            assert ["--worker", str(wid)] == cmd[cmd.index("--worker"):
                                                 cmd.index("--worker") + 2]
            remote = cmd[cmd.index("--command") + 1]
            # Rank layout: worker index is the cross rank; coordinator is
            # worker 0's internal IP on every worker.
            assert f"HOROVOD_RANK={wid}" in remote
            assert "HOROVOD_SIZE=4" in remote
            assert f"HOROVOD_CROSS_RANK={wid}" in remote
            assert "HOROVOD_CONTROLLER_ADDR=10.0.0.1" in remote
            assert remote.endswith("python train.py")
            assert ["--project", "proj"] == cmd[-2:]

    def test_tpu_vm_slots_per_host_rejected(self):
        # --slots-per-host > 1 with a cluster backend would advertise
        # SIZE=hosts*slots while launching one process per host — every
        # worker would hang at rendezvous.  Rejected at parse time.
        from horovod_tpu.runner.run import parse_args

        with pytest.raises(SystemExit):
            parse_args(["--tpu", "s", "--zone", "z",
                        "--slots-per-host", "4", "python", "t.py"])
        # slots-per-host 1 (the only coherent value) is accepted.
        args = parse_args(["--tpu", "s", "--zone", "z",
                           "--slots-per-host", "1", "python", "t.py"])
        assert args.tpu == "s"

    def test_tpu_vm_one_rank_per_host(self):
        from horovod_tpu.runner.run import parse_args
        from horovod_tpu.runner import tpu_vm

        args = parse_args(["--tpu", "s", "--zone", "z", "python", "t.py"])
        eps = [tpu_vm.TPUEndpoint(i, f"10.0.0.{i + 1}") for i in range(2)]
        cmds = tpu_vm.tpu_vm_ssh_commands(args, eps, ports=(1, 2))
        r1 = cmds[1][cmds[1].index("--command") + 1]
        assert "HOROVOD_RANK=1" in r1          # rank == worker index
        assert "HOROVOD_SIZE=2" in r1
        assert "HOROVOD_LOCAL_SIZE=1" in r1

    def test_run_tpu_vm_propagates_failure(self):
        from horovod_tpu.runner.run import parse_args
        from horovod_tpu.runner import tpu_vm

        runner, _ = self._fake_runner(n=2)

        class FakeProc:
            def __init__(self, cmd):
                self.returncode = 3 if "--worker" in cmd and \
                    cmd[cmd.index("--worker") + 1] == "1" else 0

            def wait(self):
                return self.returncode

            def poll(self):
                return self.returncode

            def terminate(self):
                pass

        args = parse_args(["--tpu", "s", "--zone", "z", "python", "t.py"])
        rc = tpu_vm.run_tpu_vm(args, runner=runner, popen=FakeProc)
        assert rc == 3

    def test_gke_jobset_manifest(self):
        from horovod_tpu.runner.run import parse_args
        from horovod_tpu.runner.tpu_vm import render_gke_jobset

        args = parse_args(["--gke-jobset", "train", "--container-image",
                           "gcr.io/p/img:1", "--gke-num-hosts", "4",
                           "--gke-accelerator", "tpu-v5p-slice",
                           "--gke-topology", "2x2x4",
                           "--cycle-time-ms", "5",
                           "python", "train.py", "--lr", "0.1"])
        y = render_gke_jobset(args, args.gke_num_hosts)
        assert "kind: JobSet" in y
        assert "parallelism: 4" in y and "completions: 4" in y
        assert "completionMode: Indexed" in y
        assert "image: gcr.io/p/img:1" in y
        assert "gke-tpu-accelerator: tpu-v5p-slice" in y
        assert "gke-tpu-topology: 2x2x4" in y
        assert "HOROVOD_CROSS_RANK=$JOB_COMPLETION_INDEX" in y
        assert "HOROVOD_SIZE=4" in y           # one rank per host
        assert "HOROVOD_LOCAL_SIZE=1" in y
        assert "HOROVOD_CONTROLLER_ADDR=train-workers-0-0.train" in y
        assert "HOROVOD_CYCLE_TIME=5" in y      # tuning knobs forwarded
        assert "python train.py --lr 0.1" in y

    def test_gke_jobset_cli_renders(self, capsys):
        from horovod_tpu.runner.run import main

        rc = main(["--gke-jobset", "j", "--container-image", "i",
                   "--gke-num-hosts", "2",
                   "--gke-accelerator", "tpu-v5-lite-podslice",
                   "--gke-topology", "4x4", "python", "t.py"])
        assert rc == 0
        out = capsys.readouterr().out
        assert "kind: JobSet" in out
        assert "completions: 2" in out

    def test_tpu_vm_forwards_tuning_knobs_and_cwd(self):
        from horovod_tpu.runner.run import parse_args
        from horovod_tpu.runner import tpu_vm
        import os

        args = parse_args(["--tpu", "s", "--zone", "z",
                           "--fusion-threshold-mb", "128",
                           "--cycle-time-ms", "5", "python", "t.py"])
        eps = [tpu_vm.TPUEndpoint(0, "10.0.0.1")]
        remote = tpu_vm.tpu_vm_ssh_commands(args, eps, ports=(1, 2))[0]
        remote = remote[remote.index("--command") + 1]
        assert f"HOROVOD_FUSION_THRESHOLD={128 * 1024 * 1024}" in remote
        assert "HOROVOD_CYCLE_TIME=5" in remote
        # Same cwd convention as the plain ssh backend.
        assert remote.startswith(f"cd {os.getcwd()} && ")

    def test_describe_rejects_not_ready(self):
        import json
        import subprocess
        import pytest
        from horovod_tpu.runner import tpu_vm

        def runner(cmd, **kw):
            return subprocess.CompletedProcess(cmd, 0, stdout=json.dumps(
                {"state": "CREATING", "networkEndpoints": []}), stderr="")
        with pytest.raises(RuntimeError, match="CREATING"):
            tpu_vm.describe_tpu("s", "z", runner=runner)
