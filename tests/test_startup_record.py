"""The start-up record (``horovod_tpu.trace.startup()``): the spans of the
launcher and of every rank, the compile ledger, the line a process leaves
beside the compile cache, the ``/metrics`` series and the monitor's
``--startup`` table.  Everything that needs a world or a fresh interpreter
runs in child processes on the CPU; one ``-np 2`` launch serves the span
tests."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CORE = os.path.join(REPO, "horovod_tpu", "trace", "core.py")

INIT_CHILDREN = ("hvd/init/config", "hvd/init/distributed",
                 "hvd/init/backend", "hvd/init/cache", "hvd/init/engine",
                 "hvd/init/native", "hvd/init/controller",
                 "hvd/init/monitor")
RANK_SPANS = ("hvd/process", "hvd/import", "hvd/init") + INIT_CHILDREN + (
    "hvd/broadcast_parameters",)
LAUNCHER_SPANS = ("hvd/launch", "hvd/launch/placement", "hvd/launch/spawn")

WORKER = r"""
import json, os, sys
import jax
jax.config.update("jax_cpu_collectives_implementation", "gloo")
import jax.numpy as jnp
import horovod_tpu as hvd
from horovod_tpu import trace
from horovod_tpu.trace import core
hvd.init()
once = len(trace.startup()["spans"])
hvd.init()                              # initialised: records nothing
twice = len(trace.startup()["spans"])
params = hvd.broadcast_parameters({"w": jnp.ones((4, 4)), "b": jnp.zeros(4)})
live = trace.startup()
placed = core._startup.directory
hvd.shutdown()
jax.jit(lambda x: x * 3)(jnp.ones(3))   # after the shutdown: not the record's
with open(os.path.join(sys.argv[1], "rank%d.json" % live["rank"]), "w") as fh:
    json.dump({"once": once, "twice": twice, "live": live, "placed": placed,
               "after": trace.startup(), "wrote": trace.write_startup()}, fh)
"""

DRIVER = r"""
import json, sys
from horovod_tpu.runner.run import main
from horovod_tpu import trace
from horovod_tpu.trace import core
rc = main(["-np", "2", sys.executable, sys.argv[1], sys.argv[2]])
with open(sys.argv[2] + "/launcher.json", "w") as fh:
    json.dump({"rc": rc, "record": trace.startup(),
               "placed": core._startup.directory}, fh)
"""


def child_env(**extra):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")
           and not k.startswith(("HOROVOD_", "HVD_"))}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO, **extra)
    return env


@pytest.fixture(scope="module")
def launch(tmp_path_factory):
    """``torovodrun -np 2`` on the CPU, as ``tests/test_runner.py``
    launches, from a driver that keeps the launcher's record; the monitor
    armed so that its span exists."""
    out = tmp_path_factory.mktemp("launch")
    (out / "worker.py").write_text(WORKER)
    (out / "driver.py").write_text(DRIVER)
    proc = subprocess.run(
        [sys.executable, str(out / "driver.py"), str(out / "worker.py"),
         str(out)], env=child_env(HOROVOD_MONITOR="1"), cwd=str(out),
        capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr[-3000:]
    found = {name: json.loads((out / f"{name}.json").read_text())
             for name in ("launcher", "rank0", "rank1")}
    assert found["launcher"]["rc"] == 0
    return found


def spans_of(record, name):
    return [s for s in record["spans"] if s["name"] == name]


@pytest.mark.parametrize("rank", [0, 1])
@pytest.mark.parametrize("name", RANK_SPANS)
def test_a_rank_records_every_span_of_the_table(launch, rank, name):
    found = spans_of(launch[f"rank{rank}"]["live"], name)
    # the engine is built, and started once everything is attached to it
    assert len(found) == (2 if name == "hvd/init/engine" else 1), found
    assert all(s["seconds"] >= 0 and s["t0"] > 1e9 for s in found)


@pytest.mark.parametrize("rank", [0, 1])
def test_the_children_nest_in_init_and_cover_it(launch, rank):
    record = launch[f"rank{rank}"]["live"]
    whole, = spans_of(record, "hvd/init")
    covered = 0.0
    for name in INIT_CHILDREN:
        for s in spans_of(record, name):
            assert s["t0"] >= whole["t0"] - 1e-3
            assert s["t0"] + s["seconds"] <= (
                whole["t0"] + whole["seconds"] + 1e-3)
            covered += s["seconds"]
    assert covered >= 0.9 * whole["seconds"], (covered, whole)
    assert covered <= whole["seconds"] + 1e-3    # siblings, none counted twice
    # the package's import ends before init begins, and began after the
    # process did
    process, = spans_of(record, "hvd/process")
    imported, = spans_of(record, "hvd/import")
    assert process["t0"] == record["process_started_at"]
    assert abs(process["t0"] + process["seconds"] - imported["t0"]) < 1e-6
    assert imported["t0"] + imported["seconds"] <= whole["t0"]
    assert process["jax_imported"] == 1         # the worker imports jax first


@pytest.mark.parametrize("rank", [0, 1])
def test_the_spans_carry_their_ids(launch, rank):
    record = launch[f"rank{rank}"]["live"]
    first = lambda name: spans_of(record, name)[0]
    whole = first("hvd/init")
    assert (whole["world"], whole["rank"], whole["multi_process"]) == (
        2, rank, 1)
    assert first("hvd/init/config")["elastic"] == 0
    assert first("hvd/init/distributed")["processes"] == 2
    backend = first("hvd/init/backend")
    assert (backend["platform"], backend["devices"], backend["fresh"]) == (
        "cpu", 2, 1)
    assert first("hvd/init/cache") .keys() >= {"dir", "reset"}
    assert first("hvd/init/native")["built"] in (0, 1)
    controller = first("hvd/init/controller")
    assert controller["attempts"] >= 1 and controller["hier"] == 0
    sent = first("hvd/broadcast_parameters")
    assert (sent["n"], sent["bytes"], sent["root"]) == (2, 4 * 4 * 4 + 16, 0)
    assert (record["role"], record["rank"], record["world"],
            record["platform"]) == ("rank", rank, 2, "cpu")
    assert record["pid"] != record["ppid"] and record["host"]


@pytest.mark.parametrize("rank", [0, 1])
def test_a_second_init_records_nothing_and_shutdown_keeps_the_record(
        launch, rank):
    found = launch[f"rank{rank}"]
    assert found["once"] == found["twice"]
    assert len(spans_of(found["live"], "hvd/init")) == 1
    # after the shutdown the record is what it was at the shutdown: the
    # program compiled after it is not in the ledger
    after = found["after"]
    assert [s["name"] for s in after["spans"]] == [
        s["name"] for s in found["live"]["spans"]]
    assert "<lambda>" not in after["ledger"]["programs"]


def test_the_two_ranks_share_one_clock(launch):
    """``jax.distributed.initialize`` returns when both have connected."""
    ends = [s["t0"] + s["seconds"] for r in (0, 1)
            for s in spans_of(launch[f"rank{r}"]["live"],
                              "hvd/init/distributed")]
    assert abs(ends[0] - ends[1]) < 0.5, ends


@pytest.mark.parametrize("name", LAUNCHER_SPANS)
def test_the_launcher_records_its_three_spans(launch, name):
    record = launch["launcher"]["record"]
    span, = spans_of(record, name)
    whole, = spans_of(record, "hvd/launch")
    assert span["t0"] >= whole["t0"] - 1e-6
    assert span["t0"] + span["seconds"] <= (
        whole["t0"] + whole["seconds"] + 1e-3)
    want = {"hvd/launch": {"np": 2, "hosts": 1},
            "hvd/launch/placement": {"chips": 0},
            "hvd/launch/spawn": {"n": 2}}[name]
    assert {k: span[k] for k in want} == want


def test_the_launcher_started_the_ranks_it_names(launch):
    head = launch["launcher"]["record"]
    assert head["role"] == "launcher" and head["world"] == 2
    whole, = spans_of(head, "hvd/launch")
    for rank in (0, 1):
        record = launch[f"rank{rank}"]["live"]
        assert record["ppid"] == head["pid"]
        # a worker begins inside the launcher's spawn, on the same clock
        assert whole["t0"] <= record["process_started_at"] + 0.02
        assert record["process_started_at"] <= (
            whole["t0"] + whole["seconds"] + 0.02)


def test_a_cpu_run_by_itself_leaves_no_file(launch):
    """No directory named and no accelerator: nobody is told where to
    write, and nobody writes."""
    assert launch["launcher"]["placed"] is None
    for rank in (0, 1):
        assert launch[f"rank{rank}"]["placed"] is None
        assert launch[f"rank{rank}"]["wrote"] is None


@pytest.mark.parametrize("placed, accelerator", [
    ("gs://bucket/cache", True), ("gs://bucket/cache", False),
    ("cc", True), ("cc", False)])
def test_the_line_goes_only_to_a_local_directory(
        tmp_path, monkeypatch, placed, accelerator):
    """A pod may share its cache through ``gs://...``, which jax reads
    through its own file system layer: the line is written with ``os``, so
    there it is not written, and no ``./gs:/`` appears where the job
    runs."""
    from horovod_tpu.common import compile_cache
    from horovod_tpu.trace import core
    local = "://" not in placed
    if local:
        placed = str(tmp_path / placed)
    monkeypatch.chdir(tmp_path)
    monkeypatch.setenv(compile_cache.ENV, placed)
    monkeypatch.setattr(core, "_startup", core._Startup())
    compile_cache.place_process_file(accelerator)
    assert core._startup.directory == (placed if local else None)
    assert core.write_startup() == (
        os.path.join(placed, core.PROCESS_FILE) if local else None)
    assert os.listdir(tmp_path) == (["cc"] if local else [])


# ---------------------------------------------------------------- native
def test_native_reads_built_on_a_fresh_artifact_and_not_after(
        tmp_path, monkeypatch):
    from horovod_tpu.common import native
    from horovod_tpu.trace import core
    fresh = str(tmp_path / "libhvdtpu_coord.fresh.so")
    monkeypatch.setattr(native, "_OUT_DIR", str(tmp_path))
    monkeypatch.setattr(native, "_out_path", lambda: fresh)
    seen = []
    monkeypatch.setattr(core, "startup_interval",
                        lambda name, t0, t1, **ids: seen.append((name, ids)))
    for want in (1, 0):
        monkeypatch.setattr(native, "_lib", None)
        native.load()
        assert seen[-1] == ("hvd/init/native", {"built": want})
    assert os.path.exists(fresh)


# ---------------------------------------------------------------- ledger
LEDGER = r"""
import json, sys
import jax, jax.numpy as jnp
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
import horovod_tpu as hvd
from horovod_tpu import trace
hvd.init()
@jax.jit
def my_program(x):
    return jnp.sin(x) @ x
my_program(jnp.ones((8, 8)))
one = trace.startup()["ledger"]
my_program(jnp.ones((4, 4)))            # another shape: a retrace
two = trace.startup()["ledger"]
hvd.shutdown()
print(json.dumps({"one": one, "two": two}))
"""


@pytest.fixture(scope="module")
def ledgers(tmp_path_factory):
    """The same program in two processes over one cache directory that the
    environment names: cold, then warm."""
    cache = tmp_path_factory.mktemp("cache")
    runs = []
    for _ in range(2):
        proc = subprocess.run(
            [sys.executable, "-c", LEDGER], cwd=str(cache),
            env=child_env(JAX_COMPILATION_CACHE_DIR=str(cache)),
            capture_output=True, text=True, timeout=240)
        assert proc.returncode == 0, proc.stderr[-3000:]
        runs.append(json.loads(proc.stdout.strip().splitlines()[-1]))
    return cache, runs


def test_the_ledger_names_a_program_and_says_it_asked_and_missed(ledgers):
    entry = ledgers[1][0]["one"]["programs"]["my_program"]
    assert (entry["count"], entry["asked_cache"], entry["hits"]) == (1, 1, 0)
    assert entry["retrieval_s"] == 0
    assert min(entry["trace_s"], entry["lower_s"], entry["backend_s"]) > 0
    assert entry["first_at"] > 1e9
    # functions traced inside it (sin, matmul) are part of it
    assert not {"sin", "matmul"} & set(ledgers[1][0]["one"]["programs"])


def test_the_ledger_counts_a_retrace(ledgers):
    entry = ledgers[1][0]["two"]["programs"]["my_program"]
    assert (entry["count"], entry["asked_cache"], entry["hits"]) == (2, 2, 0)


def test_a_second_process_hits_and_reads_the_entry_back(ledgers):
    entry = ledgers[1][1]["one"]["programs"]["my_program"]
    assert (entry["count"], entry["asked_cache"], entry["hits"]) == (1, 1, 1)
    assert entry["retrieval_s"] > 0
    totals = ledgers[1][1]["two"]["totals"]
    assert totals["hits"] == totals["asked_cache"] == totals["count"] >= 2
    assert totals["cache_writes"] == 0          # nothing left to write
    assert ledgers[1][0]["two"]["totals"]["cache_writes"] >= 2


def test_the_totals_are_the_tables_sums(ledgers):
    for run in ledgers[1]:
        ledger = run["two"]
        for key in ("count", "trace_s", "lower_s", "backend_s",
                    "asked_cache", "hits", "retrieval_s"):
            assert ledger["totals"][key] == pytest.approx(
                sum(e[key] for e in ledger["programs"].values()))


def test_each_process_left_its_line_beside_the_cache_it_was_given(ledgers):
    """The environment named the directory: a line a process, and jax
    went on writing entries into a directory that holds the file (the
    second process's hits)."""
    from horovod_tpu.trace import core
    cache, runs = ledgers
    lines = core.read_process_lines(str(cache))
    assert len(lines) == 2
    for line, run in zip(lines, runs):
        assert (line["role"], line["platform"]) == ("single", "cpu")
        # written at the shutdown: what the process read then
        assert line["ledger"]["programs"]["my_program"] == (
            run["two"]["programs"]["my_program"])
        assert [s["name"] for s in line["spans"]].count("hvd/init") == 1
    assert any(name.endswith("-cache") for name in os.listdir(cache))


# ------------------------------------------------------------------ file
def load_core():
    """``trace/core.py`` by its path: it needs nothing but the standard
    library."""
    import importlib.util
    spec = importlib.util.spec_from_file_location("hvd_trace_core", CORE)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_four_processes_appending_at_once_leave_whole_lines(tmp_path):
    code = (
        "import importlib.util, sys\n"
        f"spec = importlib.util.spec_from_file_location('c', {CORE!r})\n"
        "core = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(core)\n"
        "for i in range(8):\n"
        "    core.append_process_line(sys.argv[1], {'who': sys.argv[2],\n"
        "        'i': i, 'spans': [], 'pad': 'x' * 20000})\n")
    procs = [subprocess.Popen([sys.executable, "-c", code, str(tmp_path),
                               str(n)]) for n in range(4)]
    assert [p.wait(timeout=120) for p in procs] == [0] * 4
    rows = (tmp_path / "_hvd_processes.jsonl").read_text().splitlines()
    assert len(rows) == 32
    parsed = [json.loads(row) for row in rows]     # every line whole
    for who in "0123":
        assert [r["i"] for r in parsed if r["who"] == who] == list(range(8))


def test_the_file_is_cut_to_its_newest_lines_at_its_bound(tmp_path):
    core = load_core()
    for i in range(40):     # 40 x 50 KB: past 1 MB on the way
        core.append_process_line(str(tmp_path), {"i": i, "pad": "x" * 50000})
    path = tmp_path / core.PROCESS_FILE
    assert path.stat().st_size <= core.PROCESS_FILE_MAX_BYTES
    kept = [r["i"] for r in core.read_process_lines(str(tmp_path))]
    assert kept == list(range(kept[0], 40)) and 1 <= len(kept) < 40
    # and many small lines are cut to the newest 256
    small = tmp_path / "small"
    for i in range(300):
        core.append_process_line(str(small), {"i": i, "pad": "x" * 5000})
    kept = [r["i"] for r in core.read_process_lines(str(small))]
    assert kept[-1] == 299 and len(kept) <= core.PROCESS_FILE_KEEP_LINES + 50
    assert kept == list(range(kept[0], 300))


def test_a_foreign_line_is_skipped_and_a_missing_file_is_no_records(tmp_path):
    core = load_core()
    assert core.read_process_lines(str(tmp_path)) == []
    core.append_process_line(str(tmp_path), {"a": 1})
    with open(tmp_path / core.PROCESS_FILE, "a") as fh:
        fh.write("not json\n[1, 2]\n")
    core.append_process_line(str(tmp_path), {"a": 2})
    assert core.read_process_lines(str(tmp_path)) == [{"a": 1}, {"a": 2}]


def test_a_name_keeps_its_first_intervals_and_crowds_out_no_other():
    """A replica that pushes weights for the life of the process stamps
    ``hvd/broadcast_parameters`` every time: the name is bounded, the rest
    is counted, and a later re-``init()`` still finds room."""
    core = load_core()
    for i in range(core.STARTUP_MAX_PER_NAME + 10):
        with core.startup_span("hvd/broadcast_parameters", n=i):
            pass
    with core.startup_span("hvd/init", world=1):
        pass
    record = core.startup()
    kept = [s["n"] for s in record["spans"]
            if s["name"] == "hvd/broadcast_parameters"]
    assert kept == list(range(core.STARTUP_MAX_PER_NAME))
    assert record["spans"][-1]["name"] == "hvd/init"
    assert record["spans_dropped"] == 10 and record["ledger"] is None
    assert set(core.startup_seconds()) == {"hvd/broadcast_parameters",
                                           "hvd/init"}


# --------------------------------------------------------------- metrics
SERIES = ("hvd_inner_update_compiled_total", "hvd_inner_update_traces_total",
          "hvd_stage_group_compiled_total", "hvd_stage_group_traces_total",
          "hvd_stage_group_packed_total", "hvd_compiles_total",
          "hvd_compile_cache_requests_total", "hvd_compile_cache_hits_total")
LABELLED = ('hvd_startup_seconds{phase="hvd/import",rank="0"}',
            'hvd_compile_seconds_total{stage="trace",rank="0"}',
            'hvd_compile_seconds_total{stage="lower",rank="0"}',
            'hvd_compile_seconds_total{stage="backend",rank="0"}',
            'hvd_compile_seconds_total{stage="retrieval",rank="0"}')


@pytest.fixture(scope="module")
def prometheus():
    import jax
    import jax.numpy as jnp
    from horovod_tpu.common import compile_cache
    from horovod_tpu.monitor.agent import MonitorAgent

    class Engine:
        monitor = None

    compile_cache.register_ledger()
    jax.jit(lambda x: x + 41)(jnp.ones(3))      # one compile at least
    agent = MonitorAgent(engine=Engine())
    try:
        return agent.render_prometheus()
    finally:
        agent.close()


@pytest.mark.parametrize("series", SERIES + LABELLED)
def test_metrics_serves_the_table_of_series(prometheus, series):
    rows = [r for r in prometheus.splitlines() if not r.startswith("#")]
    name = series if "{" in series else series + '{rank="0"}'
    found = [r for r in rows if r.startswith(name + " ")]
    assert len(found) == 1, (series, found)
    assert float(found[0].split()[-1]) >= 0


def test_a_labelled_series_is_described_once(prometheus):
    rows = prometheus.splitlines()
    for name, kind in (("hvd_compile_seconds_total", "counter"),
                       ("hvd_startup_seconds", "gauge")):
        assert rows.count(f"# TYPE {name} {kind}") == 1
        assert sum(r.startswith(f"# HELP {name} ") for r in rows) == 1
    compiles = next(r for r in rows if r.startswith("hvd_compiles_total{"))
    assert float(compiles.split()[-1]) >= 1


def test_the_series_table_holds_what_registered():
    from horovod_tpu.trace import core
    assert set(SERIES[:5]) | {"hvd_startup_seconds"} <= set(core.SERIES)
    kind, text, read, label = core.SERIES["hvd_stage_group_packed_total"]
    assert (kind, label) == ("counter", None)
    assert read() == core.stage_group["packed"]


# ------------------------------------------------------------------- CLI
def made_up(role, pid, ppid, rank, started, spans, ledger=None):
    return {"v": 1, "role": role, "pid": pid, "ppid": ppid, "rank": rank,
            "world": 4, "host": "h", "platform": "" if role == "launcher"
            else "tpu", "process_started_at": started,
            "written_at": started + 60,
            "spans": [{"name": n, "t0": started + a, "seconds": b - a}
                      for n, a, b in spans], "spans_dropped": 0,
            "ledger": ledger}


def a_launch(t, launcher_pid, slow_rank=None):
    """Four ranks' lines, then their launcher's, as a launch leaves them."""
    rows = []
    for rank in range(4):
        late = 20.0 if rank == slow_rank else 0.0
        ledger = {"totals": {"count": 3, "trace_s": 1.0, "lower_s": 2.0,
                             "backend_s": 70.0 if rank else 1.5,
                             "retrieval_s": 0.0 if rank else 4.0,
                             "asked_cache": 3, "hits": 0 if rank else 3,
                             "cache_writes": 0},
                  "programs": {"grads_fn": {
                      "count": 1, "trace_s": 0.5, "lower_s": 1.0,
                      "backend_s": 60.0 if rank else 0.5,
                      "asked_cache": 1, "hits": 0 if rank else 1,
                      "retrieval_s": 0.0 if rank else 3.0,
                      "first_at": t + 40}}}
        rows.append(made_up(
            "rank", 100 + rank + launcher_pid, launcher_pid, rank, t + 3, [
                ("hvd/process", 0, 2), ("hvd/import", 2, 3),
                ("hvd/init/distributed", 3, 9 + late),
                ("hvd/init/backend", 9 + late, 17 + late),
                ("hvd/init", 3, 18 + late)], ledger))
    rows.append(made_up("launcher", launcher_pid, 1, 0, t, [
        ("hvd/process", 0, 0.1), ("hvd/import", 0.1, 2.6),
        ("hvd/launch/placement", 2.6, 2.9), ("hvd/launch/spawn", 2.9, 3.0),
        ("hvd/launch", 2.6, 3.0)]))
    return rows


def test_the_cli_prints_the_newest_launch_a_row_a_process(tmp_path, capsys):
    from horovod_tpu.monitor.__main__ import main, newest_launch
    from horovod_tpu.trace import core
    rows = a_launch(1.7e9, 5000) + a_launch(1.7e9 + 500, 6000, slow_rank=2)
    for row in rows:
        core.append_process_line(str(tmp_path), row)
    newest = newest_launch(core.read_process_lines(str(tmp_path)))
    assert [r["pid"] for r in newest] == [6000, 6100, 6101, 6102, 6103]
    assert main(["--startup", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    table = out.split("seconds by phase")[1].split("phase ended")[0]
    body = [r for r in table.splitlines()
            if r.startswith(("launcher", "rank"))]
    assert [r.split()[0:2] for r in body] == [
        ["launcher", "6000"]] + [["rank", str(r)] for r in range(4)]
    header = table.splitlines()[1].split()
    assert header[:2] == ["process", "pid"]
    assert {"launch", "launch/spawn", "import", "init/distributed",
            "init"} <= set(header)
    # the slow rank's long span stands out in its row, and the clock is one
    column = header.index("init/distributed") + 1     # "rank 2" is two words
    assert [float(r.split()[column]) for r in body[1:]] == [6, 6, 26, 6]
    ended = out.split("phase ended")[1]
    assert "one clock" in ended
    # the programs: who asked the cache, and who was served
    assert "grads_fn" in out and "rank 0: 1/1/1" in out
    assert "rank 3: 1/1/0" in out and "60.000 s" in out


def test_the_cli_names_the_process_that_kept_not_every_interval(tmp_path,
                                                               capsys):
    from horovod_tpu.monitor.__main__ import main
    from horovod_tpu.trace import core
    rows = a_launch(1.7e9, 5000)
    rows[2]["spans_dropped"] = 7
    for row in rows:
        core.append_process_line(str(tmp_path), row)
    assert main(["--startup", str(tmp_path)]) == 0
    note, = [r for r in capsys.readouterr().out.splitlines()
             if "past a name's bound" in r]
    assert note.endswith("rank 2: 7")


def test_the_cli_shows_one_process_alone_and_fails_on_nothing(tmp_path,
                                                              capsys):
    from horovod_tpu.monitor.__main__ import main
    from horovod_tpu.trace import core
    assert main(["--startup", str(tmp_path)]) == 1
    assert "no start-up record" in capsys.readouterr().out
    for row in a_launch(1.7e9, 5000):
        core.append_process_line(str(tmp_path), row)
    core.append_process_line(str(tmp_path), made_up(
        "single", 7000, 1, 0, 1.7e9 + 900,
        [("hvd/process", 0, 2), ("hvd/init", 3, 4)]))
    assert main(["--startup", str(tmp_path), "--json"]) == 0
    rows = json.loads(capsys.readouterr().out)
    assert [r["pid"] for r in rows] == [7000]
