"""The eight set-up readers and their helper (``benchmark/startup_record.py``)
on made-up records: a one-process cell reading its own process, a launched
cell reading the lines its launcher and ranks left, a program without the
record (the parent commit), and a stale launch's lines in the file."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import cell as cells              # noqa: E402
from benchmark import startup_record             # noqa: E402

NEW = {"launch_spawn_s": "launcher", "import_s": "launcher",
       "init_s": "launcher", "backend_init_s": "launcher",
       "rendezvous_s": "launcher", "trace_lower_s": "compile cache",
       "cache_retrieval_s": "compile cache",
       "cache_missed_programs": "compile cache"}
LAUNCHED_ONLY = ("launch_spawn_s", "backend_init_s", "rendezvous_s")
T = 1.79e9              # the launch, on time.time()'s clock


def read(name, ctx):
    return cells.load_module("layer_metrics", name).read(ctx)


def program(first_at, backend_s=1.0, hit=False, asked=True):
    return {"count": 1, "trace_s": 0.25, "lower_s": 0.5,
            "backend_s": backend_s, "asked_cache": int(asked),
            "hits": int(hit), "retrieval_s": 0.125 if hit else 0.0,
            "first_at": first_at}


def rank_record(rank, ppid, started, slow=0.0, fresh=1, role="rank"):
    """A rank whose process began at ``started``: 2 s to the package's
    first line, 1 s of import, half a second of the script's own, then
    ``hvd.init()``; three programs before the window, one after."""
    t = started
    spans = [("hvd/process", t, 2.0, {"jax_imported": 1}),
             ("hvd/import", t + 2, 1.0, {}),
             ("hvd/init/config", t + 3.5, 0.0, {"elastic": 0}),
             ("hvd/init/distributed", t + 3.5, 4.0 + slow, {"processes": 4}),
             ("hvd/init/backend", t + 7.5 + slow, 8.0, {"fresh": fresh}),
             ("hvd/init/engine", t + 15.5 + slow, 0.25, {}),
             ("hvd/init/native", t + 15.75 + slow, 0.5, {"built": 0}),
             ("hvd/init/controller", t + 16.25 + slow, 1.0, {"attempts": 1}),
             ("hvd/init/engine", t + 17.25 + slow, 0.25, {}),
             ("hvd/init", t + 3.5, 14.5 + slow, {"world": 4, "rank": rank})]
    programs = {"grads_fn": program(t + 20, 60.0 if rank else 0.5,
                                    hit=not rank),
                "hvd_inner_update": program(t + 30, 2.0, hit=not rank),
                "never_asked": program(t + 31, 0.25, asked=False),
                "reference_step": program(t + 500, 40.0)}
    return {"v": 1, "role": role, "pid": 1000 + rank + ppid, "ppid": ppid,
            "rank": rank, "world": 4, "host": "h", "platform": "tpu",
            "process_started_at": started,
            "spans": [{"name": n, "t0": a, "seconds": s, **ids}
                      for n, a, s, ids in spans],
            "ledger": {"totals": {}, "programs": programs}}


def launcher_record(pid, ppid, started):
    spans = [("hvd/process", started, 0.25), ("hvd/import", started + 0.25, 2.5),
             ("hvd/launch/placement", started + 2.75, 0.375),
             ("hvd/launch/spawn", started + 3.125, 0.125),
             ("hvd/launch", started + 2.75, 0.5)]
    return {"v": 1, "role": "launcher", "pid": pid, "ppid": ppid, "rank": 0,
            "world": 4, "host": "h", "platform": "",
            "process_started_at": started, "ledger": None,
            "spans": [{"name": n, "t0": a, "seconds": s}
                      for n, a, s in spans]}


def launched_ctx(world=4, setup_s=100.0):
    return {"launched_at": T, "ranks": [
        {"rank": r, "setup_s": setup_s, "world_formed_at": T + 22.0 + r}
        for r in range(world)]}


@pytest.fixture
def checkout(tmp_path, monkeypatch):
    """A checkout whose ``.jax_cache/_hvd_processes.jsonl`` the test
    fills: this process is the launcher's parent."""
    monkeypatch.setattr(cells, "ROOT", str(tmp_path))
    os.makedirs(tmp_path / ".jax_cache")

    def fill(records):
        with open(tmp_path / ".jax_cache" / startup_record.FILE, "w") as fh:
            for rec in records:
                fh.write(json.dumps(rec) + "\n")
    return fill


def a_launch(started, launcher_pid, parent=None, slow_rank=None):
    parent = os.getpid() if parent is None else parent
    return [rank_record(r, launcher_pid, started + 3.25,
                        slow=5.0 if r == slow_rank else 0.0)
            for r in range(4)] + [launcher_record(launcher_pid, parent,
                                                  started + 0.5)]


# ------------------------------------------------------- a launched cell
LAUNCHED_WANT = {
    "launch_spawn_s": 0.5,
    # the launcher's own 0.25 + 2.5 and a rank's 2 + 1
    "import_s": 5.75,
    "init_s": 19.5,                     # the slow rank's
    "backend_init_s": 8.0,
    "rendezvous_s": 10.5,               # 4 + 5 slow, 0.5, 1
    "trace_lower_s": 2.25,              # three programs before the window
    "cache_retrieval_s": 0.25,          # rank 0's two hits
    # ranks 1-3: two asked and were not served, one never asked
    "cache_missed_programs": 3.0}


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_launched_cell_reads_its_launchers_and_ranks_lines(checkout, name):
    checkout(a_launch(T, 4000, slow_rank=2))
    ctx = launched_ctx()
    assert read(name, ctx) == pytest.approx(LAUNCHED_WANT[name])
    assert isinstance(read(name, ctx), float)


def test_the_notes_say_where_world_form_s_went(checkout):
    checkout(a_launch(T, 4000, slow_rank=2))
    ctx = launched_ctx()
    read("init_s", ctx)
    notes = ctx["notes"]["startup"]
    assert [r["rank"] for r in notes["ranks"]] == [0, 1, 2, 3]
    row = notes["ranks"][2]
    assert row["seconds"]["hvd/init/distributed"] == 9.0
    assert row["seconds"]["hvd/init/engine"] == 0.5      # two intervals
    assert row["init_children_share"] == pytest.approx(19.0 / 19.5)
    assert row["script_before_init_s"] == pytest.approx(0.5)
    assert row["fresh"] == 1
    assert [p["program"] for p in row["slowest_programs"]] == [
        "grads_fn", "hvd_inner_update", "never_asked"]
    assert row["slowest_programs"][0] == {
        "program": "grads_fn", "backend_s": 60.0, "count": 1,
        "asked_cache": 1, "hits": 0}
    before = row["ledger_before_window"]
    assert (before["count"], before["asked_cache"], before["hits"],
            before["missed"], before["never_asked"]) == (3, 2, 0, 2, 1)
    assert before["backend_s"] == 62.25          # the reference's 40 is out
    assert notes["launcher"]["hvd/launch"] == 0.5
    assert notes["world_form_s"] == 25.0
    assert notes["launch_spawn_s+import_s+init_s"] == 0.5 + 5.75 + 19.5
    assert notes["gap_s"] == pytest.approx(-0.75)
    assert "script_before_init_s" in notes["gap_is"]


def test_a_stale_launchs_lines_and_anothers_are_not_this_runs(checkout):
    """Lines from before ``launched_at``, and a launch that another parent
    started meanwhile (the suite's workers rehearse side by side), are
    passed over."""
    checkout(a_launch(T - 900, 3000, slow_rank=0)
             + a_launch(T + 1, 5000, parent=1, slow_rank=1)
             + a_launch(T, 4000, slow_rank=2)
             + a_launch(T + 2, 6000, parent=2))
    ctx = launched_ctx()
    assert read("rendezvous_s", ctx) == pytest.approx(10.5)
    found = startup_record.load(ctx)
    assert found["launcher"]["pid"] == 4000
    assert [rec["ppid"] for rec, _ in found["ranks"]] == [4000] * 4


@pytest.mark.parametrize("name", sorted(NEW))
def test_only_a_stale_launch_in_the_file_reads_nothing(checkout, name):
    checkout(a_launch(T - 900, 3000))
    assert read(name, launched_ctx()) is None


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_launch_that_left_no_file_or_too_few_lines_reads_nothing(
        checkout, tmp_path, name):
    """A CPU launch by itself writes nothing; a rank that died wrote no
    line.  Nothing raises."""
    ctx = launched_ctx()
    assert read(name, ctx) is None and "notes" not in ctx
    checkout(a_launch(T, 4000)[1:])             # rank 0's line is missing
    assert read(name, launched_ctx()) is None
    with open(tmp_path / ".jax_cache" / startup_record.FILE, "w") as fh:
        fh.write("torn li")
    assert read(name, launched_ctx()) is None


def test_one_launched_process_is_a_launch_too(checkout):
    """``torovodrun -np 1``: the one worker calls itself ``single``."""
    checkout([rank_record(0, 4000, T + 3.25, role="single"),
              launcher_record(4000, os.getpid(), T + 0.5)])
    ctx = launched_ctx(world=1)
    assert read("launch_spawn_s", ctx) == 0.5
    assert read("init_s", ctx) == 14.5


# ---------------------------------------------------- a one-process cell
def inproc_ctx(setup_s=100.0):
    return {"launched_at": None, "ranks": [{"rank": 0, "setup_s": setup_s}]}


@pytest.fixture
def in_process(monkeypatch):
    from horovod_tpu import trace

    def give(record):
        monkeypatch.setattr(trace, "startup", lambda: record, raising=False)
    return give


INPROC_WANT = {"import_s": 3.0, "init_s": 14.5, "trace_lower_s": 2.25,
               "cache_retrieval_s": 0.25, "cache_missed_programs": 1.0}


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_one_process_cell_reads_its_own_process(in_process, name):
    """The script asked jax for its devices before ``hvd.init()``: the
    client opened there (``fresh`` 0) and ``backend_init_s`` is nothing,
    not the 8 s of a span that timed something else."""
    in_process(rank_record(0, 1, T, fresh=0, role="single"))
    ctx = inproc_ctx()
    value = read(name, ctx)
    if name in LAUNCHED_ONLY:
        assert value is None
    else:
        assert value == pytest.approx(INPROC_WANT[name])
    read("init_s", ctx)
    row = ctx["notes"]["startup"]["ranks"][0]
    assert row["fresh"] == 0 and row["script_before_init_s"] == 0.5
    assert "launcher" not in ctx["notes"]["startup"]


def test_the_ledger_is_cut_at_the_windows_start(in_process):
    in_process(rank_record(0, 1, T, role="single"))
    assert read("trace_lower_s", inproc_ctx(25.0)) == 0.75     # one program
    assert read("trace_lower_s", inproc_ctx(30.5)) == 1.5
    assert read("trace_lower_s", inproc_ctx(600.0)) == 3.0     # all four
    assert read("cache_missed_programs", inproc_ctx(600.0)) == 2.0
    assert read("backend_init_s", inproc_ctx()) == 8.0         # fresh here


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_parent_commit_has_no_record_and_nothing_raises(monkeypatch,
                                                            name):
    from horovod_tpu import trace
    monkeypatch.delattr(trace, "startup")
    ctx = inproc_ctx()
    assert read(name, ctx) is None
    assert "notes" not in ctx


@pytest.mark.parametrize("record", [None, {}, {"spans": []}, "text"])
def test_a_record_of_another_shape_reads_nothing(in_process, record):
    in_process(record)
    assert read("init_s", inproc_ctx()) is None


def test_a_record_without_a_ledger_still_gives_its_spans(in_process):
    record = rank_record(0, 1, T, role="single")
    record["ledger"] = None
    in_process(record)
    assert read("init_s", inproc_ctx()) == 14.5
    assert read("trace_lower_s", inproc_ctx()) is None


# --------------------------------------------------------- BENCHMARK.json
@pytest.mark.parametrize("name", sorted(NEW))
def test_the_eight_entries_move_setup_s_and_have_a_reader(name):
    bench = cells.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert entry["moves"] == "setup_s" and entry["layer"] == NEW[name]
    # read from the program's own record, not from the profile: the
    # profile's spans are PR 25's five (test_benchmark_program_spans.py)
    assert entry["source"] == "program_counter"
    # the three a one-process cell has nothing to read for list the two
    # launched cells; the other five go wherever setup_s goes
    assert entry.get("workloads") == (
        ["resnet50-eager-1c", "resnet50-eager-np4"]
        if name in LAUNCHED_ONLY else None)
    assert callable(cells.load_module("layer_metrics", name).read)
    position = [m["name"] for m in bench["per_layer"]].index(name)
    assert position >= 32                       # appended, nothing moved
