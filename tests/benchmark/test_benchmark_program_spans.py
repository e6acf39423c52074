"""``benchmark/program_spans.py`` and the five per-layer metrics that read
the program's ``hvd/...`` spans: a small made-up profile (a calling thread,
the engine's cycle thread and its watcher, one device, a window, known
gaps) written as the ``*.trace.json.gz`` the profiler leaves beside its
``.xplane.pb``, and read back through the readers."""

import gzip
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import cell as cells                 # noqa: E402
from benchmark import program_spans as ps           # noqa: E402

DEVICE, HOST = 1, 2                     # pids
OPS, MODULES = 1, 2                     # the device's threads
MAIN, CYCLE, WATCH = 11, 12, 13         # the host's

# (name, start_s, end_s, thread[, ids]); the window is [0, 10], two steps
HOST_SPANS = [
    ("bench/traced_window", 0.0, 10.0, MAIN),
    ("hvd/update", -2.0, -1.0, MAIN, {"step": 6}),   # before the window
    ("hvd/update/inner", -1.5, -1.1, MAIN),
    ("bench/step", 0.0, 5.0, MAIN), ("bench/step", 5.0, 10.0, MAIN),
    ("bench/update", 0.95, 4.05, MAIN), ("bench/update", 5.95, 9.45, MAIN),
    ("hvd/update", 1.0, 4.0, MAIN, {"step": 7, "group": 3}),
    ("hvd/update/stage", 1.0, 1.5, MAIN, {"n": 2, "bytes": 64}),
    ("hvd/update/submit", 1.5, 1.6, MAIN, {"group": 3}),
    ("hvd/update/wait", 1.6, 2.6, MAIN, {"group": 3}),
    ("hvd/update/unpack", 2.6, 3.0, MAIN, {"n": 2, "bytes": 64}),
    ("hvd/update/inner", 3.0, 3.9, MAIN),
    ("PjitFunction(multiply)", 3.1, 3.2, MAIN),      # XLA's own: not ours
    ("hvd/update", 6.0, 9.4, MAIN, {"step": 8, "group": 4}),
    ("hvd/update/stage", 6.0, 6.7, MAIN, {"n": 2, "bytes": 64}),
    ("hvd/update/submit", 6.7, 6.8, MAIN, {"group": 4}),
    ("hvd/update/wait", 6.8, 7.2, MAIN, {"group": 4}),
    ("hvd/update/unpack", 7.2, 7.8, MAIN, {"n": 2, "bytes": 64}),
    ("hvd/update/inner", 7.8, 9.3, MAIN),
    # the cycle thread: empty lock-step rounds, and the rounds with tensors
    ("hvd/cycle", 0.2, 0.4, CYCLE, {"cycle": 40, "n": 0, "groups": ""}),
    ("hvd/cycle/negotiate", 0.25, 0.35, CYCLE, {"cycle": 40}),
    ("hvd/cycle", 1.55, 2.4, CYCLE, {"cycle": 41, "n": 2, "groups": 3}),
    ("hvd/cycle/negotiate", 1.6, 1.9, CYCLE, {"cycle": 41}),
    ("hvd/cycle/dispatch", 2.0, 2.3, CYCLE,
     {"cycle": 41, "n": 2, "bytes": 64, "hit": 0}),
    ("hvd/cycle", 4.5, 4.7, CYCLE, {"cycle": 42, "n": 0, "groups": ""}),
    ("hvd/cycle/negotiate", 4.55, 4.65, CYCLE, {"cycle": 42}),
    ("hvd/cycle", 6.75, 7.1, CYCLE, {"cycle": 43, "n": 2, "groups": 4}),
    ("hvd/cycle/negotiate", 6.8, 6.9, CYCLE, {"cycle": 43}),
    ("hvd/cycle/dispatch", 6.9, 7.05, CYCLE,
     {"cycle": 43, "n": 2, "bytes": 64, "hit": 1}),
    # a round that straddles the window's end: the part inside counts
    ("hvd/cycle", 9.8, 10.4, CYCLE, {"cycle": 44, "n": 1, "groups": 5}),
    ("hvd/cycle/negotiate", 9.9, 10.2, CYCLE, {"cycle": 44}),
    ("hvd/cycle/dispatch", 10.2, 10.3, CYCLE,
     {"cycle": 44, "n": 1, "bytes": 8, "hit": 1}),
    ("hvd/settle", 2.3, 2.6, WATCH, {"cycle": 41, "n": 2}),
    ("hvd/settle", 7.05, 7.2, WATCH, {"cycle": 43, "n": 2}),
]
# the device is busy here; the last operation begins inside the window and
# ends past it, and the window's idle time is counted to its end
BUSY = [(0.0, 0.3), (0.5, 1.2), (2.3, 2.6), (3.9, 6.95), (7.05, 7.2),
        (9.4, 10.2)]
STEPS = 2


def chrome(host_spans=HOST_SPANS, busy=BUSY, device=True):
    """The made-up profile as the trace viewer's JSON."""
    def meta(pid, tid, name):
        key = "thread_name" if tid is not None else "process_name"
        e = {"ph": "M", "pid": pid, "name": key, "args": {"name": name}}
        if tid is not None:
            e["tid"] = tid
        return e

    def x(pid, tid, name, a, b, args=None):
        e = {"ph": "X", "pid": pid, "tid": tid, "name": name,
             "ts": a * 1e6, "dur": (b - a) * 1e6}
        if args:
            e["args"] = {k: str(v) for k, v in args.items()}
        return e

    events = [meta(HOST, None, "/host:CPU")]
    events += [meta(HOST, t, "python") for t in (MAIN, CYCLE, WATCH)]
    events += [x(HOST, s[3], s[0], s[1], s[2], s[4] if len(s) > 4 else None)
               for s in host_spans]
    if device:
        events += [meta(DEVICE, None, "/device:TPU:0"),
                   meta(DEVICE, OPS, "XLA Ops"),
                   meta(DEVICE, MODULES, "XLA Modules"),
                   x(DEVICE, MODULES, "jit_step", 0.0, 10.2)]
        events += [x(DEVICE, OPS, f"fusion.{i}", a, b)
                   for i, (a, b) in enumerate(busy)]
    else:               # a rehearsal: host events that carry an hlo_op
        events += [x(HOST, 14, f"fusion.{i}", a, b, {"hlo_op": f"fusion.{i}"})
                   for i, (a, b) in enumerate(busy)]
    return {"displayTimeUnit": "ns", "traceEvents": events}


def context(tmp_path, profile):
    with gzip.open(tmp_path / "vm.trace.json.gz", "wt") as fh:
        json.dump(profile, fh)
    return {"trace": {"path": str(tmp_path / "vm.xplane.pb"),
                      "steps": STEPS}}


def reader(metric):
    return cells.load_module("layer_metrics", metric).read


@pytest.mark.parametrize("metric, expected_ms", [
    # per update: stage + unpack 0.5 + 0.4 and 0.7 + 0.6 -> median 1.1 s
    ("grad_staging_ms", 1100.0),
    ("inner_update_ms", 1200.0),        # 0.9 and 1.5
    ("engine_wait_ms", 700.0),          # 1.0 and 0.4
    # rounds with tensors only: 0.3 + 0.1 + the 0.1 inside the window's
    # end, over two steps; the two empty rounds' 0.2 s are left out
    ("negotiation_ms_per_step", 250.0),
    ("engine_dispatch_ms", 225.0),      # 0.3 + 0.15; the third is outside
])
def test_reader_on_known_values(tmp_path, metric, expected_ms):
    ctx = context(tmp_path, chrome())
    assert reader(metric)(ctx) == pytest.approx(expected_ms)
    # read once a run, kept in the context, the tables in its notes
    assert ctx["program_spans"]["steps"] == STEPS
    assert set(ctx["notes"]["program_spans"]) >= {
        "idle_by_update_span", "idle_by_cycle_span", "phases_over_update"}


@pytest.mark.parametrize("table, expected", [
    # gaps 0.3-0.5, 1.2-2.3, 2.6-3.9, 6.95-7.05, 7.2-9.4, each split over
    # the innermost spans of that thread open while it passed: the second
    # begins in stage (0.3), crosses submit (0.1) and ends in wait (0.7)
    ("idle_by_update_span", [["hvd/update/inner", 2.4],
                             ["hvd/update/unpack", 1.0],
                             ["hvd/update/wait", 0.8],
                             ["hvd/update/stage", 0.3],
                             [ps.OUTSIDE_UPDATE, 0.2],
                             ["hvd/update", 0.1],
                             ["hvd/update/submit", 0.1]]),
    ("idle_by_cycle_span", [[ps.ENGINE_IDLE, 3.95],
                            ["hvd/cycle/dispatch", 0.4],
                            ["hvd/cycle/negotiate", 0.35],
                            ["hvd/cycle", 0.2]]),
])
def test_idle_time_by_program_span(tmp_path, table, expected):
    found = ps.load(context(tmp_path, chrome()))
    got = found[table]
    assert [n for n, _ in got] == [n for n, _ in expected]
    assert [s for _, s in got] == pytest.approx([s for _, s in expected])
    # both tables split the same idle time: the window less the busy time
    assert sum(s for _, s in got) == pytest.approx(10.2 - 5.3)


def test_the_builders_checks_and_the_counts():
    device_ops, host = {"/device:TPU:0": BUSY}, [
        (s[0], s[1], s[2], s[3], s[4] if len(s) > 4 else {})
        for s in HOST_SPANS]
    found = ps.reduce_spans(device_ops, host, STEPS)
    assert len(found["updates"]) == 2           # the one before is left out
    assert found["phases_over_update"] == pytest.approx(6.2 / 6.4)
    assert found["update_over_bench_update"] == pytest.approx(6.4 / 6.6)
    assert found["median_ms"]["update"] == pytest.approx(3200.0)
    assert found["median_ms"]["submit"] == pytest.approx(100.0)
    assert (found["cycles"], found["cycles_with_tensors"]) == (4, 2)


def test_a_rehearsals_stand_in_operations_are_read(tmp_path):
    on_chip = ps.load(context(tmp_path, chrome()))
    rehearsed = ps.load(context(tmp_path, chrome(device=False)))
    assert rehearsed["idle_by_cycle_span"] == on_chip["idle_by_cycle_span"]


@pytest.mark.parametrize("metric", [
    "grad_staging_ms", "inner_update_ms", "engine_wait_ms",
    "negotiation_ms_per_step", "engine_dispatch_ms"])
def test_a_program_without_the_spans_reads_nothing(tmp_path, metric):
    """The parent commit: its trace has the benchmark's spans and XLA's,
    none of the program's.  The reader returns nothing and does not raise;
    the same without a rendered trace, or with no trace at all."""
    bare = [s for s in HOST_SPANS if not s[0].startswith("hvd/")]
    ctx = context(tmp_path, chrome(host_spans=bare))
    assert reader(metric)(ctx) is None and "notes" not in ctx
    os.unlink(tmp_path / "vm.trace.json.gz")
    assert reader(metric)({"trace": ctx["trace"]}) is None
    assert reader(metric)({"trace": None}) is None


def test_one_process_world_reads_zero_negotiation(tmp_path):
    """No controller, the cycle runs inline on the calling thread under
    ``hvd/update/submit`` and never negotiates: a number, 0, not nothing;
    the cycle thread's table is then the calling thread's cycles."""
    inline = [s for s in HOST_SPANS if s[3] == MAIN] + [
        ("hvd/cycle", 1.52, 1.59, MAIN, {"cycle": 9, "n": 2, "groups": 3}),
        ("hvd/cycle/dispatch", 1.53, 1.58, MAIN,
         {"cycle": 9, "n": 2, "bytes": 64, "hit": 1})]
    ctx = context(tmp_path, chrome(host_spans=inline))
    assert reader("negotiation_ms_per_step")(ctx) == 0.0
    assert reader("engine_dispatch_ms")(ctx) == pytest.approx(25.0)
    table = ctx["program_spans"]["idle_by_cycle_span"]
    assert [n for n, _ in table] == [
        ps.ENGINE_IDLE, "hvd/cycle/dispatch", "hvd/cycle"]
    assert [s for _, s in table] == pytest.approx([4.83, 0.05, 0.02])


def test_the_new_metrics_are_the_eager_cells_alone():
    bench = cells.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    new = [m for m in bench["per_layer"] if m["source"] == "program_span"
           and m["name"] != "update_host_ms"]
    assert sorted(m["name"] for m in new) == [
        "engine_dispatch_ms", "engine_wait_ms", "grad_staging_ms",
        "inner_update_ms", "negotiation_ms_per_step"]
    for m in new:
        assert m["workloads"] == ["resnet50-eager-1c", "resnet50-eager-np4"]
        assert (m["unit"], m["better"]) == ("ms", "lower")
        assert m["moves"] == "items_per_s_per_chip.eager"
    for cell in ("resnet50-spmd-1c", "mistral7b-4l-spmd-1c"):
        _, per_layer = cells.metrics_for(bench, cell)
        assert not {m["name"] for m in per_layer} & {m["name"] for m in new}
