"""Program spans (``horovod_tpu.trace.span``): the span layer itself, the
calling thread's spans inside an eager ``DistributedOptimizer.update``, and
the engine threads' spans of the cycle that update caused.

The engine tests run real updates over a one-rank process set of the
8-virtual-device CPU mesh, with this process forced into the per-process
branch and a stub controller attached, so that the cycle thread negotiates
lock-step rounds and the in-flight watcher settles.
The recorder is handed a fake ``TraceAnnotation`` that keeps every span
with its ids, clock and thread, in place of a profiler session.
"""

import os
import subprocess
import sys
import threading
import time

import pytest

from horovod_tpu import trace
from horovod_tpu.trace import core

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class FakeAnnotation:
    """What ``jax.profiler.TraceAnnotation`` is to a span: built with the
    name and ids, entered, labelled, left.  Keeps what a profile would."""

    events = []
    built = 0

    def __init__(self, name, **ids):
        type(self).built += 1
        self.row = {"name": name, "ids": dict(ids),
                    "thread": threading.get_ident()}

    def set_metadata(self, **ids):
        self.row["ids"].update(ids)

    def __enter__(self):
        self.row["t0"] = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.row["t1"] = time.perf_counter()
        type(self).events.append(self.row)
        return False


def fresh_annotation():
    return type("Recorded", (FakeAnnotation,), {"events": [], "built": 0})


# ------------------------------------------------------------ the span layer
@pytest.fixture()
def disarmed(monkeypatch):
    """No recorder installed, whatever an earlier test file of this
    process left behind (an armed engine that was never stopped)."""
    monkeypatch.setattr(core, "_installed", None)


def test_span_disarmed_is_the_shared_noop(disarmed):
    assert trace.installed() is None
    before = sys.getallocatedblocks()
    for _ in range(10000):
        with trace.span("hvd/update/wait") as sp:
            assert sp is None
    # one shared object handed out and nothing kept: no block a span
    assert sys.getallocatedblocks() - before < 50
    assert trace.span("a") is trace.OFF and trace.span("b") is trace.OFF


def test_nested_pair_parent_covers_children_and_totals_match():
    ann = fresh_annotation()
    rec = core.TraceRecorder(annotation=ann)
    with rec.span("hvd/update", step=0) as up:
        with rec.span("hvd/update/stage", n=2):
            time.sleep(0.002)
        with rec.span("hvd/update/wait") as w:
            time.sleep(0.003)
            w.set(group=7)
        up.set(group=7)
    totals = rec.span_totals()
    assert {k: v[1] for k, v in totals.items()} == {
        "hvd/update": 1, "hvd/update/stage": 1, "hvd/update/wait": 1}
    parent = totals["hvd/update"][0]
    children = totals["hvd/update/stage"][0] + totals["hvd/update/wait"][0]
    assert parent >= children >= 5000.0
    by_name = {e["name"]: e for e in ann.events}
    assert by_name["hvd/update"]["ids"] == {"step": 0, "group": 7}
    assert by_name["hvd/update/wait"]["ids"] == {"group": 7}
    # the recorder's sum is the interval its TraceMe covered, or less
    for name, (sum_us, _) in totals.items():
        e = by_name[name]
        assert sum_us <= (e["t1"] - e["t0"]) * 1e6 + 1.0


def test_span_totals_ride_the_summary_and_the_digest():
    rec = core.TraceRecorder()          # a jax-free process: totals alone
    assert rec.annotation is None
    for _ in range(3):
        with rec.span("hvd/cycle/negotiate"):
            pass
    assert rec.phase_summary()["program_us"]["hvd/cycle/negotiate"] >= 0.0
    assert rec.digest()["program"]["hvd/cycle/negotiate"][1] == 3
    assert "program" not in core.TraceRecorder().digest()


def test_installed_recorder_is_what_span_reaches_until_it_closes(disarmed):
    class Cfg:
        trace = True
    rec = trace.maybe_install(Cfg())
    try:
        assert trace.installed() is rec
        # this process has jax: the spans are TraceMes
        import jax
        assert rec.annotation is jax.profiler.TraceAnnotation
        with trace.span("hvd/update/inner") as sp:
            assert isinstance(sp, trace.ProgramSpan)
        assert rec.span_totals()["hvd/update/inner"][1] == 1
    finally:
        rec.close()
    assert trace.installed() is None and trace.span("x") is trace.OFF


def test_trace_package_arms_without_jax():
    """``maybe_install`` in a process without jax: no import of it, spans
    keep their totals."""
    src = r"""
import importlib, os, sys, types
class BlockJax:
    def find_spec(self, name, path=None, target=None):
        if name.split('.')[0] in ('jax', 'jaxlib'):
            raise ImportError('purity: %s' % name)
sys.meta_path.insert(0, BlockJax())
m = types.ModuleType('horovod_tpu'); m.__path__ = [sys.argv[1]]
sys.modules['horovod_tpu'] = m
t = importlib.import_module('horovod_tpu.trace')
class Cfg: trace = True
rec = t.maybe_install(Cfg())
with t.span('hvd/update') as sp:
    sp.set(step=1)
assert rec.annotation is None and rec.span_totals()['hvd/update'][1] == 1
assert 'jax' not in sys.modules
print('OK')
"""
    out = subprocess.run(
        [sys.executable, "-c", src, os.path.join(REPO, "horovod_tpu")],
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 0 and "OK" in out.stdout, out.stderr


# ------------------------------------------- one traced update, in process
class StubController:
    """Lock-step rounds in which every announced entry is ready at once."""

    def __init__(self):
        self.rounds = 0

    def negotiate(self, entries):
        self.rounds += 1
        return list(entries), []

    def slot_of(self, e):
        return -1

    def forget(self, e):
        pass


UPDATES = 2


@pytest.fixture(scope="module")
def traced():
    """Two eager updates through the engine with tracing armed; yields the
    recorded spans and what the recorder kept."""
    import jax.numpy as jnp
    import optax
    import horovod_tpu as hvd
    from horovod_tpu.common import basics
    hvd.init()
    st = basics._get_state()
    eng, cfg = st.engine, st.config
    ann = fresh_annotation()
    rec = core.TraceRecorder(annotation=ann)
    saved = (eng.tracer, eng.controller, cfg.controller_addr,
             core._installed)
    with eng._cycle_lock:
        eng.tracer, eng.controller = rec, StubController()
        cfg.controller_addr, core._installed = "stub:0", rec
    ps = hvd.add_process_set([0])
    try:
        params = {"w": jnp.ones((5,)), "b": jnp.zeros((3,))}
        opt = hvd.DistributedOptimizer(optax.sgd(0.1, momentum=0.9),
                                       process_set=ps)
        state = opt.init(params)
        for i in range(UPDATES):
            grads = {"w": jnp.full((5,), 1.0 + i), "b": jnp.full((3,), 2.0)}
            updates, state = opt.update(grads, state, params)
            # an empty lock-step round between: wake the loop (idle, it
            # may be waiting out IDLE_WAIT_CAP_S) and see the round end
            rounds, deadline = eng.controller.rounds, time.monotonic() + 10
            eng.kick()
            while eng.controller.rounds == rounds:
                assert time.monotonic() < deadline, "the loop never woke"
                time.sleep(0.001)
        assert float(updates["b"][0]) < 0.0
        if eng._inflight is not None:
            eng._inflight.flush(10.0)
    finally:
        with eng._cycle_lock:
            (eng.tracer, eng.controller, cfg.controller_addr,
             core._installed) = saved
        if eng._inflight is not None:
            eng._inflight.stop()
            eng._inflight = eng._pingpong = None
        hvd.remove_process_set(ps)
    tensor_cycles = {s.cycle for s in rec._ring
                     if s.name.startswith("allreduce_gradients")}
    return {"events": sorted(ann.events, key=lambda e: e["t0"]),
            "totals": rec.span_totals(), "tensor_cycles": tensor_cycles,
            "caller": threading.get_ident(), "leaves": 2,
            "items": 1,             # both float32: one flat engine item
            "bytes": 4 * 8}        # 5 + 3 float32


def named(traced, name):
    return [e for e in traced["events"] if e["name"] == name]


def within(inner, outer):
    return (outer["thread"] == inner["thread"]
            and outer["t0"] <= inner["t0"] and inner["t1"] <= outer["t1"])


@pytest.mark.parametrize("name,ids", [
    ("hvd/update", {"step", "group"}),
    ("hvd/update/stage", {"n", "bytes", "compiled", "packed", "buffers"}),
    ("hvd/update/submit", {"group"}),
    ("hvd/update/wait", {"group"}),
    ("hvd/update/unpack", {"n", "bytes", "host"}),
    ("hvd/update/inner", {"compiled"}),
])
def test_calling_thread_span(traced, name, ids):
    spans = named(traced, name)
    assert len(spans) == UPDATES == traced["totals"][name][1]
    updates = named(traced, "hvd/update")
    for k, (sp, up) in enumerate(zip(spans, updates)):
        assert sp["thread"] == traced["caller"]
        assert set(sp["ids"]) == ids
        assert within(sp, up)
        if "group" in ids:          # one group an update, the update's own
            assert sp["ids"]["group"] == up["ids"]["group"]
        if "n" in ids:
            assert sp["ids"]["n"] == traced["leaves"]
            assert sp["ids"]["bytes"] == traced["bytes"]
        if "host" in ids:           # every leaf handed over on the device
            assert sp["ids"]["host"] == 0
        if name == "hvd/update/stage":  # every leaf through the one program
            assert sp["ids"]["compiled"] == sp["ids"]["n"]
            assert sp["ids"]["packed"] == sp["ids"]["n"]    # and flat
            assert sp["ids"]["buffers"] == traced["items"]
        elif "compiled" in ids:     # optax.sgd traces: the one program
            assert sp["ids"]["compiled"] == 1
    if name == "hvd/update":
        steps = [u["ids"]["step"] for u in updates]
        assert steps == list(range(steps[0], steps[0] + UPDATES))
        for up in updates:          # the five phases lie inside, in order
            kids = [e for e in traced["events"]
                    if e["name"].startswith("hvd/update/") and within(e, up)]
            assert [e["name"].rsplit("/", 1)[1] for e in kids] == [
                "stage", "submit", "wait", "unpack", "inner"]
            assert sum(e["t1"] - e["t0"] for e in kids) <= up["t1"] - up["t0"]


def loaded_cycles(traced):
    return [c for c in named(traced, "hvd/cycle") if c["ids"]["n"] > 0]


@pytest.mark.parametrize("name", ["hvd/cycle", "hvd/cycle/negotiate",
                                  "hvd/cycle/dispatch", "hvd/settle"])
def test_engine_thread_span(traced, name):
    cycles = loaded_cycles(traced)
    assert len(cycles) == UPDATES
    submits = named(traced, "hvd/update/submit")
    spans = named(traced, name)
    assert traced["totals"][name][1] == len(spans)
    if name == "hvd/cycle":
        for c, sub in zip(cycles, submits):
            assert c["thread"] != traced["caller"]
            assert set(c["ids"]) == {"cycle", "n", "groups", "waited_ms"}
            assert c["ids"]["n"] == traced["items"]
            # caused by that update's submit: its group, and after it began
            assert c["ids"]["groups"] == str(sub["ids"]["group"])
            assert c["t0"] >= sub["t0"]
            assert c["ids"]["cycle"] in traced["tensor_cycles"]
        assert {c["ids"]["cycle"] for c in cycles} == traced["tensor_cycles"]
        # a lock-step round with no tensor in it: its span, n = 0
        empty = [c for c in spans if c["ids"]["n"] == 0]
        assert empty and all(c["ids"]["groups"] == "" for c in empty)
        # the wait the cycle thread sat out before each cycle, in ms
        assert all(isinstance(c["ids"]["waited_ms"], float)
                   and c["ids"]["waited_ms"] >= 0.0 for c in spans)
    elif name == "hvd/cycle/negotiate":
        assert len(spans) == len(named(traced, "hvd/cycle"))
        for c in cycles:            # one a round, inside it, its round id
            mine = [s for s in spans if within(s, c)]
            assert len(mine) == 1
            assert mine[0]["ids"] == {"cycle": c["ids"]["cycle"]}
    elif name == "hvd/cycle/dispatch":
        assert len(spans) == UPDATES        # one fused batch an update
        for k, (d, c) in enumerate(zip(spans, cycles)):
            assert within(d, c)
            assert d["ids"] == {"cycle": c["ids"]["cycle"],
                                "n": traced["items"],
                                "bytes": traced["bytes"],
                                "hit": int(k > 0)}   # built once, then found
            neg = [s for s in named(traced, "hvd/cycle/negotiate")
                   if within(s, c)][0]
            assert neg["t1"] <= d["t0"]
            assert (neg["t1"] - neg["t0"]) + (d["t1"] - d["t0"]) <= \
                c["t1"] - c["t0"]
    else:
        assert len(spans) == UPDATES
        cycle_thread = cycles[0]["thread"]
        for s, c in zip(spans, cycles):     # the in-flight watcher's
            assert s["thread"] not in (cycle_thread, traced["caller"])
            assert s["ids"] == {"cycle": c["ids"]["cycle"],
                                "n": traced["items"]}


# ---------------------------------------- what stays out while tracing a step
@pytest.mark.parametrize("how", ["jit", "shard_map"])
def test_traced_step_enters_no_span(hvd, how):
    """An update traced under ``jit`` / ``shard_map`` is a step program:
    with tracing armed it opens no span and builds no TraceMe."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P
    from horovod_tpu.compat import shard_map
    ann = fresh_annotation()
    rec = core.TraceRecorder(annotation=ann)
    opt = hvd.DistributedOptimizer(optax.sgd(0.1))
    params = {"w": jnp.ones((8, 4))}
    state = opt.init(params)

    def update(g, s, p):
        return opt.update(g, s, p)[0]

    saved, core._installed = core._installed, rec
    try:
        if how == "jit":
            out = jax.jit(update)(params, state, params)
        else:
            out = jax.jit(shard_map(
                update, mesh=hvd.mesh(), in_specs=(P("hvd"), P(), P("hvd")),
                out_specs=P("hvd"), check_vma=False))(params, state, params)
        jax.block_until_ready(out)
    finally:
        core._installed = saved
    assert ann.built == 0 and not rec.span_totals()


def test_disarmed_update_builds_no_trace_annotation(hvd, monkeypatch,
                                                    disarmed):
    """``HOROVOD_TRACE`` unset: ``engine.tracer is None`` and no
    ``TraceAnnotation`` is constructed anywhere on the eager update path."""
    import jax
    import jax.numpy as jnp
    import optax
    from horovod_tpu.common import basics
    st = basics._get_state()
    assert st.engine.tracer is None and trace.installed() is None
    built = []

    class Counting(jax.profiler.TraceAnnotation):
        def __init__(self, *a, **k):
            built.append(a)
            super().__init__(*a, **k)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    monkeypatch.setattr(st.config, "controller_addr", "stub:0")
    ps = hvd.add_process_set([0])
    try:
        params = {"w": jnp.ones((5,))}
        opt = hvd.DistributedOptimizer(optax.sgd(0.1, momentum=0.9),
                                       process_set=ps)
        state = opt.init(params)
        before = st.engine.pipeline_dispatches
        updates, state = opt.update({"w": jnp.full((5,), 3.0)}, state,
                                    params)
    finally:
        hvd.remove_process_set(ps)
    assert st.engine.pipeline_dispatches == before + 1  # through the engine
    assert float(updates["w"][0]) == pytest.approx(-0.3)
    assert built == []


def test_monitor_cli_prints_program_spans_from_the_digest():
    """A fleet without a profiler: the totals ride the MON1 digest and
    ``python -m horovod_tpu.monitor`` prints them per rank."""
    from horovod_tpu.monitor.__main__ import render
    rec = core.TraceRecorder()
    for _ in range(2):
        with rec.span("hvd/update"):
            with rec.span("hvd/update/wait"):
                pass
    text = render({"table": {"0": {"trace": rec.digest()},
                             "1": {"trace": core.TraceRecorder().digest()}}})
    rows = [l for l in text.splitlines() if l.lstrip().startswith("hvd/")]
    assert [r.split()[0] for r in rows] == ["hvd/update", "hvd/update/wait"]
    assert all("rank 0:" in r and r.rstrip().endswith("x 2") for r in rows)
    assert "rank 1" not in "".join(rows)    # a rank with no span: no cell
