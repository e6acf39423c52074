"""The program's own spans (``hvd/...``, ``horovod_tpu.trace.span``) in a
traced run: what the five span-reading per-layer metrics share.  Not a
metric itself.

The spans are TraceMes, so they sit in the profile beside the device
operations, on one clock, each on its own thread's line.  They are read
from ``vm.trace.json.gz``, the rendering the profiler writes beside the
``.xplane.pb``: gzip and json, no jax, because ``report()`` of a launched
cell runs in the parent that must never open a backend — and because there
every event carries its thread's id, where the ``.xplane.pb`` names every
Python thread's line ``python``.

``load(ctx)`` reads the file once a run and keeps what ``reduce_spans``
makes of it in the context; ``reduce_spans`` is arithmetic on plain lists
and is what the tests check on known values.  A program without the spans
(a parent commit) gives ``None``, and so do the readers.

Calling thread (the one that holds ``bench/traced_window``), per
``hvd/update`` inside the window: its child spans summed by name; a metric
is the median over the updates.  Engine's cycle thread (the one that holds
``hvd/cycle``; with the inline kick of a one-process world that is the
calling thread): ``hvd/cycle/negotiate`` of the cycles with ``n`` > 0 and
``hvd/cycle/dispatch``, clipped to the window and summed.  The first
device's idle time is split a second time over the innermost ``hvd/update``
span of the calling thread, and a third over the innermost ``hvd/cycle``
span of the cycle thread: both tables go to the run's ``notes``.  Unlike
``trace_reduce.idle_gaps``, which gives a gap whole to the span open when
it BEGAN, these give each span the idle time that passed while it was the
innermost one open: an eager step's gaps outlast the phases (the gap that
begins at the fused program's launch runs on through wait and unpack).
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import statistics
from collections import defaultdict

from . import trace_reduce as tr

UPDATE = "hvd/update"
CYCLE = "hvd/cycle"
NEGOTIATE = "hvd/cycle/negotiate"
DISPATCH = "hvd/cycle/dispatch"
PHASES = ("stage", "submit", "wait", "unpack", "inner")
OUTSIDE_UPDATE = "(outside hvd/update)"
ENGINE_IDLE = "(engine thread idle)"


def read_events(path):
    """({device: [(start_s, end_s)]}, [(name, start_s, end_s, thread,
    args)]) from a ``*.trace.json.gz``.  A device is a process named
    ``/device:...`` and its operations the events of its ``XLA Ops``
    thread; in a rehearsal on the CPU the host events that carry an
    ``hlo_op`` stand in, as in ``trace_reduce.read_planes``."""
    with gzip.open(path, "rt") as fh:
        events = json.load(fh)["traceEvents"]
    process, thread = {}, {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            process[e["pid"]] = e["args"]["name"]
        elif e.get("ph") == "M" and e.get("name") == "thread_name":
            thread[e["pid"], e["tid"]] = e["args"]["name"]
    device_ops, host, stand_in = defaultdict(list), [], []
    for e in events:
        if e.get("ph") != "X":
            continue
        a = e["ts"] * 1e-6
        b = a + e.get("dur", 0.0) * 1e-6
        plane = process.get(e["pid"], "")
        if plane.startswith("/device:"):
            if thread.get((e["pid"], e["tid"])) == tr.OPS_LINE:
                device_ops[plane].append((a, b))
        elif plane.startswith("/host:"):
            args = e.get("args") or {}
            if "hlo_op" in args:
                stand_in.append((a, b))
            elif b > a:
                host.append((e["name"], a, b, (e["pid"], e["tid"]), args))
    if not device_ops and stand_in:
        device_ops = {"/host:CPU (rehearsal stand-in)": stand_in}
    return dict(device_ops), host


def inside(events, lo, hi):
    return [e for e in events if e[1] >= lo and e[2] <= hi]


def innermost(events, lo, hi, nowhere):
    """[(start, end, name)] covering [lo, hi] in order: the innermost of
    one thread's (nested) spans open at each moment, ``nowhere`` where
    none is."""
    out, stack, at = [], [], lo

    def emit(until):
        nonlocal at
        until = min(max(until, lo), hi)
        if until > at:
            out.append((at, until, stack[-1][0] if stack else nowhere))
            at = until

    def close_until(t):
        while stack and stack[-1][2] <= t:
            emit(stack[-1][2])          # to its end under its own name
            stack.pop()

    for e in sorted(events, key=lambda e: (e[1], -e[2])):
        close_until(e[1])
        emit(e[1])
        stack.append(e)
    close_until(float("inf"))
    emit(hi)
    return out


def idle_by_span(busy, events, lo, hi, nowhere, top=10):
    """The idle time of [lo, hi] (``busy``: disjoint, sorted) by the
    innermost span open while it passed, most first."""
    gaps, by_name, j = tr.subtract([(lo, hi)], busy), defaultdict(float), 0
    for a, b, name in innermost(events, lo, hi, nowhere):
        while j < len(gaps) and gaps[j][1] <= a:
            j += 1
        k = j
        while k < len(gaps) and gaps[k][0] < b:
            by_name[name] += min(b, gaps[k][1]) - max(a, gaps[k][0])
            k += 1
    return sorted(([n, s] for n, s in by_name.items() if s > 0),
                  key=lambda e: (-e[1], e[0]))[:top]


def reduce_spans(device_ops, host_events, steps):
    """``device_ops``: {device: [(start_s, end_s)]}; ``host_events``:
    [(name, start_s, end_s, thread, args)]; ``steps``: the traced steps.
    ``None`` where no ``hvd/`` span was recorded."""
    spans = [e for e in host_events if e[0].startswith("hvd/")]
    if not spans:
        return None
    windows = [e for e in host_events if e[0] == tr.WINDOW_SPAN]
    every = [i for ops in device_ops.values() for i in ops]
    if windows:
        lo, hi = windows[0][1:3]
        caller = windows[0][3]
    else:
        lo = min(e[1] for e in spans)
        hi = max(e[2] for e in spans)
        caller = next((e[3] for e in spans if e[0] == UPDATE), None)
    # as ``trace_reduce.reduce_events`` closes its window: at the end of
    # the last operation that began inside it
    hi_dev = max([hi] + [b for a, b in every if a < hi])

    # ---- calling thread: one row an update, child spans summed by name
    mine = [e for e in spans if e[3] == caller]
    updates = []
    for u in inside([e for e in mine if e[0] == UPDATE], lo, hi):
        row = {p: 0.0 for p in PHASES}
        for e in inside(mine, u[1], u[2]):
            phase = e[0][len(UPDATE) + 1:]
            if phase in row:
                row[phase] += e[2] - e[1]
        row["update"] = u[2] - u[1]
        updates.append(row)

    # ---- cycle thread: negotiation while a tensor waited, and dispatch
    cycles = [e for e in spans if e[0] == CYCLE]
    engine = {e[3] for e in cycles}
    loaded = [e for e in cycles if int(e[4].get("n", 0) or 0) > 0]

    def clipped(name, within=None):
        total = 0.0
        for e in spans:
            if e[0] != name or e[3] not in engine:
                continue
            if within is not None and not any(
                    c[3] == e[3] and c[1] <= e[1] and e[2] <= c[2]
                    for c in within):
                continue
            total += max(0.0, min(e[2], hi) - max(e[1], lo))
        return total

    # ---- the first device's idle time, by program span
    busy = tr.union(tr.clip(device_ops[sorted(device_ops)[0]], lo, hi_dev)) \
        if device_ops else []

    out = {
        "updates": updates, "steps": steps, "window_s": hi - lo,
        "cycles": len(inside(cycles, lo, hi)),
        "cycles_with_tensors": len(inside(loaded, lo, hi)),
        "negotiate_s": clipped(NEGOTIATE, loaded),
        "dispatch_s": clipped(DISPATCH),
        "idle_by_update_span": idle_by_span(
            busy, [e for e in mine if e[0].startswith(UPDATE)], lo, hi_dev,
            OUTSIDE_UPDATE),
        "idle_by_cycle_span": idle_by_span(
            busy, [e for e in spans
                   if e[3] in engine and e[0].startswith(CYCLE)],
            lo, hi_dev, ENGINE_IDLE),
    }
    bench_update = [e[2] - e[1] for e in inside(host_events, lo, hi)
                    if e[0] == "bench/update" and e[3] == caller]
    if updates:
        med = {k: statistics.median(r[k] for r in updates)
               for k in PHASES + ("update",)}
        out["median_ms"] = {k: v * 1e3 for k, v in med.items()}
        # the builder's checks: the five phases cover hvd/update, and
        # hvd/update is the benchmark's own span around the same call
        out["phases_over_update"] = (
            sum(sum(r[p] for p in PHASES) for r in updates)
            / sum(r["update"] for r in updates))
        if bench_update:
            out["update_over_bench_update"] = (
                sum(r["update"] for r in updates) / sum(bench_update))
    return out


def load(ctx):
    """What ``reduce_spans`` finds in this run's trace, read once and kept
    in the context; the idle tables and the checks go to its ``notes``."""
    if "program_spans" in ctx:
        return ctx["program_spans"]
    found, trace = None, ctx.get("trace")
    if trace and trace.get("path"):
        rendered = sorted(glob.glob(os.path.join(
            os.path.dirname(trace["path"]), "*.trace.json.gz")))
        if rendered:
            found = reduce_spans(*read_events(rendered[-1]), trace["steps"])
    ctx["program_spans"] = found
    if found:
        ctx.setdefault("notes", {})["program_spans"] = {
            k: found[k] for k in (
                "median_ms", "phases_over_update", "update_over_bench_update",
                "cycles", "cycles_with_tensors", "idle_by_update_span",
                "idle_by_cycle_span") if k in found}
    return found


def median_ms(ctx, *phases):
    """Median over the traced updates of the named phases' summed time."""
    found = load(ctx)
    if not found or not found["updates"]:
        return None
    return statistics.median(
        sum(r[p] for p in phases) for r in found["updates"]) * 1e3


def per_step_ms(ctx, key):
    """A cycle-thread total of the traced window over the traced steps."""
    found = load(ctx)
    if not found or not found["steps"]:
        return None
    return found[key] / found["steps"] * 1e3
