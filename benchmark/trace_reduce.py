"""From a profiler trace (``.xplane.pb``) to numbers: the only reader of
traces in the benchmark.

``reduce_file`` reads the planes with ``jax.profiler.ProfileData`` and
hands plain lists to ``reduce_events``, which is arithmetic on intervals
and is what the tests check on known values:

- busy: the union of the intervals in which an operation ran on a device,
  inside the traced window, averaged over the devices; idle share is
  1 - busy / window.
- per-operation totals, by name.
- collective time, and the part of it during which no other operation ran
  on that device (exposed).
- kernel time: custom calls whose target is ``tpu_custom_call``, which is
  what a Pallas kernel lowers to (XLA's own ``ConcatBitcast`` custom calls
  and the like are not kernels).
- idle gaps of the first device, each given to the innermost host span
  that was open when the gap began, summed by that span's name.
- the benchmark's own host spans (``bench/...``), durations by name.

The traced window is the host span ``bench/traced_window`` where there is
one, else from the first device operation to the end of the last.

A device is a plane named ``/device:...`` and its operations are the events
of its ``XLA Ops`` line.  The CPU backend of a rehearsal has no such plane:
there the events that carry an ``hlo_op`` stat stand in for them, so that
the code is walked; nothing read that way is a device number.
"""

from __future__ import annotations

import re
from collections import defaultdict

WINDOW_SPAN = "bench/traced_window"
# HLO's collectives; ``psum`` is how the CPU backend of a rehearsal names
# its all-reduce thunk.
COLLECTIVE = re.compile(r"^(all-reduce|all-gather|reduce-scatter|all-to-all|"
                        r"collective-permute|collective-broadcast|psum)")
KERNEL_TARGETS = ("tpu_custom_call",)       # what a Pallas kernel lowers to
OPS_LINE = "XLA Ops"
# "%fusion.5 = bf16[8,128]{...} fusion(...), kind=..." as the TPU names an
# operation: its name, its first result shape, its opcode.
HLO = re.compile(r"^%?(?P<name>[\w.\-]+) = \(?(?P<shape>\w+\[[\d,]*\])?.*?"
                 r"\s(?P<opcode>[\w\-]+)\(")
TARGET = re.compile(r'custom_call_target="([^"]+)"')


def describe(text):
    """(label, opcode, custom-call target) of a device event's name.  A
    name that is not HLO text (the CPU's, a test's) is its own label and
    its opcode is the name without a trailing ``.N``."""
    m = HLO.match(text)
    if not m:
        return text, re.sub(r"\.\d+$", "", text.lstrip("%")), None
    target = TARGET.search(text) if m["opcode"] == "custom-call" else None
    parts = [m["name"], target.group(1) if target else None, m["shape"]]
    return (" ".join(p for p in parts if p), m["opcode"],
            target.group(1) if target else None)


# ------------------------------------------------------- interval arithmetic
def union(intervals):
    """Sorted, disjoint intervals covering the same points."""
    out = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def length(intervals):
    return sum(b - a for a, b in intervals)


def clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def subtract(intervals, cover):
    """The parts of ``intervals`` (disjoint, sorted) not under ``cover``
    (disjoint, sorted)."""
    out, j = [], 0
    for a, b in intervals:
        while j < len(cover) and cover[j][1] <= a:
            j += 1
        k, at = j, a
        while k < len(cover) and cover[k][0] < b:
            if cover[k][0] > at:
                out.append((at, cover[k][0]))
            at = max(at, cover[k][1])
            k += 1
        if at < b:
            out.append((at, b))
    return out


def pair_async(ops):
    """A collective issued as ``X-start`` ... ``X-done`` lasts from the
    start's beginning to the done's end; others as they stand."""
    open_starts, out = defaultdict(list), []
    for opcode, a, b in sorted(ops, key=lambda e: e[1]):
        m = COLLECTIVE.match(opcode)
        if not m:
            continue
        kind = m.group(1)
        if opcode == kind + "-start":
            open_starts[kind].append(a)
        elif opcode == kind + "-done" and open_starts[kind]:
            out.append((open_starts[kind].pop(0), b))
        else:
            out.append((a, b))
    return out


# ----------------------------------------------------------------- reduction
def reduce_events(device_ops, host_events, top=10):
    """``device_ops``: {device: [(name, start_s, end_s)]}; ``host_events``:
    [(name, start_s, end_s[, thread])] of the host threads.  Times in
    seconds on one clock."""
    windows = [e[1:3] for e in host_events if e[0] == WINDOW_SPAN]
    threads = {e[3] for e in host_events if e[0] == WINDOW_SPAN and len(e) > 3}
    own = [e for e in host_events if len(e) < 4 or not threads
           or e[3] in threads]
    every = [e for ops in device_ops.values() for e in ops]
    if windows:
        lo, hi = windows[0]
        hi = max([hi] + [b for _, a, b in every if a < hi])
    elif every:
        lo, hi = min(a for _, a, _ in every), max(b for _, _, b in every)
    else:
        return {"window_s": 0.0, "busy_s": 0.0, "devices": 0}

    busy, collective, exposed, custom = [], [], [], []
    totals = defaultdict(float)
    first_busy = None
    for dev in sorted(device_ops):
        described = [(describe(n), a, b) for n, a, b in device_ops[dev]]
        ops = [(d, max(a, lo), min(b, hi)) for d, a, b in described
               if min(b, hi) > max(a, lo)]
        cover = union((a, b) for _, a, b in ops)
        busy.append(length(cover))
        if first_busy is None:
            first_busy = cover
        for (label, _, _), a, b in ops:
            totals[label] += b - a
        coll = union(clip(pair_async(
            [(d[1], a, b) for d, a, b in described]), lo, hi))
        compute = union((a, b) for (_, opcode, _), a, b in ops
                        if not COLLECTIVE.match(opcode))
        collective.append(length(coll))
        exposed.append(length(subtract(coll, compute)))
        custom.append(sum(b - a for (_, _, target), a, b in ops
                          if target in KERNEL_TARGETS))

    mean = lambda xs: sum(xs) / len(xs) if xs else 0.0
    spans = defaultdict(list)
    for e in host_events:
        if e[0].startswith("bench/"):
            spans[e[0]].append(e[2] - e[1])
    return {
        "window_s": hi - lo, "devices": len(device_ops),
        "busy_s": mean(busy),
        "idle_pct": 100.0 * (1.0 - mean(busy) / (hi - lo)),
        "collective_s": mean(collective),
        "collective_exposed_s": mean(exposed),
        "custom_call_s": mean(custom),
        "device_ops": sorted(([n, s / max(len(device_ops), 1)]
                              for n, s in totals.items()),
                             key=lambda e: -e[1])[:top],
        "device_op_kinds": len(totals),
        "idle_gaps": idle_gaps(first_busy or [], own, lo, hi, top),
        "host_spans": dict(spans),
    }


def idle_gaps(busy, host_events, lo, hi, top=10):
    """The first device's idle time inside [lo, hi], summed by the
    innermost span of the benchmark's own thread (the one that holds the
    window span; every thread where there is none) open when each gap
    began.  Spans of one thread nest, so a stack follows them."""
    gaps = subtract([(lo, hi)], busy)
    events = sorted((e[:3] for e in host_events if e[2] > lo and e[1] < hi),
                    key=lambda e: (e[1], -e[2]))
    by_name = defaultdict(float)
    stack, nxt = [], 0
    for a, b in gaps:
        while nxt < len(events) and events[nxt][1] <= a:
            stack.append(events[nxt])
            nxt += 1
        while stack and stack[-1][2] <= a:
            stack.pop()
        # an ended span under an open one of another thread: skip it
        inner = next((e for e in reversed(stack) if e[2] > a), None)
        by_name[inner[0] if inner else "(no host span)"] += b - a
    return sorted(([n, s] for n, s in by_name.items()),
                  key=lambda e: -e[1])[:top]


# ------------------------------------------------------------------- reading
def read_planes(path):
    """({device: [(name, start_s, end_s)]},
    [(name, start_s, end_s, thread)])."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    device_ops, host, stand_in = {}, [], []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops[plane.name] = [
                        (e.name, e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    span = (e.name, e.start_ns * 1e-9,
                            (e.start_ns + e.duration_ns) * 1e-9, line.name)
                    if any(k == "hlo_op" for k, _ in e.stats):
                        stand_in.append(span[:3])
                    elif e.duration_ns > 0:
                        host.append(span)
    if not device_ops and stand_in:
        device_ops = {"/host:CPU (rehearsal stand-in)": stand_in}
    return device_ops, host


def reduce_file(path, top=10):
    device_ops, host = read_planes(path)
    if not device_ops:
        raise RuntimeError(f"no device operation in the trace {path}")
    return reduce_events(device_ops, host, top)
