"""Span core of the distributed collective tracer (no jax imports).

A gradient's latency in the background-coordinator design is spread across
five host-side phases that the per-rank chrome timeline (N10) and the
monitor's scalar counters cannot attribute:

    queue       enqueue          -> first cycle drain
    negotiation first drain      -> globally-ready verdict
    copy_in     ready            -> fused program dispatched (the fusion
                                    copy-in / program fetch+launch)
    reduce      dispatch         -> device results settled (the collective
                                    itself, as the host observes it)
    drain       settle begin     -> waiter released (done.set)

The engine stamps monotonic timestamps at each boundary into a
:class:`TensorSpan` claimed from a preallocated ring (:class:`TraceRecorder`)
— zero allocation on the hot path (span objects are reused in place), and
strictly zero cost when tracing is disarmed (``engine.tracer is None``; every
stamp site is a single attribute check, the same contract the timeline and
monitor hooks follow).

Cross-rank correlation key: the **negotiation cycle id** (the controller's
lock-step round counter, identical on every rank for the same round — the
single-controller engine falls back to its local cycle index) plus the
response-cache **slot id** when one is known.  The merge tool
(``python -m horovod_tpu.trace``) joins per-rank trace files on the cycle id
and draws flow arrows tying the same cycle across ranks' lanes.

**Program spans** (:meth:`TraceRecorder.span`, :func:`span`) are the second
kind of record: one interval of one thread between two of the program's own
layer boundaries (``hvd/update/stage``, ``hvd/cycle/negotiate``, ...), not
one tensor's lifecycle.  Armed, a span is a ``jax.profiler.TraceAnnotation``
(a TraceMe: with a profiler session active it lands in the ``.xplane.pb`` on
the device operations' clock, on its own thread's line) and its duration is
added to the recorder's sum and count by name, which ride the summary and
the digest below so that a fleet without a profiler still gets the totals.
Disarmed there is no recorder: :func:`span` hands out the shared
:data:`OFF` and the engine's sites are the ``tracer is None`` check they
already make.

Compact per-cycle digests (:meth:`TraceRecorder.digest`) ride the existing
MON1 monitor side-channel inside the agent's JSON snapshot — interval-gated,
size-capped (``DIGEST_*`` caps below), and version-safe (old peers ignore
unknown snapshot keys).

**The start-up record** (:func:`startup_span`, :func:`startup`) is the third
kind, kept whether or not ``HOROVOD_TRACE`` is set: a dozen named intervals
of one process between its start and the end of ``hvd.init()`` (the
launcher's own three too), on ``time.time()`` — the clock a launcher and its
ranks share on one host and jax's compile events carry — so set-up can be
read from inside the program.  It costs a stamp a site at start-up and
nothing on a step's path.  :func:`startup` returns it with the compile
ledger (``common/compile_cache.py``); one JSON line a process goes beside
the compile cache (:func:`write_startup`).
"""

from __future__ import annotations

import functools
import os
import sys
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

# Phase names, in lifecycle order.  The wire/digest/JSON key order
# everywhere else follows this tuple.
PHASES = ("queue", "negotiation", "copy_in", "reduce", "drain")

# Sub-legs of the ``reduce`` phase for two-level (hierarchical) dispatches
# (ISSUE 17): the host cannot stamp inside one XLA launch, so the engine
# stamps each hier span with the MODELED cross-link share of its wire time
# (``parallel.topology.cross_fraction`` — DCN bytes over total bytes) and
# the recorder splits the measured reduce duration accordingly.  Flat
# spans carry cross_frac 0.0 and never touch the leg accumulators, so the
# legs partition exactly the hier share of ``reduce``:
#     reduce_intra  ICI legs (intra-slice reduce-scatter + allgather)
#     reduce_cross  DCN leg  (cross-slice allreduce over the leader ring)
REDUCE_LEGS = ("reduce_intra", "reduce_cross")

# Span stamp keys on the wire (writer span lines), in lifecycle order:
# enqueue, drain, ready, launch, result, finished.  PHASES[i] spans
# STAMPS[i] -> STAMPS[i+1].  THE single definition — the writer, the merge
# tool and the analyzer all key off this tuple.
STAMPS = ("e", "d", "r", "l", "x", "f")


def phases_from_stamps(stamps) -> Dict[str, float]:
    """Per-phase microseconds from the six lifecycle stamps (monotonic
    seconds, 0.0 = not reached), carrying the last reached stamp forward
    past missing ones — an aborted span's elapsed time lands in the phase
    that actually contains it instead of vanishing.  THE one attribution
    rule: ``TensorSpan.phases_us`` (live recorder/digest) and the offline
    analyzer both call this, so reports can never disagree on partially
    stamped spans."""
    out: Dict[str, float] = {}
    prev = stamps[0]
    for phase, t in zip(PHASES, stamps[1:]):
        if t and prev:
            out[phase] = max(0.0, (t - prev) * 1e6)
            prev = t
        else:
            out[phase] = 0.0
    return out

# Per-phase histogram buckets (microseconds): spans the inline-kick fast
# path through a slow multi-host negotiation round.  Mirrors the monitor
# registry's default cycle-time buckets so /metrics phase histograms read
# on the same scale as hvd_cycle_time_us.
PHASE_BUCKETS_US: Tuple[float, ...] = (
    50.0, 100.0, 250.0, 500.0, 1000.0, 2500.0, 5000.0, 10000.0, 50000.0,
    250000.0, 1000000.0)

# MON1 digest caps: recent cycle rows and open-span entries shipped per
# snapshot.  The rendered digest stays well under the agent's 48KB blob
# guard (tests pin a hard byte cap).
DIGEST_MAX_CYCLES = 24
DIGEST_MAX_OPEN = 8


class TensorSpan:
    """One tensor's lifecycle through one collective (ring slot, reused).

    Timestamps are ``time.monotonic()`` seconds; 0.0 means "not reached".
    ``cycle`` is the cross-rank correlation id (negotiation round), ``slot``
    the response-cache slot (-1 unknown).
    """

    __slots__ = ("name", "cycle", "slot", "t_enqueue", "t_drain", "t_ready",
                 "t_launch", "t_result", "t_done", "error", "committed",
                 "cross_frac", "prefetch")

    def __init__(self):
        self.reset("", 0.0, 0.0)
        self.committed = True     # a fresh slot is reclaimable

    def reset(self, name: str, t_enqueue: float, t_drain: float) -> None:
        self.name = name
        self.cycle = -1
        self.slot = -1
        self.t_enqueue = t_enqueue
        self.t_drain = t_drain
        self.t_ready = 0.0
        self.t_launch = 0.0
        self.t_result = 0.0
        self.t_done = 0.0
        self.error = False
        self.committed = False
        # Modeled DCN share of the reduce phase; 0.0 = flat dispatch.
        self.cross_frac = 0.0
        # FSDP parameter-prefetch gather (ISSUE 18): stamped at backlog
        # push for PREFETCH-lane batches; its reduce time feeds the
        # "prefetch" leg of the phase breakdown (prefetch-depth tuning).
        self.prefetch = False

    def phase_name(self) -> str:
        """The phase this span is currently in (stall attribution)."""
        if self.t_done:
            return "done"
        if self.t_result:
            return "drain"
        if self.t_launch:
            return "reduce"
        if self.t_ready:
            return "copy_in"
        if self.t_drain:
            return "negotiation"
        return "queue"

    def phases_us(self) -> Dict[str, float]:
        """Per-phase durations in microseconds, over the stamped prefix of
        the lifecycle (an aborted span yields zeros past its last stamp).
        The sum equals ``lifecycle_us`` exactly when every stamp landed."""
        return phases_from_stamps((self.t_enqueue, self.t_drain,
                                   self.t_ready, self.t_launch,
                                   self.t_result, self.t_done))

    def lifecycle_us(self) -> float:
        end = self.t_done or self.t_result or self.t_launch or \
            self.t_ready or self.t_drain
        start = self.t_enqueue or self.t_drain
        return max(0.0, (end - start) * 1e6) if end and start else 0.0


class CycleRecord:
    """One coordinator cycle's stamps plus the per-phase sums of the spans
    it carried (filled in as those spans commit — possibly cycles later,
    when the in-flight window is deep)."""

    __slots__ = ("cycle", "t0", "t_drain", "t_ready", "t_dispatch",
                 "n_tensors", "negotiation_us", "phase_us", "n_committed")

    def __init__(self, cycle: int, t0: float, t_drain: float, t_ready: float,
                 t_dispatch: float, n_tensors: int, negotiation_us: float):
        self.cycle = cycle
        self.t0 = t0
        self.t_drain = t_drain
        self.t_ready = t_ready
        self.t_dispatch = t_dispatch
        self.n_tensors = n_tensors
        self.negotiation_us = negotiation_us
        self.phase_us = [0.0] * len(PHASES)
        self.n_committed = 0

    def digest_row(self) -> list:
        """Compact wire row: [cycle, n_tensors, q, neg, cpy, red, drn] —
        phase sums rounded to whole microseconds."""
        return [self.cycle, self.n_tensors] + \
            [int(round(v)) for v in self.phase_us]


class _Off:
    """What :func:`span` hands out while tracing is disarmed: one shared
    object, entered and left without a record.  ``with span(..) as sp``
    binds None, so a site that labels its span late (``sp.set``) guards
    that with the one ``is not None`` check."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


OFF = _Off()


class ProgramSpan:
    """One armed program span (context manager): a TraceMe on the calling
    thread where the process has jax, and a duration for the recorder's
    by-name totals.  ``ids`` become the event's stats in the profile;
    :meth:`set` adds those known only once the span is open (a program
    cache hit, the round id a negotiation came back with).  The profile
    splits stats at commas: join a list with another character."""

    __slots__ = ("name", "_rec", "_ann", "_t0")

    def __init__(self, rec: "TraceRecorder", name: str, ids: dict):
        self.name = name
        self._rec = rec
        make = rec.annotation
        self._ann = make(name, **ids) if make is not None else None
        self._t0 = 0.0

    def set(self, **ids) -> None:
        if self._ann is not None:
            self._ann.set_metadata(**ids)

    def __enter__(self) -> "ProgramSpan":
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt_us = (time.perf_counter() - self._t0) * 1e6
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self._rec.add_span(self.name, dt_us)
        return False


# The recorder the calling thread's sites reach through :func:`span`: the
# one most recently installed (``trace.maybe_install``), None while
# tracing is disarmed or once that recorder closed.
_installed: Optional["TraceRecorder"] = None


def installed() -> Optional["TraceRecorder"]:
    return _installed


# Process-wide counts of the compiled inner update (``jax/optimizer.py``):
# ``compiled`` eager calls that went through a wrapper's compiled callable,
# ``traces`` of that callable, counted in Python from inside the traced
# function — a retrace every step reads ``traces == compiled``.  Kept with
# tracing armed or not; ``monitor/agent.py`` exports them.
inner_update = {"compiled": 0, "traces": 0}

# The same pair for a group's staging (``ops/eager.py`` ``_stage_group``,
# ``_stage_packed``): ``compiled`` members put into the engine's stacked
# layout by the one program over their group, ``traces`` of that program.
# A group whose shapes change from call to call costs one trace a distinct
# signature.  ``packed``: those of ``compiled`` that went in as part of one
# flat buffer a dtype (``_pack_leaves``: the eager gradient path) and not
# as a member each (``_stack_leaves``).
stage_group = {"compiled": 0, "traces": 0, "packed": 0}

# Call sites of the recurrent mixers' causal convolution
# (``models/gated_delta.py`` ``causal_conv_silu``), counted in Python at
# trace time: ``kernel`` those that took the Pallas kernel pair
# (``ops/causal_conv.py``: a TPU, a shape that fits its tiles), ``plain``
# those that kept XLA's code.  ``plain`` rising on a TPU is a shape the
# tiles do not fit.
causal_conv = {"kernel": 0, "plain": 0}

# The same pair for the Mamba-1 mixers' selective scan
# (``ops/selective_scan.py`` ``selective_scan``): ``kernel`` call sites took
# the Pallas kernel pair, ``plain`` the ``lax.scan`` over chunks of tokens.
# ``plain`` rising on a TPU is a shape the tiles do not fit.
selective_scan = {"kernel": 0, "plain": 0}

# The same pair for the Gated DeltaNet mixers' chunked delta rule
# (``models/gated_delta.py`` ``gated_delta_net``): ``kernel`` call sites
# took the Pallas kernel pair (``ops/delta_rule.py``: a TPU, key and value
# widths of 32 or more), ``padded`` those of them whose widths are no
# multiple of 128 and run rounded up on zero-padded heads (a subset of
# ``kernel``), ``plain`` XLA's code for all chunks at once.  ``plain``
# rising on a TPU is a shape the kernels do not take.
delta_rule = {"kernel": 0, "padded": 0, "plain": 0}

# The dropless expert layer's blocks (``models/moe.py``
# ``dropless_moe_ffn``), added up in Python once a traced call site:
# ``sites`` the call sites, ``blocks`` the equal blocks their sorted
# assignments are cut into, ``block_rows`` the rows of a block.  How many of
# a site's blocks a call computes hangs on the routing:
# ``moe.live_blocks`` gives it from the counts the layer returns.
expert_blocks = {"sites": 0, "blocks": 0, "block_rows": 0}

# The flash attention kernels' block schedules (``ops/flash_attention.py``
# ``_schedule``), added up in Python once a traced kernel call, a head:
# ``grid`` the blocks of the dense grid, ``steps`` the steps the schedule
# keeps of it (a causal call a little over half).
flash_blocks = {"grid": 0, "steps": 0}

# Call sites of a family whose attention differs by layer kind
# (``models/laguna.py`` ``_attention_block``; ``models/joyai.py``'s latent
# attention, keys of one width and values of another), counted in Python
# at trace time by the layer's kind and the path it took: ``*_flash`` the
# Pallas kernels (``ops/flash_attention.py``; a window layer's walk only
# its band's blocks), ``*_plain`` XLA's masked softmax.  ``*_plain`` rising
# on a TPU is a sequence under the kernels' crossover or a config that
# turned them off.
attention = {"full_flash": 0, "full_plain": 0, "window_flash": 0,
             "window_plain": 0, "latent_flash": 0, "latent_plain": 0}

# The one table of the process-wide series: name -> (kind, help, read,
# label).  ``read()`` gives a number, or with a ``label`` a dict from the
# label's value to a number (``hvd_startup_seconds{phase="hvd/init"}``).
# ``monitor/agent.py`` walks it at every snapshot; a module that keeps a
# process-wide count registers it here and the exporter needs no edit.
SERIES: Dict[str, tuple] = {}


def register_series(name: str, kind: str, help: str, read: Callable,
                    label: Optional[str] = None) -> None:
    """``kind`` is ``counter`` or ``gauge``."""
    SERIES[name] = (kind, help, read, label)


def _register_counts(prefix: str, counts: dict, helps: dict) -> None:
    for key, text in helps.items():
        register_series(f"{prefix}_{key}_total", "counter", text,
                        lambda key=key: counts[key])


# traces rising with calls is a retrace every step
_register_counts("hvd_inner_update", inner_update, {
    "compiled": "eager inner updates run as one compiled program",
    "traces": "traces of the compiled inner update"})
# members that went through the one program over their group, traces of
# it, and the members it packed into one flat buffer a dtype
_register_counts("hvd_stage_group", stage_group, {
    "compiled": "group members staged by one compiled program",
    "traces": "traces of the staging program",
    "packed": "group members staged inside one flat buffer a dtype"})
_register_counts("hvd_causal_conv", causal_conv, {
    "kernel": "causal convolution call sites traced as the Pallas kernels",
    "plain": "causal convolution call sites traced as XLA's own code"})
_register_counts("hvd_selective_scan", selective_scan, {
    "kernel": "selective scan call sites traced as the Pallas kernels",
    "plain": "selective scan call sites traced as XLA's own code"})
_register_counts("hvd_delta_rule", delta_rule, {
    "kernel": "chunked delta rule call sites traced as the Pallas kernels",
    "padded": "chunked delta rule call sites traced as the Pallas kernels "
              "at widths rounded up to whole lanes",
    "plain": "chunked delta rule call sites traced as XLA's own code"})
_register_counts("hvd_expert_blocks", expert_blocks, {
    "sites": "dropless expert layer call sites traced",
    "blocks": "blocks the traced expert layers cut their sorted "
              "assignments into",
    "block_rows": "rows of a block, added over the traced expert layers"})
_register_counts("hvd_attention", attention, {
    "full_flash": "full-attention call sites traced as the flash kernels",
    "full_plain": "full-attention call sites traced as XLA's own code",
    "window_flash": "window-attention call sites traced as the flash "
                    "kernels",
    "window_plain": "window-attention call sites traced as XLA's own code",
    "latent_flash": "latent-attention call sites traced as the flash "
                    "kernels",
    "latent_plain": "latent-attention call sites traced as XLA's own code"})
_register_counts("hvd_flash_blocks", flash_blocks, {
    "grid": "blocks a head of the dense grids of traced flash kernel calls",
    "steps": "steps a head the flash kernels' schedules keep of those grids"})


def span(name: str, **ids):
    """A program span on the calling thread: ``with trace.span("hvd/update/
    wait", group=gid): ...``.  Disarmed (``HOROVOD_TRACE`` unset, no
    recorder installed) this is one ``is None`` check and the shared
    :data:`OFF`."""
    rec = _installed
    if rec is None:
        return OFF
    return ProgramSpan(rec, name, ids)


class TraceRecorder:
    """Preallocated span ring + phase accumulators + optional file writer.

    One recorder per engine; built by :func:`horovod_tpu.trace.maybe_install`
    when ``HOROVOD_TRACE`` arms tracing.  ``begin`` runs on the cycle thread;
    ``commit`` on the cycle thread or the in-flight watcher — both take one
    short lock.  Ring slots are recycled oldest-committed-first; if every
    scanned slot is still open (pathologically deep in-flight windows) the
    claim is dropped and counted, never blocked.
    """

    # Bounded forward scan for a reclaimable slot before dropping a claim.
    _SCAN = 64

    def __init__(self, capacity: int = 4096, cycle_capacity: int = 512,
                 writer=None, rank: int = 0, annotation=None):
        self.rank = int(rank)
        # What opens a program span's TraceMe: jax.profiler.TraceAnnotation
        # where the installing process has jax (``maybe_install`` resolves
        # it), None in a jax-free one, which keeps the totals alone.
        self.annotation = annotation
        # Program spans by name: [sum_us, count].
        self._span_totals: Dict[str, List[float]] = {}
        self.capacity = max(16, int(capacity))
        self.cycle_capacity = max(16, int(cycle_capacity))
        self.buckets = PHASE_BUCKETS_US
        self._writer = writer
        self._lock = threading.Lock()
        self._ring: List[TensorSpan] = [TensorSpan()
                                        for _ in range(self.capacity)]
        self._next = 0
        self.dropped = 0
        self.spans_committed = 0
        # Per-phase accumulators: sum_us, count, per-bucket counts
        # (len(buckets)+1, last = +Inf overflow).
        self._phase_sum = {p: 0.0 for p in PHASES}
        self._phase_buckets = {p: [0] * (len(self.buckets) + 1)
                               for p in PHASES}
        # Two-level reduce legs (REDUCE_LEGS): fed only by spans whose
        # cross_frac > 0 — the flat path never touches these, so their
        # absence from a digest proves no hier dispatch happened.
        self._leg_sum = {p: 0.0 for p in REDUCE_LEGS}
        self._leg_buckets = {p: [0] * (len(self.buckets) + 1)
                             for p in REDUCE_LEGS}
        self.leg_spans = 0
        # FSDP prefetch leg (ISSUE 18): reduce-phase time of PREFETCH-lane
        # gathers, keyed "prefetch" in phase_histograms once any commits —
        # the phase-breakdown signal HOROVOD_PREFETCH_DEPTH tunes against.
        self._prefetch_sum = 0.0
        self._prefetch_buckets = [0] * (len(self.buckets) + 1)
        self.prefetch_spans = 0
        self.lifecycle_us_total = 0.0
        # Recent cycles, newest last; _cycle_by_id lets late span commits
        # find their cycle's aggregate.
        self._cycles: List[CycleRecord] = []
        self._cycle_by_id: Dict[int, CycleRecord] = {}
        # Wall/monotonic anchor pair: maps this process's monotonic stamps
        # onto a shareable time base for the cross-rank merge.
        self.anchor_wall = time.time()
        self.anchor_mono = time.monotonic()
        if writer is not None:
            writer.header(rank=self.rank, anchor_wall=self.anchor_wall,
                          anchor_mono=self.anchor_mono)

    # ------------------------------------------------------------ recording
    def begin(self, name: str, t_enqueue: float,
              t_drain: float) -> Optional[TensorSpan]:
        """Claim a ring slot for a tensor entering negotiation.  Returns
        None (claim dropped, counted) when no committed slot is found
        within the bounded scan."""
        with self._lock:
            for _ in range(min(self._SCAN, self.capacity)):
                span = self._ring[self._next]
                self._next = (self._next + 1) % self.capacity
                if span.committed:
                    span.reset(name, t_enqueue, t_drain)
                    return span
            self.dropped += 1
            return None

    def _count(self, counts: List[int], v: float) -> None:
        """One observation into a per-bucket count list (the last slot is
        the +Inf overflow)."""
        for i, le in enumerate(self.buckets):
            if v <= le:
                counts[i] += 1
                return
        counts[-1] += 1

    def commit(self, span: Optional[TensorSpan]) -> None:
        """Finalize a span: accumulate its phases, fold them into its
        cycle's aggregate, emit it to the trace file.  Idempotent; must
        never raise past its own guard (callers sit on settle paths)."""
        if span is None or span.committed:
            return
        phases = span.phases_us()
        w = self._writer
        record = None
        with self._lock:
            if span.committed:          # racing commit lost
                return
            if w is not None:
                # Snapshot BEFORE flipping committed: the flip makes the
                # slot reclaimable, and a concurrent begin() (which only
                # recycles committed slots, under this lock) could reset
                # the fields mid-write otherwise.
                record = (span.name, span.cycle, span.slot, span.t_enqueue,
                          span.t_drain, span.t_ready, span.t_launch,
                          span.t_result, span.t_done, span.error,
                          span.cross_frac)
            span.committed = True
            self.spans_committed += 1
            self.lifecycle_us_total += span.lifecycle_us()
            for p, v in phases.items():
                self._phase_sum[p] += v
                self._count(self._phase_buckets[p], v)
            frac = span.cross_frac
            if frac > 0.0:
                # Split the measured reduce duration into the modeled
                # ICI/DCN legs; together they re-add to reduce exactly.
                self.leg_spans += 1
                red = phases["reduce"]
                for leg, v in ((REDUCE_LEGS[0], red * (1.0 - frac)),
                               (REDUCE_LEGS[1], red * frac)):
                    self._leg_sum[leg] += v
                    self._count(self._leg_buckets[leg], v)
            if span.prefetch:
                self.prefetch_spans += 1
                self._prefetch_sum += phases["reduce"]
                self._count(self._prefetch_buckets, phases["reduce"])
            rec = self._cycle_by_id.get(span.cycle)
            if rec is not None:
                rec.n_committed += 1
                for i, p in enumerate(PHASES):
                    rec.phase_us[i] += phases[p]
        if record is not None:
            w.span_record(*record)

    def cycle(self, cycle: int, t0: float, t_drain: float, t_ready: float,
              t_dispatch: float, n_tensors: int,
              negotiation_us: float) -> None:
        """Record one coordinator cycle that carried tensors."""
        rec = CycleRecord(cycle, t0, t_drain, t_ready, t_dispatch,
                          n_tensors, negotiation_us)
        with self._lock:
            self._cycles.append(rec)
            self._cycle_by_id[cycle] = rec
            if len(self._cycles) > self.cycle_capacity:
                old = self._cycles.pop(0)
                self._cycle_by_id.pop(old.cycle, None)
        w = self._writer
        if w is not None:
            w.cycle(rec)

    def span(self, name: str, **ids) -> ProgramSpan:
        """A program span on the calling thread (see :func:`span`); the
        engine's sites open theirs through their recorder."""
        return ProgramSpan(self, name, ids)

    def add_span(self, name: str, dt_us: float) -> None:
        with self._lock:
            tot = self._span_totals.get(name)
            if tot is None:
                tot = self._span_totals[name] = [0.0, 0]
            tot[0] += dt_us
            tot[1] += 1

    # -------------------------------------------------------------- reading
    def span_totals(self) -> Dict[str, Tuple[float, int]]:
        """Program spans by name -> (sum_us, count), cumulative."""
        with self._lock:
            return {n: (t[0], int(t[1]))
                    for n, t in self._span_totals.items()}

    def open_spans(self, limit: int = DIGEST_MAX_OPEN) -> Dict[str, str]:
        """name -> current phase for in-progress spans (stall/digest)."""
        out: Dict[str, str] = {}
        with self._lock:
            for span in self._ring:
                if not span.committed:
                    out[span.name] = span.phase_name()
                    if len(out) >= limit:
                        break
        return out

    def phase_histograms(self) -> Dict[str, tuple]:
        """phase -> (bucket_counts, sum_us, count) cumulative totals, the
        payload the monitor collector mirrors into registry histograms.
        The two-level reduce legs (REDUCE_LEGS) appear as extra keys once
        a hierarchical dispatch commits — the collector mirrors whatever
        keys arrive, so ``hvd_trace_reduce_intra_us`` /
        ``hvd_trace_reduce_cross_us`` materialize exactly when the
        two-level path engages."""
        with self._lock:
            out = {p: (list(self._phase_buckets[p]), self._phase_sum[p],
                       sum(self._phase_buckets[p])) for p in PHASES}
            if self.leg_spans:
                for p in REDUCE_LEGS:
                    out[p] = (list(self._leg_buckets[p]), self._leg_sum[p],
                              sum(self._leg_buckets[p]))
            if self.prefetch_spans:
                out["prefetch"] = (list(self._prefetch_buckets),
                                   self._prefetch_sum,
                                   sum(self._prefetch_buckets))
            return out

    def phase_summary(self) -> dict:
        """Mean per-phase microseconds + mean lifecycle of the committed
        spans.  ``phase_sum_us`` ~= ``cycle_us`` whenever all five stamps
        landed (``tests/test_trace.py`` pins the partition)."""
        with self._lock:
            n = self.spans_committed
            if not n:
                out = {"spans": 0, "phases_us": None, "cycle_us": None,
                       "phase_sum_us": None}
            else:
                phases = {p: round(self._phase_sum[p] / n, 2)
                          for p in PHASES}
                out = {"spans": n, "phases_us": phases,
                       "cycle_us": round(self.lifecycle_us_total / n, 2),
                       "phase_sum_us": round(sum(phases.values()), 2)}
            if self.leg_spans:
                out["leg_spans"] = self.leg_spans
                out["legs_us"] = {
                    p: round(self._leg_sum[p] / self.leg_spans, 2)
                    for p in REDUCE_LEGS}
            if self._span_totals:
                # program spans: mean per span, by name
                out["program_us"] = {
                    name: round(t[0] / t[1], 2)
                    for name, t in self._span_totals.items()}
            return out

    def digest(self) -> dict:
        """Compact cross-rank digest for the MON1 monitor snapshot."""
        with self._lock:
            cycles = [rec.digest_row()
                      for rec in self._cycles[-DIGEST_MAX_CYCLES:]]
            phases = {p: [int(round(self._phase_sum[p])),
                          sum(self._phase_buckets[p])] for p in PHASES}
            legs = {p: [int(round(self._leg_sum[p])), self.leg_spans]
                    for p in REDUCE_LEGS} if self.leg_spans else None
            n, total = self.spans_committed, self.lifecycle_us_total
        program = {name: [int(round(sum_us)), count]
                   for name, (sum_us, count) in self.span_totals().items()}
        out = {"v": 1, "spans": n, "phases": phases, "cycles": cycles,
               "dropped": self.dropped}
        if program:
            # Program spans by name: [sum_us, count].  Like ``legs``, a
            # key old peers ignore.
            out["program"] = program
        if legs:
            # Appears only once the two-level path engaged; old peers
            # ignore unknown digest keys (version-safe).
            out["legs"] = legs
        if n:
            out["cycle_us"] = round(total / n, 1)
        open_ = self.open_spans()
        if open_:
            out["open"] = open_
        return out

    def close(self) -> None:
        global _installed
        if _installed is self:
            _installed = None
        w, self._writer = self._writer, None
        if w is not None:
            w.close()


# ---------------------------------------------------------------- start-up
# One record a process, kept with tracing armed or not (module docstring).
# The file its line goes to, inside the compile cache's directory: no cache
# entry and no name a cache key can take (those end in ``-cache`` or
# ``-atime``).  Cut to its newest lines when it outgrows its bound.
PROCESS_FILE = "_hvd_processes.jsonl"
PROCESS_FILE_MAX_BYTES = 1 << 20
PROCESS_FILE_KEEP_LINES = 256
# The record is of set-up, and bounded a name: ``hvd/broadcast_parameters``
# is stamped at every call for the life of the process (a serving replica's
# weight pushes), so a name keeps its first intervals and what came after
# is counted.  No name can crowd out another: a re-``init()`` after the
# thousandth broadcast still finds room for its ``hvd/init/*``.
STARTUP_MAX_PER_NAME = 16


@functools.lru_cache(maxsize=None)
def process_started_at() -> Optional[float]:
    """When the OS started this process, on ``time.time()``'s clock: its
    start in clock ticks since boot (``/proc/self/stat``, field 22)
    against the seconds since boot now, read once.  None where ``/proc``
    has neither."""
    try:
        with open("/proc/self/stat") as fh:
            # the fields after the command's closing parenthesis
            fields = fh.read().rsplit(")", 1)[1].split()
        with open("/proc/uptime") as fh:
            up, now = float(fh.read().split()[0]), time.time()
        age = up - int(fields[19]) / os.sysconf("SC_CLK_TCK")
        return now - max(0.0, age)
    except (OSError, ValueError, IndexError):
        return None


class _Startup:
    """This process's start-up record."""

    def __init__(self):
        self.lock = threading.Lock()
        self.spans: List[dict] = []
        self.held: Dict[str, int] = {}      # intervals kept, by name
        self.dropped = 0
        self.identity = {"role": "single", "rank": 0, "world": 1,
                         "platform": ""}
        # what the compile ledger registers: () -> its totals and table
        self.ledger: Optional[Callable[[], dict]] = None
        # where the process's line goes: set where the compile cache was
        # placed (``common/compile_cache.py``), None on a CPU run by itself
        self.directory: Optional[str] = None
        self.frozen: Optional[dict] = None
        self.written = False


_startup = _Startup()


class StartupSpan:
    """One interval of the start-up record (context manager), and the
    ``jax.profiler.TraceAnnotation`` of the same name where the process
    has jax, so a user who profiles their own start-up sees the phases on
    the profiler's clock too.  :meth:`set` adds ids known only once the
    span is open."""

    __slots__ = ("name", "ids", "t0", "_ann")

    def __init__(self, name: str, ids: dict, t0: Optional[float] = None):
        self.name, self.ids, self.t0, self._ann = name, ids, t0, None

    def set(self, **ids) -> None:
        self.ids.update(ids)
        if self._ann is not None:
            self._ann.set_metadata(**ids)

    def __enter__(self) -> "StartupSpan":
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        if profiler is not None:
            self._ann = profiler.TraceAnnotation(self.name, **self.ids)
            self._ann.__enter__()
        if self.t0 is None:
            self.t0 = time.time()
        return self

    def __exit__(self, *exc):
        t1 = time.time()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        startup_interval(self.name, self.t0, t1, **self.ids)
        return False


def startup_span(name: str, **ids) -> StartupSpan:
    """``with trace.startup_span("hvd/init/backend") as sp: ...``: always
    recorded, on ``time.time()``."""
    return StartupSpan(name, ids)


def startup_interval(name: str, t0: float, t1: float, **ids) -> None:
    """An interval whose ends are known (``hvd/process`` began before any
    line of Python ran)."""
    st = _startup
    with st.lock:
        held = st.held.get(name, 0)
        if held >= STARTUP_MAX_PER_NAME:
            st.dropped += 1
            return
        st.held[name] = held + 1
        st.spans.append({"name": name, "t0": t0,
                         "seconds": max(0.0, t1 - t0), **ids})


def begin_import(t0: float, jax_imported: bool) -> StartupSpan:
    """From the first line of ``horovod_tpu/__init__.py``: closes
    ``hvd/process`` (the OS's start of the process to that line) and opens
    ``hvd/import``, which the package's last line closes."""
    started = process_started_at()
    if started is not None:
        startup_interval("hvd/process", started, t0,
                         jax_imported=int(jax_imported))
    return StartupSpan("hvd/import", {}, t0).__enter__()


def startup_identity(**fields) -> None:
    """``role`` (``launcher``, ``rank``, ``single``), ``rank``, ``world``,
    ``platform``: what ``hvd.init()`` and the launcher know of the
    process."""
    _startup.identity.update(fields)


def startup_attach(ledger: Optional[Callable[[], dict]] = None,
                   directory: Optional[str] = None) -> None:
    """What ``common/compile_cache.py`` hands the record: its ledger's
    reader, and the directory the process's line goes to."""
    if ledger is not None:
        _startup.ledger = ledger
    if directory is not None:
        _startup.directory = directory


def startup_seconds() -> Dict[str, float]:
    """Seconds by span name (two intervals of one name add up)."""
    out: Dict[str, float] = {}
    with _startup.lock:
        for s in _startup.spans:
            out[s["name"]] = out.get(s["name"], 0.0) + s["seconds"]
    return out


register_series("hvd_startup_seconds", "gauge",
                "seconds of a start-up phase (the start-up record)",
                startup_seconds, label="phase")


def _compose() -> dict:
    import socket
    st = _startup
    with st.lock:
        spans, dropped = [dict(s) for s in st.spans], st.dropped
    return {"v": 1, **st.identity, "pid": os.getpid(),
            "ppid": os.getppid(), "host": socket.gethostname(),
            "process_started_at": process_started_at(),
            "written_at": time.time(), "spans": spans,
            "spans_dropped": dropped,
            "ledger": st.ledger() if st.ledger is not None else None}


def startup() -> dict:
    """This process's start-up record: ``role``, ``pid``, ``rank``,
    ``world``, ``host``, ``platform``, ``process_started_at``, the spans
    (``name``, ``t0``, ``seconds`` and their ids) and the compile ledger
    (``totals`` and ``programs`` by name; None before ``hvd.init()``).
    After ``hvd.shutdown()`` it is what it was at the shutdown."""
    return _startup.frozen or _compose()


def startup_freeze(on: bool) -> None:
    """``hvd.shutdown()`` keeps the record as it is (what compiles after it
    is no part of this runtime's set-up); the next ``hvd.init()`` goes on
    with it."""
    _startup.frozen = _compose() if on else None


def append_process_line(directory: str, record: dict) -> str:
    """One whole line appended to ``<directory>/_hvd_processes.jsonl`` under
    a lock on the file, whoever else appends; past its bound the file is
    cut, in place, to its newest lines."""
    import fcntl
    import json
    os.makedirs(directory, exist_ok=True)
    path = os.path.join(directory, PROCESS_FILE)
    data = (json.dumps(record, separators=(",", ":")) + "\n").encode()
    fd = os.open(path, os.O_RDWR | os.O_CREAT | os.O_APPEND, 0o644)
    try:
        fcntl.flock(fd, fcntl.LOCK_EX)
        os.write(fd, data)
        size = os.fstat(fd).st_size
        if size > PROCESS_FILE_MAX_BYTES:
            lines = os.pread(fd, size, 0).splitlines(keepends=True)
            lines = lines[-PROCESS_FILE_KEEP_LINES:]
            while len(lines) > 1 and \
                    sum(map(len, lines)) > PROCESS_FILE_MAX_BYTES // 2:
                lines = lines[len(lines) // 2:]
            os.ftruncate(fd, 0)
            os.write(fd, b"".join(lines))
    finally:
        os.close(fd)                # and with it the lock
    return path


def read_process_lines(directory: str) -> List[dict]:
    """The records in the file, oldest first; a line that is no JSON object
    (a foreign hand) is skipped, a missing file is no records."""
    import json
    try:
        with open(os.path.join(directory, PROCESS_FILE)) as fh:
            rows = fh.read().splitlines()
    except OSError:
        return []
    out = []
    for row in rows:
        try:
            rec = json.loads(row)
        except ValueError:
            continue
        if isinstance(rec, dict):
            out.append(rec)
    return out


def write_startup(directory: Optional[str] = None) -> Optional[str]:
    """Append this process's record to the file in ``directory``.  Without
    one: where the compile cache was placed, once a process (the first of
    ``hvd.shutdown()``, interpreter exit and, in the launcher, its last
    worker's exit), and nowhere on a CPU run by itself.  Returns the path
    written, or None."""
    st = _startup
    if directory is None:
        if st.written or st.directory is None:
            return None
        st.written = True
        directory = st.directory
    try:
        return append_process_line(directory, startup())
    except OSError:
        return None                 # a record, never a failure
