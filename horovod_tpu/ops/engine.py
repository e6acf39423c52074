"""The collective engine: Horovod's background coordinator, TPU-style.

TPU-native re-design of the reference's L2 core runtime
(``horovod/common/operations.cc`` ``BackgroundThreadLoop``/``RunLoopOnce``,
``tensor_queue.cc``, ``fusion_buffer_cache.cc``, ``response_cache.cc``,
``controller.cc`` — SURVEY.md §2a N1/N2/N6/N7/N8 and §3.2).

What survives from the reference (per SURVEY.md §7's design stance):
the *control plane* — a background cycle thread draining a thread-safe
tensor queue, negotiating which tensors are globally ready, fusing them, and
dispatching one collective per fused batch — plus timeline tracing and stall
inspection.  What changes: the *data plane*.  There is no NCCL ring or
fusion-buffer memcpy machinery to manage; a fused batch becomes a single
**jitted XLA micro-program** (flatten → concat → collective → split) compiled
once per (op, dtype, shape-set, process-set) and cached.  XLA owns the ICI
scheduling; the cache plays the role of the reference's response cache on the
steady-state hot path (SURVEY.md §7 "hard parts" #1 and #5).

Tensor representation ("stacked global array" convention): an eager tensor of
logical per-rank shape S is a ``jax.Array`` of shape ``[world, *S]`` sharded
over the world mesh axis — shard r is rank r's contribution.  Single-process
SPMD holds all shards; multi-process mode assembles the global array from each
process's local shards.  Results come back in natural global form:
allreduce/broadcast → replicated ``[*S]``; allgather → replicated concat;
alltoall/reducescatter → stacked, sharded ``[world, ...]``.
"""

from __future__ import annotations

import dataclasses
import enum
import itertools
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from ..compat import shard_map

import heapq

from . import collectives as C
from .scheduler import (  # noqa: F401  (re-export: public engine surface)
    CKPT_LANE, FAST_LANE, FUSED_LANE, PREFETCH_LANE, CheckpointChunk,
    FusedProgramCache, InflightRing, PingPongBuffers, StallInspector,
    TensorQueue, partition_name, partition_plan, pop_checkpoint_items,
    pop_gradient_batches,
)
from ..common.exceptions import ControlPlaneError
from ..trace.core import OFF as _TRACE_OFF
from ..utils.logging import get_logger

log = get_logger()

# Idle back-off (docs/tensor-fusion.md "Cycle time"): the longest the cycle
# thread waits between cycles while nothing is enqueued.  A cycle that did
# something is followed by a wait of ``cycle_time_s``; each cycle that did
# nothing doubles it, up to this.  Any wake ends the wait at once.
IDLE_WAIT_CAP_S = 0.032


class CollectiveType(enum.Enum):
    ALLREDUCE = "allreduce"
    ALLGATHER = "allgather"
    BROADCAST = "broadcast"
    ALLTOALL = "alltoall"
    REDUCESCATTER = "reducescatter"
    BARRIER = "barrier"


@dataclasses.dataclass
class TensorTableEntry:
    """One pending collective request (reference: TensorTableEntry, N6)."""
    handle: int
    name: str
    ctype: CollectiveType
    tensor: Any                      # stacked global array [world, *S] (or None for barrier)
    reduce_op: C.ReduceOp = C.ReduceOp.AVERAGE
    root_rank: int = 0
    process_set_id: int = 0
    prescale_factor: Optional[float] = None
    postscale_factor: Optional[float] = None
    group_id: int = -1               # grouped ops execute atomically together
    donate: bool = False             # engine owns the buffer: donate to XLA
    # Wire-dtype compression fused into the jitted program ("bf16"/"fp16"/
    # None): cast-down before the collective, cast-up after — halves ICI
    # bytes with zero extra launches (reference N18's cast kernels, done
    # the XLA way).  Reduction ops only; part of the fusion key AND the
    # negotiation digest (divergence would execute mismatched programs).
    compression: Optional[str] = None
    # ZeRO-sharded data plane (ISSUE 15): True for the reduce-scatter /
    # allgather legs of a sharded optimizer program; "full" (ISSUE 18)
    # for the legs of the full-parameter-sharded (FSDP) plane.  Part of
    # the fusion key AND the negotiation digest: a compiled sharded
    # program can never cross-serve an ordinary collective (or a
    # full-sharded one a state-only-sharded one) of the same shapes, and
    # a rank whose sharded= flag diverges from its peers fails
    # negotiation with attribution instead of executing a mismatched
    # program.
    sharded: Any = False               # False | True | "full"
    # Two-level data plane (ISSUE 17): per-call override of the engine's
    # HOROVOD_HIERARCHICAL_ALLREDUCE default — True forces the two-level
    # schedule for this entry, False forces flat, None defers to the
    # engine knob + HOROVOD_HIER_THRESHOLD crossover.  Part of the fusion
    # key but NOT the negotiation digest (results are bitwise-identical
    # either way for SUM/AVERAGE/MIN/MAX, so peers need not agree — but
    # the VALUE must still be rank-invariant, like sharded=, because
    # batching groups by fusion key; analyzer rule HVD110 checks that).
    hierarchical: Optional[bool] = None
    # Drain priority (higher drains first; default 0 = FIFO).  Stamped by
    # the DistributedOptimizer bindings with reverse-registration order so
    # first-needed gradients lead each cycle (ByteScheduler-style priority
    # scheduling); must be identical across ranks for a given name.
    priority: int = 0
    enqueue_time: float = 0.0
    # Latency fast lane (ISSUE 8): marked at the ready verdict for
    # sub-threshold ungrouped allreduces — the entry dispatches as its own
    # single-tensor batch through a persistent pre-compiled program,
    # skipping the fusion-buffer concat/split and the per-cycle program-
    # cache key construction entirely (bitwise-identical results).
    fast_lane: bool = False
    # FSDP parameter-prefetch lane (ISSUE 18): marked by the full-sharded
    # optimizer binding on the allgathers that rematerialize the next
    # bucket's parameters.  Routes the batch onto the PREFETCH backlog
    # lane (after FAST, before FUSED, budget-exempt) so bucket k+1's
    # gather overlaps bucket k's compute without perturbing gradient
    # dispatch order.  Part of the fusion key but NOT the digest, like
    # hierarchical= — peers need not agree, but the value must be
    # rank-invariant (HVD110) because batching groups by fusion key.
    prefetch: bool = False
    # Response-cache slot (stamped by the controller when this entry's
    # announce rides the warm-path bitvector; -1 until learned).  The
    # engine's persistent-program pin key: slot ids are server-assigned
    # and digest-scoped, so a compiled program pinned to a slot is valid
    # for exactly as long as the slot is (coordinated invalidation via
    # the controller's slot_drop_hook).
    cache_slot: int = -1
    # ByteScheduler-style partitioning: sub-tensors of a split parent
    # carry (parent_name, index, count) plus the parent entry; the parent
    # itself never enters the queue (synchronize reassembles from the
    # parts, invisibly to callers).
    partition: Optional[Tuple] = None
    parent: Any = None
    # Lifecycle trace span (horovod_tpu.trace): claimed at first drain when
    # tracing is armed, stamped at each phase boundary, committed at settle.
    # None whenever tracing is disarmed — every stamp site guards on it.
    span: Any = None
    # filled on completion:
    result: Any = None
    error: Optional[BaseException] = None
    done: threading.Event = dataclasses.field(default_factory=threading.Event)


def _fusion_key(e: TensorTableEntry) -> Tuple:
    """Entries with equal keys may fuse into one XLA program.

    dtype is deliberately NOT part of the key: a fused program groups leaves
    by dtype internally (one concat+psum per dtype) and XLA's collective
    combiner merges those into one wire transfer — this keeps grouped ops
    with mixed fp32/bf16 members atomic in a single batch (reference: group
    table N13 semantics).

    The partition COUNT (never the raw threshold bytes, mirroring the
    chunk-plan keying) distinguishes a partitioned sub-tensor's program
    from a same-shaped ordinary tensor's, so a slot-pinned part program
    can never cross-serve an unpartitioned entry; parts of equal-shaped
    parents still share one compiled program.
    """
    return (e.ctype, e.reduce_op, e.root_rank, e.process_set_id,
            e.prescale_factor, e.postscale_factor, e.compression,
            e.sharded, e.hierarchical, e.prefetch,
            e.partition[2] if e.partition is not None else 0)


# Sentinel for a tensor whose trace-span claim was dropped (ring full):
# marks the entry permanently untraceable for this collective, so later
# drains cannot re-claim it with a fresh drain time (which would fold the
# negotiation cycles already spent into the queue phase) and the recorder's
# dropped counter counts each entry once.  Every stamp/commit site treats
# it as "no span".
_SPAN_DROPPED = object()


def _live_span(e):
    """The entry's traceable span, or None (untraced / claim dropped)."""
    sp = e.span
    return None if (sp is None or sp is _SPAN_DROPPED) else sp


def _span_cycle(batch) -> int:
    """The negotiation round that readied ``batch`` (the id its entries'
    ``TensorSpan``s carry), -1 where none of them is traced."""
    for e in batch:
        sp = _live_span(e)
        if sp is not None:
            return sp.cycle
    return -1


def _np_dtype(name: str) -> np.dtype:
    """numpy dtype from its string form, including ml_dtypes extensions
    (bfloat16/fp8) that ``np.dtype`` alone does not know."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes
        return np.dtype(getattr(ml_dtypes, name))


class CollectiveEngine:
    """Background coordinator: queue → negotiate → fuse → execute.

    Single-controller negotiation is local (everything submitted is ready —
    the one process is every rank).  Multi-process mode plugs a TCP
    controller in at ``self.controller`` so all processes agree on the
    response list before executing identical programs; the execution path
    below is shared by both modes.
    """

    def __init__(self, state):
        self._state = state
        cfg = state.config
        self.queue = TensorQueue()
        self.cache = FusedProgramCache(cfg.cache_capacity)
        self.stall = StallInspector(cfg.stall_check_time_s,
                                    cfg.stall_shutdown_time_s,
                                    cfg.stall_check_disable)
        self.cycle_time_s = cfg.cycle_time_ms / 1000.0
        self.fusion_threshold = cfg.fusion_threshold_bytes
        # Pipelined data plane (HOROVOD_PIPELINE_CHUNK / HOROVOD_MAX_
        # INFLIGHT).  chunk 0 = off: one chunk per fused batch, the legacy
        # single-collective program (a true off, because atomic clusters
        # can exceed the fusion threshold — see _chunk_plan); >0 splits
        # the fusion buffer so cast-down → reduce → cast-up stages overlap
        # across chunks inside the jitted program.  Both runtime-tunable
        # (autotune coordinates in multi-process mode).
        self.pipeline_chunk_bytes = cfg.pipeline_chunk_bytes
        self.max_inflight = cfg.max_inflight
        self._inflight: Optional[InflightRing] = None
        # Pipeline observability (/metrics hvd_pipeline_*_total through
        # monitor/agent.py; the timeline gets a per-cycle "pipeline"
        # counter track).
        self.pipeline_chunks_total = 0
        self.pipeline_dispatches = 0
        self.last_cycle_chunks = 0
        # Small-message latency war (ISSUE 8, docs/performance.md
        # "Latency fast lane").  fast_lane_threshold: ungrouped allreduces
        # below it skip the fusion buffer — single-tensor batches through
        # persistent pre-compiled programs (_fast_programs: slot id — or
        # name in single-controller mode — -> pinned program record,
        # invalidated via the controller's slot_drop_hook).
        # partition_threshold: tensors above it split at enqueue into
        # priority-inheriting sub-tensors (ByteScheduler) so a small
        # high-priority gradient preempts a huge transfer between parts;
        # synchronize() reassembles transparently.  The dispatch backlog
        # (_backlog, ring mode only) is what makes preemption real: ready
        # batches queue by (lane, priority) and feed the in-flight window
        # only as it has room, so a later cycle's hotter batch overtakes
        # a huge tensor's remaining parts instead of queueing behind them.
        self.fast_lane_threshold = cfg.fast_lane_threshold_bytes
        self.partition_threshold = cfg.partition_threshold_bytes
        self._fast_programs: Dict[Any, tuple] = {}
        self._pingpong: Optional[PingPongBuffers] = None
        self._staging_tokens: Dict[int, list] = {}
        self._backlog: List[tuple] = []       # heap: (lane, -prio, seq, batch)
        self._backlog_seq = itertools.count()
        # Checkpoint-lane staging (ISSUE 14): submit_checkpoint_io runs
        # on the TRAINING thread while the cycle thread heappops the
        # backlog — heap mutation is not thread-safe, so cross-thread
        # submissions land here (own lock) and the cycle thread folds
        # them into the heap at its next turn.
        self._ckpt_staging: List = []
        self._ckpt_staging_lock = threading.Lock()
        self.fast_lane_dispatches = 0         # fast-lane batches dispatched
        self.fast_lane_hits = 0               # ... served by a pinned program
        self.partition_splits = 0             # parents split at enqueue
        # Resilient state plane (ISSUE 14, docs/fault_tolerance.md):
        # checkpoint shard writes ride the SAME backlog at CKPT_LANE —
        # strictly after every gradient batch, popped by their own
        # per-cycle budget so the durability stream overlaps training
        # without touching gradient dispatch order or the control plane
        # (checkpoint chunks are local I/O, never negotiated).
        self.ckpt_lane_budget = max(1, int(cfg.ckpt_lane_budget))
        self.ckpt_chunks_dispatched = 0
        self.stateplane = None
        if cfg.ckpt_dir:
            # One plane per directory per PROCESS (stateplane.obtain):
            # it survives elastic re-init like the per-host agent — a
            # survivor's in-memory epoch is exactly what a re-joining
            # rank restores from, so it must outlive the generation.
            from ..elastic.stateplane import obtain as _obtain_plane
            self.stateplane = _obtain_plane(
                cfg.ckpt_dir, rank=max(0, cfg.rank_env),
                world=max(1, cfg.size_env), engine=self,
                chunk_bytes=cfg.ckpt_chunk_bytes)
        self.hierarchical_allreduce = cfg.hierarchical_allreduce
        self.hierarchical_allgather = cfg.hierarchical_allgather
        self.hierarchical_broadcast = cfg.hierarchical_broadcast
        self._hier_local_size = cfg.hierarchical_local_size
        # Two-level data plane (ISSUE 17): payload crossover + explicit
        # slice membership override.  hier_threshold_bytes is a local
        # knob like pipeline_chunk_bytes — autotunable, never negotiated.
        self.hier_threshold_bytes = cfg.hier_threshold_bytes
        self.slice_map = cfg.slice_map
        # Per-process-set slice topology, derived once (device attrs /
        # HOROVOD_SLICE_MAP / local-size knob — parallel/topology.py) and
        # probed on every dispatch by the crossover decision.
        self._slice_topos: Dict[int, Any] = {}
        # Leg counters: proof the two-level path actually engaged.  One
        # hier dispatch = 2 intra-slice (ICI) legs (reduce-scatter +
        # allgather) + 1 cross-slice (DCN) leg.
        self.hier_dispatches = 0
        self.hier_intra_legs = 0
        self.hier_cross_legs = 0
        # Two-level allgather legs (ISSUE 18 satellite — the knob was a
        # no-op until now): one hier-AG dispatch = 1 intra-slice (ICI)
        # gather leg + 1 cross-slice (DCN) leader-exchange leg.
        self.hier_ag_dispatches = 0
        self.hier_ag_intra_legs = 0
        self.hier_ag_cross_legs = 0
        # Two-level broadcast legs (ISSUE 19 satellite — serving's weight
        # fan-out made this path hot): one hier-broadcast dispatch = 1
        # cross-slice (DCN) leader-exchange leg + 1 intra-slice (ICI)
        # fan-out leg.
        self.hier_bcast_dispatches = 0
        self.hier_bcast_intra_legs = 0
        self.hier_bcast_cross_legs = 0
        # Non-uniform HOROVOD_SLICE_MAP rejections (ISSUE 18 satellite):
        # counted once per process set (the topology probe is cached), so
        # mixed-size fleets can see WHY collectives stayed flat.
        self.slice_map_fallbacks = 0
        # FSDP parameter-prefetch lane (ISSUE 18): PREFETCH-lane batches
        # dispatched, and how many of those were dispatched while an
        # earlier bucket's gather was still in flight (overlap engaged —
        # the acceptance criterion's evidence).
        self.prefetch_dispatches = 0
        self.prefetch_overlapped = 0
        self._handle_counter = itertools.count(1)
        self._handles: Dict[int, TensorTableEntry] = {}
        self._handles_lock = threading.Lock()
        self._cycle_lock = threading.Lock()  # serializes cycles (bg + kick)
        self._shutdown = threading.Event()
        self._wake = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._cycle_index = 0
        self.controller = None       # multi-process TCP controller (optional)
        # Control-plane fault latch (HVD303): set by _abort_engine when a
        # ControlPlaneError (dead peer / round timeout) surfaces from
        # negotiation.  Once set, the engine is cleanly down — every
        # pending/in-flight waiter was settled with the error, and new
        # enqueues raise it immediately instead of queueing into a dead
        # world.  Elastic re-init builds a fresh engine, clearing it.
        self._fault: Optional[BaseException] = None
        # Clean world-membership change (protocol v6, NOT a fault): set
        # when the coordinator's leave notice names peers that departed
        # via clean LEAVE.  World-level (default-process-set) work fails
        # with it — the control plane's world shrank but the data-plane
        # world is still the old fixed size, so executing a shrunk-world
        # verdict would wedge the transport — while /health stays ok and
        # no HVD303 is raised; the elastic wrapper re-rendezvouses keeping
        # current parameters.  Elastic re-init clears it with the engine.
        self._world_changed: Optional[BaseException] = None
        # Control-plane observability: cumulative negotiation wall time and
        # round count (multi-process mode only — single-controller cycles
        # have no negotiation).  /metrics exports both through
        # monitor/agent.py; the timeline gets a per-cycle counter track.
        self.negotiation_us_total = 0.0
        self.negotiation_cycles = 0
        self.last_negotiation_us = 0.0
        # Zero-RTT warm path (protocol v7): a cycle's verdict may come from
        # the coordinator's speculative prediction — negotiate() returned
        # without waiting for the response, so the negotiation phase
        # collapses toward zero.  The dispatch path below is deliberately
        # identical for predicted and lock-step verdicts (same entries,
        # same deterministic batching, same programs): a mispredict never
        # reaches this layer — the controller absorbs it by merging the
        # next announce into the still-pending server entry, so results
        # stay bitwise identical and nothing needs un-dispatching here.
        # Whole-cycle wall-time accounting (drain + negotiate + fuse +
        # dispatch): the per-rank numbers the monitor subsystem aggregates
        # into slowest-rank / cycle-time-spread straggler attribution
        # (horovod_tpu.monitor).  `monitor` is a MonitorAgent installed by
        # init() when HOROVOD_MONITOR=1 — None costs one attribute check
        # per cycle.
        self.cycle_us_total = 0.0
        self.cycle_count = 0
        self.last_cycle_ts = 0.0
        # Idle back-off: cycles that did nothing (``_cycle_did_nothing``),
        # the wait now in force between cycles, and the wait the cycle
        # thread last sat out (the ``hvd/cycle`` span's ``waited_ms``).
        self.idle_cycles = 0
        self.idle_wait_s = self.cycle_time_s
        self._waited_s = 0.0
        self.monitor = None
        # Distributed collective tracing (HOROVOD_TRACE, horovod_tpu.trace):
        # per-tensor lifecycle spans (queue/negotiation/copy_in/reduce/
        # drain) stamped through the cycle below, ring-buffered, optionally
        # written to a per-rank trace file, and digested into the monitor
        # side-channel.  None when disarmed — every stamp site is then one
        # attribute check (tests/test_trace.py::test_disarmed_recorder_is_none).
        from ..trace import maybe_install as _trace_install
        self.tracer = _trace_install(
            cfg, rank=cfg.rank_env if cfg.rank_env >= 0 else 0)
        # XLA:CPU executes collectives via blocking rendezvous on a shared
        # Eigen pool; back-to-back ASYNC launches can starve a participant
        # thread and abort the process ("Expected N threads to join the
        # rendezvous", reproducible on 1-core hosts with 8 virtual devices,
        # with or without this engine).  On the hermetic CPU tier, wait for
        # each fused program before launching the next; TPU keeps the fully
        # async pipeline (its executor serializes per-core streams).
        self._serialize_launches = jax.default_backend() == "cpu"
        # Cached off the hot dispatch path (engine is built after the jax
        # world forms): >1 ⇒ eager ops need the negotiation controller.
        self._world_processes = jax.process_count()
        # Opt-in runtime collective sanitizer (HVD_TPU_SANITIZER=1):
        # records the per-rank submission ledger and stamps entries with
        # seq/call-site tags the controller folds into its negotiation
        # digest, so cross-rank order divergence fails fast with call-site
        # attribution (analysis/runtime_sanitizer.py).  May replace
        # self.stall with a tightened, ledger-reporting inspector.
        from ..analysis import runtime_sanitizer as _rts
        self.sanitizer = _rts.maybe_install(self)
        self.autotuner = None        # reference N9 parameter manager
        if cfg.autotune:
            from .autotune import ParameterManager
            self.autotuner = ParameterManager(
                self, warmup_samples=cfg.autotune_warmup_samples,
                steps_per_sample=cfg.autotune_steps_per_sample,
                log_path=cfg.autotune_log,
                max_evals=cfg.autotune_max_evals)

    # ------------------------------------------------------------- lifecycle
    def start(self):
        self._thread = threading.Thread(
            target=self._background_loop, name="hvd-tpu-coordinator", daemon=True)
        self._thread.start()

    def quiesce(self, timeout: float = 10.0) -> bool:
        """Stop the cycle thread at a round boundary for a CLEAN departure.

        Sets the shutdown flag and joins the thread WITHOUT severing the
        controller socket first: in a healthy world the in-flight
        lock-step round completes in milliseconds and the thread exits at
        the loop check, leaving the socket quiet — the precondition for
        ``controller.leave()`` (the LEAVE frame must not interleave with a
        round in flight).  Returns True when the thread exited cleanly
        with no fault latched; False (thread wedged — a peer is already
        gone or the coordinator is stuck) tells the caller to fall back to
        the legacy ``interrupt()`` sever."""
        self._shutdown.set()
        self._wake.set()
        t = self._thread
        if t is not None:
            t.join(timeout=timeout)
            if t.is_alive():
                return False
            self._thread = None
        return self._fault is None

    def stop(self):
        self._shutdown.set()
        self._wake.set()
        if self._thread is not None:
            self._thread.join(timeout=10)
            self._thread = None
        # The cycle thread is gone: this thread is now the heap's sole
        # mutator, so staged checkpoint items can fold in safely.
        if self._fault is None:
            self._drain_ckpt_staging()
        if self._backlog and self._fault is None:
            # Undispatched ready batches (the preemptive backlog only
            # defers dispatch while the window is full): dispatch them now,
            # before the ring drains — their waiters must not outlive the
            # engine unsignalled.  Checkpoint-lane items run too (the
            # shutdown finishes the durable write instead of abandoning
            # a healthy epoch).  The fault path already settled both.
            while self._backlog:
                lane, _, _, item = heapq.heappop(self._backlog)
                if lane == CKPT_LANE:
                    self._run_ckpt_item(item)
                else:
                    self._perform_operation(item)
        if self._inflight is not None:
            # Settles every dispatched batch first: a waiter blocked in
            # synchronize() must never outlive the watcher unsignalled.
            self._inflight.stop()
            self._inflight = None
        if self.tracer is not None:
            # After the ring: settling commits spans, and the trace file
            # must hold them all before the final flush.
            self.tracer.close()
        if self.stateplane is not None:
            # After the backlog drain above: any in-flight durable write
            # has finished (or failed with attribution).  DETACH, never
            # close — the plane (its shard server + in-memory epoch)
            # survives the engine exactly like the per-host agent, so a
            # re-joining rank can still restore from this survivor while
            # the world re-forms.  Commits between generations write
            # inline.
            if self.stateplane.engine is self:
                self.stateplane.engine = None
            self.stateplane = None

    def _abort_engine(self, exc: BaseException, busy: bool = False):
        """Clean engine shutdown on a control-plane fault (HVD303).

        Invariant restored here: NO waiter may hang.  Every entry still
        queued is settled with the error, the in-flight ring fails its
        window without blocking on device results that may never come
        (a collective whose participant died can block forever), new
        enqueues raise immediately, and the monitor's ``/health`` flips
        to ``peer_dead`` with the dead-rank list.  Runs on the cycle
        thread; idempotent.

        ``busy`` is the caller's hint that the failing cycle itself was
        carrying entries; together with the queue/ring state it picks the
        log severity — losing a peer with NO work outstanding is the
        shape of an ordinary staggered clean shutdown (the first rank to
        leave severs its socket and the server declares it dead; no wire
        protocol distinguishes that from a crash), so it must not put an
        ERROR in every clean run's logs."""
        if self._fault is not None:
            return
        self._fault = exc
        # Everything still waiting to negotiate fails now — the control
        # plane will never answer it.
        pending = self.queue.drain()
        idle = (not busy and not pending and not self._backlog
                and (self._inflight is None or len(self._inflight) == 0))
        if idle:
            log.warning(
                "control plane lost peer(s) with no work outstanding — a "
                "staggered clean shutdown looks exactly like this (a peer "
                "crash between bursts does too); shutting the engine down: "
                "%s", exc)
        else:
            log.error("control plane failed; shutting the engine down "
                      "cleanly: %s", exc)
        self._settle_queued(pending, exc)
        # Ready-but-undispatched batches parked in the preemptive backlog
        # are waiters too: settle them with the fault (their negotiation
        # lane is the one still open on the timeline).  Checkpoint-lane
        # items fail their write job instead — the epoch is abandoned
        # cleanly and the previous durable epoch remains the restore
        # point (never a torn write).  Staged-but-unfolded items get the
        # same treatment (runs on the cycle thread; later submits fail
        # fast on the latched fault).
        self._drain_ckpt_staging()
        while self._backlog:
            lane, _, _, item = heapq.heappop(self._backlog)
            if lane == CKPT_LANE:
                try:
                    item.fail(exc)
                except Exception:  # noqa: BLE001 - keep the abort going
                    log.exception("checkpoint-lane abort settle failed")
            else:
                self._settle_batch(item, None, exc)
        if self._pingpong is not None:
            # Both staging buffers settle exactly once: outstanding tokens
            # are released (idempotently — a racing watcher settle is a
            # no-op) and no dispatcher may block on a slot the wedged
            # watcher will never free.
            self._pingpong.abort()
        if self._inflight is not None:
            self._inflight.abort(exc)
        ctl = self.controller
        if ctl is not None:
            # Join waiters are part of the invariant too: the all-joined
            # verdict can never arrive from a dead control plane, and
            # hvd.join()'s default is timeout=None.
            try:
                ctl.fail_join(exc)
            except Exception:  # noqa: BLE001 - keep the abort going
                log.exception("failing join waiters failed")
        mon = self.monitor
        if mon is not None:
            try:
                mon.on_peer_failure(getattr(exc, "dead_ranks", []) or [],
                                    str(exc))
            except Exception:  # noqa: BLE001 - telemetry only
                log.exception("monitor peer-failure hook failed")
        # Stop cycling: further lock-step rounds against a stopped server
        # would only churn errors.  basics.shutdown() still runs the full
        # teardown (thread join, controller close) afterwards.
        self._shutdown.set()

    def _settle_queued(self, entries, exc: BaseException):
        """Settle queued-but-never-negotiated entries with a fault — THE
        one implementation of the no-waiter-may-hang invariant for the
        pre-negotiation stage (both _abort_engine's drain and the
        enqueue-vs-abort race path funnel through here, so the settle
        sequence cannot drift between them)."""
        tl = self._state.timeline
        tr = self.tracer
        for e in entries:
            e.error = exc
            if tl is not None:
                tl.end_activity(e.name, "QUEUE")
            sp = _live_span(e) if tr is not None else None
            if sp is not None:
                # Requeued entries may already carry a claimed span: commit
                # it as aborted so the ring slot is reclaimable.
                sp.error = True
                tr.commit(sp)
            self.queue.mark_done(e)
            e.done.set()

    @property
    def fault(self) -> Optional[BaseException]:
        """The control-plane fault (HVD303) that shut this engine down, or
        ``None`` while healthy.  Public contract: ``basics.shutdown`` keys
        its abrupt-teardown path off it, and fault-tolerance acceptance
        workers poll it to converge on the typed verdict.  Elastic re-init
        builds a fresh engine, which clears it."""
        return self._fault

    @property
    def world_changed(self) -> Optional[BaseException]:
        """The ``PeerLeftInterrupt`` latched when peers departed via clean
        LEAVE (protocol v6), or ``None``.  NOT a fault: ``fault`` stays
        ``None`` and ``/health`` stays ok — but world-level work fails
        with this until the elastic re-init forms the next generation
        (which builds a fresh engine, clearing it)."""
        return self._world_changed

    # ------------------------------------------------------------- submit API
    def enqueue(self, name: str, ctype: CollectiveType, tensor,
                reduce_op=C.ReduceOp.AVERAGE, root_rank: int = 0,
                process_set_id: int = 0, prescale_factor=None,
                postscale_factor=None, group_id: int = -1,
                donate: bool = False, compression: Optional[str] = None,
                priority: int = 0, sharded: bool = False,
                hierarchical: Optional[bool] = None) -> int:
        return self.enqueue_group([dict(
            name=name, ctype=ctype, tensor=tensor, reduce_op=reduce_op,
            root_rank=root_rank, process_set_id=process_set_id,
            prescale_factor=prescale_factor, postscale_factor=postscale_factor,
            group_id=group_id, donate=donate, compression=compression,
            priority=priority, sharded=sharded,
            hierarchical=hierarchical)])[0]

    def enqueue_group(self, items: Sequence[dict]) -> List[int]:
        """Enqueue several entries atomically w.r.t. the drain — a cycle
        sees all of them or none, so grouped members always negotiate (and
        batch) together (reference: group_table N13)."""
        if self._fault is not None:
            # The control plane is down (dead peer / round timeout): fail
            # fast with the original HVD303 error instead of queueing work
            # no negotiation round will ever answer.
            raise self._fault
        if self._world_changed is not None and any(
                int(kw.get("process_set_id", 0) or 0) == 0 for kw in items):
            # Peers departed via clean LEAVE (protocol v6): world-level
            # work cannot run until the world re-forms — fail fast with
            # the re-rendezvous interrupt, NOT an HVD303 fault.
            raise self._world_changed
        if self.controller is None and self._world_processes > 1:
            # A multi-process world without the launcher's negotiation
            # controller (pod auto-detect mode): eager collectives cannot
            # coordinate safely — the SPMD shard_map path is unaffected.
            raise RuntimeError(
                "eager collectives need the torovodrun-launched "
                "negotiation controller in a multi-process world; this "
                "process joined via pod auto-detect "
                "(HOROVOD_ONE_PROC_PER_HOST without HOROVOD_CONTROLLER_"
                "ADDR).  Launch with torovodrun, or use the in-graph "
                "psum/shard_map path")
        entries = []
        for kw in items:
            handle = next(self._handle_counter)
            entries.append(TensorTableEntry(handle=handle, **kw))
        # ByteScheduler partitioning: tensors above the threshold split
        # into priority-inheriting sub-tensors HERE, before the sanitizer
        # and the queue — the parts are what negotiate (under
        # deterministic sub-names every rank derives identically); the
        # parent stays handle-registered and is reassembled transparently
        # in synchronize().
        queued = self._maybe_partition(entries)
        if self.sanitizer is not None:
            # BEFORE the push: the cycle thread may drain a pushed entry
            # within microseconds, and an untagged digest racing a tagged
            # peer announce would be a false mismatch.
            self.sanitizer.observe(queued)
        with self._handles_lock:
            for e in entries:
                self._handles[e.handle] = e
        try:
            self.queue.push_many(queued)
        except ValueError:
            with self._handles_lock:
                for e in entries:
                    self._handles.pop(e.handle, None)
            if self.sanitizer is not None:
                # Duplicate-name rejection is rank-local: peers never see
                # these entries, so the advanced seq counters must be
                # rolled back or every later tag skews cross-rank.
                self.sanitizer.rollback(queued)
            raise
        tl = self._state.timeline
        if tl is not None:
            for e in queued:
                tl.start_activity(e.name, "QUEUE")
        fault = self._fault
        if fault is not None:
            # Lost the race with _abort_engine (the fault landed between
            # the guard above and the push).  Drain-as-claim: the queue pop
            # is atomic, so only entries still queued are ours to settle —
            # anything already drained (the abort's sweep, or a cycle that
            # then fails them) is settled exactly once by its drainer,
            # never twice (a double settle garbles the timeline's QUEUE
            # begin/end pairing).
            self._settle_queued(self.queue.drain(), fault)
        self._wake.set()
        return [e.handle for e in entries]

    def _maybe_partition(
            self, entries: List[TensorTableEntry]) -> List[TensorTableEntry]:
        """Split oversized reduction entries into sub-tensors (ByteScheduler
        partitioning): returns the queue-facing entry list — parents
        replaced by their parts.  Eligibility and the plan are pure
        functions of the negotiated (shape, dtype) plus the fleet-wide
        threshold, so every rank derives identical sub-names/shapes.
        ADASUM is excluded (its dot products span the whole vector —
        splitting changes the math); grouped members stay whole (groups
        are atomic)."""
        thr = self.partition_threshold
        if thr <= 0:
            return list(entries)
        out: List[TensorTableEntry] = []
        for e in entries:
            if (e.ctype != CollectiveType.ALLREDUCE or e.group_id >= 0
                    or e.tensor is None
                    or e.reduce_op == C.ReduceOp.ADASUM
                    or e.tensor.nbytes <= thr):
                out.append(e)
                continue
            shape = tuple(e.tensor.shape)
            per_rank = shape[1:]
            n = int(np.prod(per_rank)) if per_rank else 1
            # The threshold counts GLOBAL stacked bytes (the same
            # convention as the fusion threshold and the eligibility gate
            # above); the plan runs over the per-rank flat buffer, so
            # scale it down by world — parts come out ~threshold-sized
            # globally, and the gate and the plan can never disagree
            # about whether a split happens.
            per_rank_thr = max(1, thr // max(1, shape[0]))
            plan = partition_plan(n, e.tensor.dtype.itemsize, per_rank_thr)
            if len(plan) <= 1:
                out.append(e)
                continue
            arrays = self._split_parts(e, plan)
            k = len(plan)
            subs = []
            for i, arr in enumerate(arrays):
                sub = TensorTableEntry(
                    handle=next(self._handle_counter),
                    name=partition_name(e.name, i, k),
                    ctype=e.ctype, tensor=arr, reduce_op=e.reduce_op,
                    root_rank=e.root_rank,
                    process_set_id=e.process_set_id,
                    prescale_factor=e.prescale_factor,
                    postscale_factor=e.postscale_factor,
                    group_id=-1, donate=True, compression=e.compression,
                    priority=e.priority,          # priority inheritance
                    hierarchical=e.hierarchical)
                sub.partition = (e.name, i, k)
                sub.parent = e
                subs.append(sub)
            e.parts = subs
            e.partition_shape = per_rank
            e.tensor = None           # staged into the parts; free it
            out.extend(subs)
            self.partition_splits += 1
        return out

    def _split_parts(self, e: TensorTableEntry, plan) -> List[Any]:
        """One jitted splitter launch: flatten the per-rank payload and
        slice the plan's parts out, keeping the stacked [world, n_i]
        convention and the world-axis sharding (each part is an ordinary
        engine tensor from here on).  Cached like any other program."""
        shape = tuple(e.tensor.shape)
        mesh, axis, _world = self._mesh_axis(e.process_set_id)
        key = ("partition_split", shape, str(e.tensor.dtype), plan,
               e.process_set_id)

        def build():
            sharding = NamedSharding(mesh, P(axis))

            def split(x):
                flat = x.reshape(shape[0], -1)
                return tuple(flat[:, off:off + ln] for off, ln in plan)

            return jax.jit(split, out_shardings=sharding)

        fn = self.cache.get_or_build(key, build)
        return list(fn(e.tensor))

    def _assemble_parts(self, e: TensorTableEntry):
        """Reassemble a partitioned tensor's result from its settled parts
        (concat + reshape back to the per-rank logical shape) — runs on
        the synchronizing caller's thread, invisible to it."""
        parts = e.parts
        per_rank = tuple(e.partition_shape)
        key = ("partition_join",
               tuple(tuple(s.result.shape) for s in parts),
               str(parts[0].result.dtype), per_rank)

        def build():
            def join(*xs):
                flat = (jnp.concatenate([x.reshape(-1) for x in xs])
                        if len(xs) > 1 else xs[0].reshape(-1))
                return flat.reshape(per_rank)

            return jax.jit(join)

        fn = self.cache.get_or_build(key, build)
        return fn(*[s.result for s in parts])

    def synchronize(self, handle: int, timeout: Optional[float] = None):
        """Block until the handle's collective completed; return result.

        Reference parity: ``horovod/torch/mpi_ops.py synchronize()``.
        Partitioned entries wait on every part and reassemble — callers
        cannot tell a split tensor from a whole one.
        """
        with self._handles_lock:
            e = self._handles.get(handle)
        if e is None:
            raise ValueError(f"Unknown handle {handle}")
        parts = getattr(e, "parts", None)
        if parts is not None:
            deadline = (None if timeout is None
                        else time.monotonic() + timeout)
            for s in parts:
                left = (None if deadline is None
                        else max(0.0, deadline - time.monotonic()))
                if not s.done.wait(left):
                    raise TimeoutError(
                        f"Collective {e.name!r} did not complete within "
                        f"{timeout}s ({sum(1 for p in parts if p.done.is_set())}"
                        f"/{len(parts)} parts settled)")
            with self._handles_lock:
                self._handles.pop(handle, None)
            err = next((s.error for s in parts if s.error is not None), None)
            if err is not None:
                raise err
            if e.result is None:
                e.result = self._assemble_parts(e)
            return e.result
        if not e.done.wait(timeout):
            raise TimeoutError(f"Collective {e.name!r} did not complete "
                               f"within {timeout}s")
        with self._handles_lock:
            self._handles.pop(handle, None)
        if e.error is not None:
            raise e.error
        return e.result

    def poll(self, handle: int) -> bool:
        with self._handles_lock:
            e = self._handles.get(handle)
        if e is None:
            return True
        parts = getattr(e, "parts", None)
        if parts is not None:
            return all(s.done.is_set() for s in parts)
        return e.done.is_set()

    # ------------------------------------------------------- checkpoint lane
    def submit_checkpoint_io(self, items: Sequence) -> None:
        """Queue checkpoint-lane work items (ISSUE 14): shard-chunk
        writes from the state plane, scheduled at :data:`CKPT_LANE` —
        strictly after every gradient batch, popped by their own
        per-cycle budget (``HOROVOD_CKPT_LANE_BUDGET``).  Items are
        plain local-I/O callables, never negotiated: zero control-plane
        bytes, no cross-rank ordering requirement.  After a fault the
        lane is closed — items fail immediately so the write job
        abandons its epoch instead of queueing into a dead engine."""
        # Stage, never touch the heap: this runs on the TRAINING thread
        # (state.commit), and heappush racing the cycle thread's heappop
        # would corrupt the backlog ordering every rank must share.  The
        # cycle thread folds the staging in at its next turn.  The fault/
        # shutdown check lives INSIDE the staging lock: _abort_engine
        # latches the fault BEFORE draining the staging under this same
        # lock, so an item either lands before that drain (and is failed
        # there) or observes the latched fault here — never neither (an
        # unlocked check could stage into an already-aborted engine,
        # leaving the write job neither run nor failed and commit(wait)
        # blocked for its full timeout).
        with self._ckpt_staging_lock:
            fault = self._fault
            stopped = fault is not None or self._shutdown.is_set()
            if not stopped:
                self._ckpt_staging.extend(items)
        if stopped:
            for it in items:
                try:
                    it.fail(fault or RuntimeError("engine stopped"))
                except Exception:  # noqa: BLE001 - settle the rest
                    log.exception("checkpoint item fail hook failed")
            return
        self._wake.set()

    def _drain_ckpt_staging(self) -> None:
        """Fold staged checkpoint items into the backlog heap — CYCLE
        THREAD ONLY (the heap has exactly one mutator)."""
        with self._ckpt_staging_lock:
            items, self._ckpt_staging = self._ckpt_staging, []
        for it in items:
            heapq.heappush(
                self._backlog,
                (CKPT_LANE, -int(getattr(it, "priority", 0)),
                 next(self._backlog_seq), it))

    def _run_ckpt_item(self, item) -> None:
        """Dispatch one checkpoint-lane item on the cycle thread.  The
        item owns its own retries/failure attribution (the state plane's
        write job); the engine only guarantees a raising item cannot
        kill the cycle loop."""
        try:
            item.run()
            self.ckpt_chunks_dispatched += 1
        except BaseException:  # noqa: BLE001 - the cycle must survive
            log.exception("checkpoint-lane item %r failed",
                          getattr(item, "name", item))

    # ------------------------------------------------------------- main loop
    def _background_loop(self):
        while not self._shutdown.is_set():
            # Callers enqueue, then set; this loop clears, then drains: a
            # wake that lands after the clear ends the NEXT wait at once,
            # so no entry ever sits out an idle wait.
            t0 = time.monotonic()
            self._wake.wait(timeout=self.idle_wait_s)
            self._wake.clear()
            self._waited_s = time.monotonic() - t0
            try:
                self.run_loop_once()
            except Exception:       # pragma: no cover - engine bug surface
                log.exception("coordinator cycle failed")

    def kick(self):
        """Hint that a caller is about to block on a just-enqueued handle.

        Single-controller mode: run the cycle INLINE on the calling thread —
        the submit→wake→cycle-thread→done→waiter round trip costs two thread
        handoffs that dominate small-tensor latency (VERDICT r3 weak #3);
        executing the drain/fuse/dispatch pipeline here removes both while
        preserving fusion (a concurrent burst drains into the same cycle).
        Multi-process mode: negotiation must stay on the lock-step cycle
        thread; just wake it.
        """
        if self.controller is None:
            self.run_loop_once()
        else:
            self._wake.set()

    def run_loop_once(self):
        """One coordinator cycle (reference: RunLoopOnce, SURVEY.md §3.2).

        Serialized by ``_cycle_lock`` — the background thread and blocking
        submitters (``kick``) may race to run a cycle.

        Any failure during planning (negotiation error, stall-shutdown
        abort, timeline I/O) must fail the drained entries — never drop
        them — or waiters in ``synchronize()`` would hang forever.
        """
        with self._cycle_lock:
            self._set_idle_wait(self._run_cycle_locked())

    def _run_cycle_locked(self) -> bool:
        """One cycle; returns whether it did nothing
        (``_cycle_did_nothing``)."""
        t_cycle0 = time.perf_counter()
        self._cycle_index += 1
        tl = self._state.timeline
        if tl is not None:
            tl.mark_cycle(self._cycle_index)
        waited_s, self._waited_s = self._waited_s, 0.0
        self._drain_ckpt_staging()
        entries = self.queue.drain()
        if not entries and self.controller is None and not self._backlog:
            # (The backlog check keeps the checkpoint lane draining on
            # otherwise-idle single-controller cycles.)
            return True
        tr = self.tracer
        if tr is None:
            return self._negotiate_and_dispatch(entries, t_cycle0)
        t_drain = time.monotonic()
        t_trace0 = t_drain - (time.perf_counter() - t_cycle0)
        for e in entries:
            if e.span is None:
                # queue phase closes at this first drain; requeued
                # entries keep their span (still in negotiation).  A
                # dropped claim latches the sentinel: claim at most
                # once per entry.
                e.span = tr.begin(e.name, e.enqueue_time, t_drain) \
                    or _SPAN_DROPPED
        # ``groups``: the ids the calling thread's ``hvd/update/submit``
        # spans carry ('|'-joined: the profile splits stats at commas).
        groups = sorted({e.group_id for e in entries if e.group_id >= 0})
        with tr.span("hvd/cycle", n=len(entries),
                     groups="|".join(map(str, groups)),
                     waited_ms=round(waited_s * 1e3, 3)) as cyc:
            return self._negotiate_and_dispatch(entries, t_cycle0, tr, cyc,
                                                t_trace0, t_drain)

    def _set_idle_wait(self, idle: bool) -> None:
        """The wait before the next cycle: ``cycle_time_s`` after a cycle
        that did something, twice the last wait after one that did
        nothing, up to ``IDLE_WAIT_CAP_S`` — and to a quarter of the
        controller's round deadline, so a live idle rank never misses a
        round — but never under ``cycle_time_s``."""
        cycle = self.cycle_time_s
        if not idle:
            self.idle_wait_s = cycle
            return
        self.idle_cycles += 1
        cap = IDLE_WAIT_CAP_S
        deadline = getattr(self.controller, "round_timeout_s", 0.0)
        if deadline:
            cap = min(cap, deadline / 4)
        self.idle_wait_s = max(cycle, min(self.idle_wait_s * 2, cap))

    def _cycle_did_nothing(self, entries, responses) -> bool:
        """Whether this cycle may lengthen the next wait: nothing drained
        (a not-ready entry is requeued, so drained again), nothing
        dispatched or left in the backlog or the checkpoint staging, and a
        controller with no round in flight, no join pending or open (a
        joined rank synthesizes its peers' collectives and keeps their
        pace) and a last round that carried no verdict for ANY rank — the
        server broadcasts every verdict, so a rank outside a process set
        stays on the short wait while its peers' collectives stream."""
        if entries or responses or self._backlog or self._ckpt_staging:
            return False
        ctl = self.controller
        return ctl is None or (
            getattr(ctl, "last_round_quiet", True)
            and not getattr(ctl, "inflight_rounds", 0)
            and not getattr(ctl, "join_open", False))

    def _negotiate_and_dispatch(self, entries, t_cycle0: float, tr=None,
                                cyc=None, t_trace0: float = 0.0,
                                t_drain: float = 0.0):
        """The cycle past the queue's drain: negotiate, batch, dispatch,
        account.  Tracing armed, ``tr`` is the recorder and ``cyc`` the
        open ``hvd/cycle`` span.  Returns whether the cycle did nothing
        (``_cycle_did_nothing``)."""
        tl = self._state.timeline
        # Multi-process mode: every rank must complete a (possibly empty)
        # lock-step negotiation round each cycle, or peers with pending
        # tensors would block on this rank's missing frame.
        try:
            responses, not_ready = self._compute_response_list(entries)
        except BaseException as exc:  # noqa: BLE001 - propagate to waiters
            if isinstance(exc, ControlPlaneError):
                ctl = self.controller
                if ctl is not None and getattr(ctl, "interrupted", False):
                    # Expected teardown: basics.shutdown() severed the
                    # lock-step socket to unblock this thread, which makes
                    # the in-flight round fail exactly like a peer death.
                    # Not a fault — settle and exit quietly (stop() joins
                    # us next) instead of logging HVD303 and flipping
                    # /health to peer_dead on every clean multi-process
                    # shutdown.
                    pass
                else:
                    # A dead peer / missed round deadline: the control
                    # plane cannot recover in place — shut the engine down
                    # cleanly, settling EVERY outstanding waiter with the
                    # error (the elastic wrapper then restores +
                    # re-rendezvouses; static jobs fail fast with HVD303
                    # attribution instead of hanging).  MUST run before
                    # this cycle's waiters are released below: a waiter
                    # that wakes first reads engine.fault in
                    # basics.shutdown() to pick the abrupt teardown — a
                    # still-None fault would route a poisoned jax world
                    # through the graceful shutdown barrier it can never
                    # complete.
                    self._abort_engine(exc, busy=bool(entries))
            for e in entries:
                e.error = exc
                sp = _live_span(e) if tr is not None else None
                if sp is not None:
                    sp.error = True
                    tr.commit(sp)
                self.queue.mark_done(e)
                e.done.set()
            return False
        if not_ready:
            self.queue.requeue(not_ready)
        t_ready = 0.0
        if tr is not None:
            # The cycle id is the cross-rank correlation key — the
            # controller's lock-step round counter is identical on every
            # rank for the same round; single-controller mode uses the
            # local index.
            ctl = self.controller
            cyc_id = ctl.rounds if ctl is not None else self._cycle_index
            cyc.set(cycle=cyc_id)
            # Globally-ready verdict: negotiation phase closes.
            t_ready = time.monotonic()
            for batch in responses:
                for e in batch:
                    sp = _live_span(e)
                    if sp is None:
                        # ONLY synthesized join entries claim here (they
                        # never drained, so ready-time is their drain).
                        # An ordinary entry whose drain-time claim was
                        # dropped (ring full) stays untraced: re-claiming
                        # it now would fold its negotiation time into the
                        # queue phase and skew the attribution exactly
                        # under the load that saturates the ring.
                        if e.span is not None or \
                                not getattr(e, "trace_synthesized", False):
                            continue
                        sp = tr.begin(e.name, e.enqueue_time, t_ready)
                        e.span = sp or _SPAN_DROPPED
                    if sp is not None:
                        sp.t_ready = t_ready
                        sp.cycle = cyc_id
                        if ctl is not None and sp.slot < 0:
                            sp.slot = ctl.slot_of(e)
        cycle_chunks = 0
        ring = self._inflight_ring()
        if ring is None:
            for batch in responses:
                cycle_chunks += self._perform_operation(batch)
        else:
            # Preemptive dispatch backlog (ByteScheduler): ready batches
            # queue by (lane, priority, arrival) and each cycle dispatches
            # every fast-lane batch plus up to `max_inflight` fused
            # batches — leftovers wait HERE, where a later cycle's
            # higher-priority batch (or any fast-lane batch) overtakes
            # them.  This is what partitioning buys: a huge tensor's
            # remaining parts yield mid-transfer to a small hot gradient.
            # The budget is deliberately a pure function of knob + heap
            # state (never of local ring occupancy): every rank pushes
            # identical batches with identical (lane, priority, arrival)
            # keys, so every rank pops — and therefore LAUNCHES — in the
            # identical order, which cross-process XLA collectives
            # require.  An over-eager pop just blocks briefly in the
            # ring's bounded submit, exactly like the pre-backlog path.
            # Checkpoint-lane items (ISSUE 14) sort after BOTH gradient
            # lanes and never touch the fused budget — pop_gradient_
            # batches is the identical budget rule with a CKPT_LANE
            # guard, so gradient dispatch order is bitwise-unchanged
            # with checkpointing armed (pinned by the dispatch-order
            # tests).
            for batch in responses:
                if batch[0].fast_lane:
                    lane = FAST_LANE
                elif batch[0].prefetch:
                    # FSDP parameter gathers (ISSUE 18): after FAST,
                    # before FUSED, budget-exempt — bucket k+1's gather
                    # launches ahead of the gradient stream without
                    # consuming its in-flight budget or reordering it.
                    lane = PREFETCH_LANE
                    self.prefetch_dispatches += 1
                    for e in batch:
                        sp = _live_span(e)
                        if sp is not None:
                            sp.prefetch = True
                else:
                    lane = FUSED_LANE
                prio = max(e.priority for e in batch)
                heapq.heappush(self._backlog,
                               (lane, -prio, next(self._backlog_seq), batch))
            for batch in pop_gradient_batches(
                    self._backlog, max(1, int(self.max_inflight))):
                cycle_chunks += self._perform_operation(batch)
        # Checkpoint-lane tail (both dispatch modes): once no gradient
        # batch remains poppable this cycle, a bounded number of shard-
        # chunk writes ride the cycle's tail — the overlap-scheduled
        # durability stream.
        for item in pop_checkpoint_items(self._backlog,
                                         self.ckpt_lane_budget):
            self._run_ckpt_item(item)
        if self._backlog:
            # Leftovers (either lane) must not wait out a long cycle
            # timer: run the next cycle (and its negotiation round)
            # immediately.
            self._wake.set()
        if responses:
            self.last_cycle_chunks = cycle_chunks
            if tl is not None and tl.enabled:
                tl.counter("pipeline", {
                    "chunks": cycle_chunks,
                    "inflight": len(self._inflight)
                    if self._inflight is not None else 0})
        if tr is not None and responses:
            ctl = self.controller
            tr.cycle(ctl.rounds if ctl is not None else self._cycle_index,
                     t_trace0, t_drain, t_ready, time.monotonic(),
                     sum(len(b) for b in responses),
                     self.last_negotiation_us if ctl is not None else 0.0)
        if self.autotuner is not None and self.autotuner.tuning:
            nbytes = sum(e.tensor.nbytes for b in responses for e in b
                         if e.tensor is not None)
            self.autotuner.on_cycle(nbytes)
        dt_us = (time.perf_counter() - t_cycle0) * 1e6
        self.cycle_us_total += dt_us
        self.cycle_count += 1
        self.last_cycle_ts = time.time()
        if self.monitor is not None:
            self.monitor.on_cycle(dt_us)
        return self._cycle_did_nothing(entries, responses)

    # --------------------------------------------------------- negotiation
    def _compute_response_list(self, entries) -> List[List[TensorTableEntry]]:
        """Group ready entries into fused batches (reference: N2
        ``ComputeResponseList``).

        Local mode: all entries are ready.  Grouped entries (group_id >= 0)
        must land in one batch (reference: group_table N13).  Batches are
        split at the fusion threshold, never across fusion keys.

        Returns ``(batches, not_ready)``; not-ready entries (multi-process
        negotiation) are re-queued by the caller for the next cycle.
        """
        not_ready: List[TensorTableEntry] = []
        if self.controller is not None:
            self.controller.synthesizer = self._synthesize_join_entry
            self.controller.slot_drop_hook = self._on_slot_drop
            # Zero-RTT dispatch-safety gate (protocol v7): a speculative
            # verdict is dispatched before peers have its real verdict,
            # so this thread must stay free to keep serving them rounds —
            # only the async in-flight window qualifies.  The serialized-
            # launch CPU tier (and an inline-settling window) block the
            # cycle thread inside the collective: a speculating rank
            # would starve the peer of the very frame it needs to launch,
            # deadlocking the fleet.  Pipelined rounds are unaffected
            # (a deferred verdict is already in every rank's buffer).
            self.controller.spec_dispatch_ok = (
                not self._serialize_launches and self.max_inflight > 1)
            tr = self.tracer
            t0 = time.perf_counter()
            if tr is None:
                ready, errored = self.controller.negotiate(entries)
            else:
                # the interval negotiation_us_total times, as a span
                with tr.span("hvd/cycle/negotiate") as sp:
                    ready, errored = self.controller.negotiate(entries)
                    sp.set(cycle=self.controller.rounds)
            dt_us = (time.perf_counter() - t0) * 1e6
            self.negotiation_us_total += dt_us
            self.negotiation_cycles += 1
            self.last_negotiation_us = dt_us
            tl0 = self._state.timeline
            if tl0 is not None and tl0.enabled:
                st = self.controller.cache_stats
                ctl0 = self.controller
                tl0.counter("negotiation", {
                    "us": round(dt_us, 1), "cache_hits": st.hits,
                    "cache_misses": st.misses,
                    "cache_invalidations": st.invalidations,
                    # Zero-RTT speculation/pipelining (protocol v7).
                    "spec_hits": getattr(ctl0, "spec_hits", 0),
                    "spec_mispredicts": getattr(ctl0, "spec_mispredicts",
                                                0),
                    "inflight_rounds": getattr(ctl0, "inflight_rounds",
                                               0)})
            # Per-tensor negotiation failures (shape/dtype divergence across
            # ranks): fail ONLY those waiters; the runtime stays up
            # (reference: per-tensor error Responses, SURVEY.md N2).
            from ..common.controller import NegotiationError
            # Grouped ops are atomic (reference N13): one member failing
            # negotiation fails every local member of its group.  Name
            # sequences are aligned across ranks (see enqueue naming), so
            # every rank fails the same group deterministically.
            bad_groups = {e.group_id for e, _ in errored if e.group_id >= 0}
            if bad_groups:
                by_handle = {e.handle for e, _ in errored}
                for e in entries:
                    if e.group_id in bad_groups and e.handle not in by_handle:
                        errored.append((e, f"grouped collective aborted: a "
                                        f"member of group {e.group_id} failed "
                                        f"negotiation"))
                        # The member may still be mid-negotiation: clear the
                        # controller's announce bookkeeping so a retried op
                        # reusing the name renegotiates from scratch.
                        self.controller.forget(e)
            tl = self._state.timeline
            for e, msg in errored:
                e.error = NegotiationError(msg)
                if tl is not None:
                    tl.end_activity(e.name, "QUEUE")
                sp = _live_span(e) if tr is not None else None
                if sp is not None:
                    sp.error = True
                    tr.commit(sp)
                self.queue.mark_done(e)
                # A failed entry is finished: clear the stall inspector's
                # live-stall state (and warn latch) like any completion.
                self.stall.progressed(e.name)
                e.done.set()
            errored_handles = {e.handle for e, _ in errored}
            done_handles = {e.handle for e in ready} | errored_handles
            not_ready = [e for e in entries if e.handle not in done_handles]
            entries = [e for e in ready if e.handle not in errored_handles]
            left = getattr(self.controller, "left_ranks", None)
            if left:
                # Clean world shrink (protocol v6 leave notice): world-level
                # verdicts were computed over the SHRUNK control-plane
                # world, but the data-plane world is still the old fixed
                # size — executing them would wedge the transport.  Fail
                # every default-process-set entry (ready AND still-pending)
                # with PeerLeftInterrupt: not a fault, /health stays ok,
                # and the elastic wrapper re-rendezvouses keeping current
                # parameters.  Sub-process-set collectives that exclude
                # the leavers keep flowing.
                if self._world_changed is None:
                    from ..common.exceptions import PeerLeftInterrupt
                    self._world_changed = PeerLeftInterrupt(left)
                exc_left = self._world_changed
                keep_r: List[TensorTableEntry] = []
                keep_nr: List[TensorTableEntry] = []
                poisoned: List[TensorTableEntry] = []
                for src, kept in ((entries, keep_r), (not_ready, keep_nr)):
                    for e in src:
                        if getattr(e, "process_set_id", 0) == 0:
                            self.controller.forget(e)
                            poisoned.append(e)
                        else:
                            kept.append(e)
                self._settle_queued(poisoned, exc_left)
                for e in poisoned:
                    self.stall.progressed(e.name)
                entries, not_ready = keep_r, keep_nr
                # Zero-RTT race closure (protocol v7): a SPECULATIVE
                # dispatch may have preceded this notice by one round — a
                # world collective launched from a predicted verdict in
                # the very round the leaver departed was never dispatched
                # by the leaver and can never complete (lock-step's
                # poison-before-dispatch guarantee does not cover it,
                # because the verdict was consumed before the notice was
                # readable).  With speculation armed, settle the
                # in-flight window with the same re-rendezvous interrupt
                # instead of letting its waiters wedge on a dead
                # collective: the elastic wrapper restores and re-runs
                # the step, exactly like any other world change.
                ctl2 = self.controller
                if (self._inflight is not None and len(self._inflight)
                        and getattr(ctl2, "spec_ready_after", 0) > 0
                        and getattr(ctl2, "spec_dispatch_ok", False)):
                    self._inflight.abort(exc_left)
        for e in entries:
            if self._state.timeline is not None:
                self._state.timeline.end_activity(e.name, "QUEUE")
                self._state.timeline.start_activity(
                    e.name, f"NEGOTIATE_{e.ctype.name}")
        self.stall.check(entries + not_ready)

        # Batching must be a pure function of the NEGOTIATED entry order —
        # never of local handle/group counters, which differ across ranks
        # (every rank must build byte-identical fused programs).  Grouped
        # members are pulled together at the first member's position.
        #
        # Latency fast lane: sub-threshold ungrouped allreduces skip the
        # fusion buffer entirely — each becomes its own single-tensor
        # batch, dispatched FIRST (they are the latency-critical blocking
        # ops; the threshold is identical on every rank, and nbytes
        # derives from the negotiated shape/dtype, so the fork is
        # deterministic fleet-wide).  Partitioned sub-tensors likewise
        # stay single-entry batches: the part — not the re-fused whole —
        # is the preemption unit.
        fast: List[TensorTableEntry] = []
        thr = self.fast_lane_threshold
        if thr > 0:
            rest: List[TensorTableEntry] = []
            for e in entries:
                if (e.group_id < 0 and e.partition is None
                        and e.ctype == CollectiveType.ALLREDUCE
                        and e.tensor is not None and e.tensor.nbytes < thr):
                    e.fast_lane = True
                    fast.append(e)
                else:
                    rest.append(e)
            entries = rest
        batches: List[List[TensorTableEntry]] = [[e] for e in fast]

        clusters: List[List[TensorTableEntry]] = []
        seen_groups: set = set()
        for e in entries:
            if e.group_id >= 0:
                if e.group_id in seen_groups:
                    continue
                seen_groups.add(e.group_id)
                clusters.append([m for m in entries
                                 if m.group_id == e.group_id])
            else:
                clusters.append([e])

        by_key: Dict[Tuple, List[List[TensorTableEntry]]] = {}
        for members in clusters:
            if members[0].partition is not None:
                batches.append(members)       # one batch per part, never
                continue                      # re-fused past the split
            by_key.setdefault(_fusion_key(members[0]), []).append(members)
        for key, key_clusters in by_key.items():
            cur: List[TensorTableEntry] = []
            cur_bytes = 0
            for members in key_clusters:
                mbytes = sum(m.tensor.nbytes for m in members
                             if m.tensor is not None)
                if cur and cur_bytes + mbytes > self.fusion_threshold:
                    batches.append(cur)
                    cur, cur_bytes = [], 0
                cur.extend(members)
                cur_bytes += mbytes
            if cur:
                batches.append(cur)
        return batches, not_ready

    # ----------------------------------------------------------- execution
    def _perform_operation(self, batch: List[TensorTableEntry]) -> int:
        """Dispatch one fused batch; returns its chunk count.

        With the in-flight window active (multi-process, MAX_INFLIGHT > 1)
        the entries are NOT settled here: the async launch enters the
        bounded ring and the completion watcher settles ``e.done`` off this
        thread, so the cycle thread proceeds straight to negotiating the
        next round while the device executes this one."""
        tl = self._state.timeline
        for e in batch:
            if tl is not None:
                tl.end_activity(e.name, f"NEGOTIATE_{e.ctype.name}")
                tl.start_activity(e.name, f"XLA_{e.ctype.name}")
        pp = self._pingpong
        if pp is not None and not batch[0].fast_lane:
            # Double-buffered fusion staging: claim one of the two ping-
            # pong slots per dtype group before launching, released by the
            # InflightRing watcher at settle — cycle N+1's copy_in may
            # overlap cycle N's reduce, N+2's may not.  Fast-lane batches
            # skip it: they stage no fusion buffer.
            keys = sorted({str(e.tensor.dtype) for e in batch
                           if e.tensor is not None})
            if keys:
                self._staging_tokens[id(batch)] = [pp.acquire(k)
                                                   for k in keys]
        tr = self.tracer
        try:
            if tr is None:
                results, chunks = self._execute_batch(batch)
            else:
                # program fetch or build + the async launch; ``hit`` is
                # the fused-program cache's (a pinned fast-lane program
                # never asks it, so reads as one).
                misses = self.cache.misses
                with tr.span("hvd/cycle/dispatch", cycle=_span_cycle(batch),
                             n=len(batch),
                             bytes=self._batch_payload_bytes(batch)) as sp:
                    results, chunks = self._execute_batch(batch)
                    sp.set(hit=int(self.cache.misses == misses))
        except BaseException as exc:  # noqa: BLE001 - propagate to waiters
            self._settle_batch(batch, None, exc)
            return 0
        if tr is not None:
            # copy_in phase closes: the fused program (fetch/build + the
            # async XLA launch — the fusion copy-in lives inside it) has
            # been dispatched; reduce runs from here to settle.  Fast-lane
            # entries served by a pinned program were already stamped
            # pre-invoke (their copy_in is the O(1) pin fetch — the
            # device wait belongs to the reduce phase); never restamp.
            t_launch = time.monotonic()
            for e in batch:
                sp = _live_span(e)
                if sp is not None and not sp.t_launch:
                    sp.t_launch = t_launch
        self.pipeline_chunks_total += chunks
        self.pipeline_dispatches += 1
        if batch[0].fast_lane:
            self.fast_lane_dispatches += 1
        ring = self._inflight_ring()
        if ring is None:
            with self._settle_span(batch):
                self._settle_batch(batch, results)
        else:
            if tl is not None:
                for e in batch:
                    tl.start_activity(e.name, "INFLIGHT")
            ring.submit(batch, results)
        return chunks

    def _settle_span(self, batch):
        """``hvd/settle`` for one batch: from blocking on its results (the
        in-flight watcher; the inline settle blocks on nothing) to the
        last ``done.set()``."""
        tr = self.tracer
        if tr is None:
            return _TRACE_OFF
        return tr.span("hvd/settle", cycle=_span_cycle(batch), n=len(batch))

    def _settle_batch(self, batch: List[TensorTableEntry], results,
                      error: Optional[BaseException] = None,
                      inflight: bool = False):
        """Completion epilogue (cycle thread inline, or the in-flight
        watcher): assign results/error, close timeline lanes, release
        waiters.  Must never raise — a lost settle hangs synchronize()."""
        tl = self._state.timeline
        tr = self.tracer
        t_result = time.monotonic() if tr is not None else 0.0
        tokens = self._staging_tokens.pop(id(batch), None)
        if tokens is not None and self._pingpong is not None:
            # Hand the ping-pong staging slots back FIRST: the cycle
            # thread may be blocked in acquire() waiting on exactly this
            # settle.  Idempotent per token — an abort that already
            # settled them is a no-op.
            for tok in tokens:
                self._pingpong.release(tok)
        if error is None:
            for e, r in zip(batch, results):
                e.result = r
        else:
            for e in batch:
                e.error = error
        for e in batch:
            try:
                if tl is not None:
                    if inflight:
                        tl.end_activity(e.name, "INFLIGHT")
                    tl.end_activity(e.name, f"XLA_{e.ctype.name}")
                sp = _live_span(e) if tr is not None else None
                if sp is not None:
                    sp.t_result = t_result
                    sp.t_done = time.monotonic()
                    sp.error = error is not None
                    tr.commit(sp)
                self.queue.mark_done(e)
                self.stall.progressed(e.name)
            except Exception:  # noqa: BLE001 - keep settling the rest
                # Timeline I/O (disk full, closed file) must never cost a
                # waiter its done signal — a lost set() is a hang, and on
                # the watcher thread it would take the whole window down.
                log.exception("settle bookkeeping failed for %r", e.name)
            finally:
                e.done.set()

    def _inflight_ring(self) -> Optional[InflightRing]:
        """The bounded dispatch window, or None for inline settling.

        Only the multi-process engine pipelines: single-controller cycles
        have no negotiation to overlap, and the inline-kick latency path
        relies on same-thread settling.  (The controller attaches after
        construction, hence the lazy build.)  CPU keeps launches serialized
        via ``_serialize_launches`` — the ring then only moves *settling*
        off the cycle thread, which still exercises the full machinery in
        the hermetic tier without the rendezvous-starvation hazard."""
        if self.max_inflight <= 1 or self.controller is None:
            return None
        if self._inflight is None:
            self._inflight = InflightRing(
                jax.block_until_ready,
                lambda b, r, err: self._settle_batch(b, r, err,
                                                     inflight=True),
                depth=self.max_inflight, span=self._settle_span)
            # Double-buffered fusion staging rides the same lifecycle: the
            # ring's watcher is what hands the ping-pong slots back.
            self._pingpong = PingPongBuffers(slots=2)
        else:
            self._inflight.depth = max(1, int(self.max_inflight))
        return self._inflight

    def _mesh_axis(self, ps_id: int):
        ps = self._state.process_set_table.get(ps_id)
        return ps.mesh, ps.axis_name, ps.size()

    @staticmethod
    def _join_fill_value(ctype: CollectiveType, op: C.ReduceOp, dt: np.dtype):
        """A joined rank's implicit contribution: the reduction's IDENTITY
        element, so it cannot perturb the peers' result (reference: hvd.join
        'a tensor of zeros' — generalized to non-additive ops; plain zeros
        would zero out a PRODUCT or clamp a MAX of negatives)."""
        if ctype not in (CollectiveType.ALLREDUCE,
                         CollectiveType.REDUCESCATTER):
            return 0          # broadcast/allgather/alltoall payload: zeros
        if op == C.ReduceOp.PRODUCT:
            return 1
        if op in (C.ReduceOp.MIN, C.ReduceOp.MAX):
            hi = op == C.ReduceOp.MIN    # identity for MIN is the dtype max
            if dt == np.bool_:
                return hi
            try:
                info = np.finfo(dt)
            except ValueError:
                # numpy's finfo rejects ml_dtypes (bf16/fp8: "not inexact")
                # and iinfo rejects them too ("invalid integer data type V")
                # — ml_dtypes ships its own finfo for exactly this.
                try:
                    import ml_dtypes
                    info = ml_dtypes.finfo(dt)
                except ValueError:
                    info = np.iinfo(dt)
            return info.max if hi else info.min
        return 0              # SUM / AVERAGE (divisor stays world) / ADASUM

    def _synthesize_join_entry(self, name: str, digest: str,
                               group_id: int = -1) -> TensorTableEntry:
        """Implicit-contribution entry for a peer's collective while this
        rank is JOINED (reference: hvd.join).  The digest (the same one
        negotiation checks for consistency) carries op/dtype/shape/root,
        and the server-echoed group id preserves grouped batching, so this
        rank builds and executes the byte-identical fused program with a
        local identity contribution.
        """
        handle = next(self._handle_counter)
        now = time.monotonic()   # fresh age: must not trip the stall check
        if digest == "barrier":
            e = TensorTableEntry(handle=handle, name=name,
                                 ctype=CollectiveType.BARRIER, tensor=None,
                                 enqueue_time=now)
            # Tracer marker: synthesized entries never drain, so their
            # span is claimed at the ready verdict instead (and ONLY for
            # entries carrying this flag).
            e.trace_synthesized = True
            if self.sanitizer is not None:
                # The peer advanced its per-set seq by submitting; advance
                # ours too or every post-join collective mismatches on seq.
                self.sanitizer.observe_synthesized(e)
            return e
        parts = digest.split("|")
        ctype = CollectiveType(parts[0])
        dt = _np_dtype(parts[1])
        import ast
        shape = tuple(ast.literal_eval(parts[2]))
        op = C.ReduceOp[parts[3]]
        root = int(parts[4])
        pre = None if parts[5] == "None" else float(parts[5])
        post = None if parts[6] == "None" else float(parts[6])
        comp = None
        if len(parts) > 7 and parts[7] in ("bf16", "fp16"):
            # parts[7] is the wire-compression slot ("none" when off); the
            # server may append the sanitizer tag after it — trailing
            # parts stay ignored as before.
            comp = parts[7]
        # ZeRO-sharded digest dimension (appended ONLY for sharded ops, so
        # flat digests are byte-identical to the pre-sharding protocol):
        # the synthesized entry must carry the flag or its fusion key —
        # and therefore its fused program — would diverge from the peers'.
        # "sharded-full" (ISSUE 18) is the FSDP plane's token — a full-
        # sharded program must never cross-serve a state-only one.
        sharded: Any = False
        if len(parts) > 8:
            if parts[8] == "sharded":
                sharded = True
            elif parts[8] == "sharded-full":
                sharded = "full"
        ps = self._state.process_set_table.get(0)
        sharding = NamedSharding(ps.mesh, P(ps.axis_name))
        local_devs = [d for d in ps.mesh.devices.flat
                      if d.process_index == jax.process_index()]
        fill = np.full((1,) + shape,
                       self._join_fill_value(ctype, op, dt), dt)
        shards = [jax.device_put(fill, d) for d in local_devs]
        arr = jax.make_array_from_single_device_arrays(
            (ps.size(),) + shape, sharding, shards)
        e = TensorTableEntry(
            handle=handle, name=name, ctype=ctype, tensor=arr, reduce_op=op,
            root_rank=root, prescale_factor=pre, postscale_factor=post,
            group_id=group_id, donate=True, compression=comp,
            sharded=sharded, enqueue_time=now)
        e.trace_synthesized = True
        if self.sanitizer is not None:
            self.sanitizer.observe_synthesized(e)
        return e

    def _slice_topology(self, ps_id: int):
        """The slice-level structure of this process set's world
        (``parallel/topology.py``), derived once and cached, or None.

        Precedence: ``HOROVOD_SLICE_MAP`` (explicit override, CPU/
        simulated worlds) → device ``slice_index`` attributes (real
        multi-slice TPU) → ``HOROVOD_HIERARCHICAL_LOCAL_SIZE`` →
        per-process device counts (the PR-3 host-based derivation).
        Only the global process set is eligible — subgroup process sets
        keep the flat path.  A malformed slice map logs once and falls
        back flat instead of killing the cycle thread."""
        if ps_id != 0:
            return None
        if ps_id in self._slice_topos:
            return self._slice_topos[ps_id]
        from ..parallel import topology as slice_topo
        topo = self._state.topology
        ps = self._state.process_set_table.get(ps_id)
        devs = list(np.asarray(ps.mesh.devices).reshape(-1))
        try:
            st = slice_topo.slice_topology(
                devs, slice_map=self.slice_map,
                local_size=self._hier_local_size,
                local_counts=(topo.local_counts
                              if topo is not None else None))
        except ValueError as exc:
            # One-time attributed fallback (ISSUE 18 satellite): the topo
            # is cached per process set, so mixed-size fleets get exactly
            # one warning naming the offending slice sizes (the ValueError
            # text carries them) plus a monitor-scrapable counter — not a
            # silent flat path.
            self.slice_map_fallbacks += 1
            log.warning(
                "HOROVOD_SLICE_MAP rejected for process set %d (%s); "
                "hierarchical allreduce/allgather stay FLAT on this fleet "
                "— fix the slice map to uniform sizes to re-enable "
                "two-level collectives", ps_id, exc)
            st = None
        self._slice_topos[ps_id] = st
        return st

    def _hier_mesh(self, ps_id: int):
        """2-D (cross, local) mesh for two-level collectives, or None.

        Reference parity: ``HOROVOD_HIERARCHICAL_ALLREDUCE`` in
        ``horovod/common/ops/nccl_operations.cc`` (SURVEY.md N17) splits the
        world into NCCL-intra-node × MPI-cross-node; here the split is
        local = ICI within a slice, cross = DCN between slices, with the
        membership derived by ``_slice_topology``.  Ranks are slice-major
        (``common.topology.ordered_devices`` sorts slice_index first), so
        the reshape lays every slice along the ``local`` axis and the
        cross axis walks the leader ring in rank order — the DCN ring
        order derived from leader torus coordinates at rank assignment."""
        st = self._slice_topology(ps_id)
        if st is None:
            return None
        ps = self._state.process_set_table.get(ps_id)
        devs = np.asarray(ps.mesh.devices).reshape(st.num_slices,
                                                   st.local_size)
        return Mesh(devs, ("cross", "local"))

    def _hier_decision(self, e0: "TensorTableEntry", nbytes: int) -> bool:
        """Per-batch flat-vs-two-level verdict — a pure function of the
        negotiated batch (op/dtype/bytes), the engine knobs, and the
        fleet-static slice topology, so every rank decides identically
        with ZERO control-plane traffic (the knobs ride neither the
        digest nor the announce, same rule as HOROVOD_PIPELINE_CHUNK).

        ``nbytes`` counts per-rank payload bytes: the crossover trades
        the two extra phase latencies against the DCN byte savings,
        which scale with what each rank actually moves."""
        if e0.hierarchical is False:
            return False
        if e0.hierarchical is None and not self.hierarchical_allreduce:
            return False
        if e0.ctype != CollectiveType.ALLREDUCE:
            return False
        if e0.reduce_op not in (C.ReduceOp.SUM, C.ReduceOp.AVERAGE,
                                C.ReduceOp.MIN, C.ReduceOp.MAX,
                                C.ReduceOp.ADASUM):
            return False
        if e0.hierarchical is None and nbytes < self.hier_threshold_bytes:
            return False
        st = self._slice_topology(e0.process_set_id)
        if st is None:
            return False
        if e0.reduce_op == C.ReduceOp.ADASUM:
            # Two-level VHD needs power-of-two extents at both levels.
            from ..parallel.topology import hier_bit_orders
            if hier_bit_orders(st.local_size, st.num_slices) is None:
                return False
        return True

    def _hier_ag_decision(self, e0: "TensorTableEntry") -> bool:
        """Per-entry flat-vs-two-level verdict for allgather (ISSUE 18
        satellite — ``HOROVOD_HIERARCHICAL_ALLGATHER`` was a no-op knob
        until now).  Same override semantics as ``_hier_decision`` and the
        same zero-control-plane property: a pure function of the entry's
        ``hierarchical`` override, the engine knob, and the fleet-static
        slice topology.  No payload crossover — a two-level gather moves
        the same total bytes as flat (every rank still receives the full
        [world, *S] result); the win is that only the leader ring crosses
        DCN, so the decision is purely topological."""
        if e0.ctype != CollectiveType.ALLGATHER:
            return False
        if e0.hierarchical is False:
            return False
        if e0.hierarchical is None and not self.hierarchical_allgather:
            return False
        return self._slice_topology(e0.process_set_id) is not None

    def _hier_bcast_decision(self, e0: "TensorTableEntry") -> bool:
        """Per-entry flat-vs-two-level verdict for broadcast (ISSUE 19
        satellite — serving's versioned weight fan-out is the workload).
        Same override semantics and zero-control-plane property as
        ``_hier_ag_decision``: pure function of the entry's
        ``hierarchical`` override, the engine knob, and the fleet-static
        slice topology.  No payload crossover — two-level broadcast
        moves the same bytes to every rank; the win is that only the
        root→leader exchange crosses DCN (fan-out rides ICI), so the
        decision is purely topological."""
        if e0.ctype != CollectiveType.BROADCAST:
            return False
        if e0.hierarchical is False:
            return False
        if e0.hierarchical is None and not self.hierarchical_broadcast:
            return False
        return self._slice_topology(e0.process_set_id) is not None

    def _batch_payload_bytes(self, batch) -> int:
        """Per-rank payload bytes of a fused batch (stacked tensors carry
        [world, *S]; the per-rank shard is what rides the wire)."""
        total = 0
        for e in batch:
            t = e.tensor
            if t is None:
                continue
            world = max(1, int(t.shape[0])) if t.ndim else 1
            total += t.nbytes // world
        return total

    def _chunk_plan(self, ctype: CollectiveType, shapes, dtypes) -> Tuple:
        """Per-dtype-group chunk counts for a fused reduction.

        A pure function of (chunk knob, per-rank shapes, dtypes): every rank
        computes the same plan from the same negotiated batch, so the fused
        programs stay byte-identical.  The *counts* — not the raw chunk
        byte values — key the program cache: retuning the knob only
        recompiles when the plan actually changes, keeping program count
        bounded.  Empty plan = chunking off or a non-reduction op (gathers
        and permutes have no cast/reduce/cast stages to overlap).

        Knob 0 is a true OFF, not "fusion-threshold-sized chunks": an
        atomic cluster (one grouped_allreduce of the whole model, or a
        single oversized tensor) is never split by the batch planner, so
        it can exceed the threshold — deriving chunks from it would
        silently chunk default-config workloads."""
        if ctype != CollectiveType.ALLREDUCE or self.pipeline_chunk_bytes <= 0:
            return ()
        chunk = max(1, int(self.pipeline_chunk_bytes))
        groups: Dict[str, Tuple[int, int]] = {}   # dtype -> (elems, bytes)
        for s, dt in zip(shapes, dtypes):
            n = int(np.prod(s[1:])) if len(s) > 1 else 1
            b = n * _np_dtype(dt).itemsize
            e_, b_ = groups.get(dt, (0, 0))
            groups[dt] = (e_ + n, b_ + b)
        return tuple(min(max(1, -(-b // chunk)), max(1, e))
                     for e, b in groups.values())

    def _on_slot_drop(self, slot: int):
        """Controller invalidation hook: a response-cache slot this client
        dropped (eviction / forget / trim / id reuse) takes its pinned
        persistent program with it."""
        self._fast_programs.pop(slot, None)

    def _fast_pin_key(self, e: TensorTableEntry):
        """Persistent-program pin key: the server-assigned response-cache
        slot (digest-scoped, coordinated invalidation) when known, the
        tensor name in single-controller mode (no slots exist; the
        validity compare below keeps name reuse sound)."""
        return e.cache_slot if e.cache_slot >= 0 else e.name

    def _execute_fast_lane(self, e: TensorTableEntry, hier_now: bool):
        """Dispatch a fast-lane entry through its pinned pre-compiled
        program — zero fusion-key construction, zero chunk planning, zero
        program-cache tuple hashing on the warm path; one dict probe and
        a handful of scalar compares.  ``hier_now`` is the batch's
        flat-vs-two-level verdict (``_hier_decision``): the pin stores
        the verdict its program was built under, so a threshold retune
        that flips the schedule drops the pin and rebuilds — never
        serves a flat program to a two-level decision or vice versa.
        Returns ``(results, chunks)`` or None (no valid pin yet — the
        caller takes the regular path and pins the program it builds)."""
        rec = self._fast_programs.get(self._fast_pin_key(e))
        if rec is None:
            return None
        (fkey, shape, dtype, donate, chunk_knob, hier, fn, chunks) = rec
        if (shape != e.tensor.shape or dtype != e.tensor.dtype
                or donate != e.donate
                or chunk_knob != self.pipeline_chunk_bytes
                or hier != hier_now
                or fkey != _fusion_key(e)):
            # Stale pin (name reuse under new params, knob retune, ...):
            # drop it; the regular path rebuilds and re-pins.
            self._fast_programs.pop(self._fast_pin_key(e), None)
            return None
        self.fast_lane_hits += 1
        tr = self.tracer
        if tr is not None:
            sp = _live_span(e)
            if sp is not None and not sp.t_launch:
                # copy_in closes HERE, before the invoke: the fast lane
                # stages no fusion buffer and fetches no key — the device
                # wait that follows belongs to the reduce phase (this is
                # what makes copy_in ≈ 0 on the fast lane in the phase
                # breakdown, tests/test_engine_fastlane.py).
                sp.t_launch = time.monotonic()
        outs = fn(e.tensor)
        if not isinstance(outs, (list, tuple)):
            outs = [outs]
        if self._serialize_launches:
            jax.block_until_ready(outs)
        return list(outs), chunks

    def _execute_batch(self, batch: List[TensorTableEntry]):
        """Build-or-fetch the fused program and launch it; returns
        ``(results, chunk_count)`` — results may still be async (the
        in-flight watcher blocks on them) unless ``_serialize_launches``."""
        e0 = batch[0]
        if e0.ctype == CollectiveType.BARRIER:
            return [None for _ in batch], 0
        # Two-level crossover verdict — once per batch, BEFORE the fast
        # lane probe (the pin's validity record compares against it) and
        # before the cache key (the DECISION keys the program, never the
        # raw knobs: retuning HOROVOD_HIER_THRESHOLD only recompiles when
        # a batch actually changes schedule, mirroring chunk-plan keying).
        if e0.ctype == CollectiveType.ALLGATHER:
            # Two-level allgather verdict (ISSUE 18 satellite): per-entry,
            # same override semantics as allreduce (e.hierarchical True
            # forces, False forces flat, None defers to the knob), no
            # payload threshold — the FSDP prefetch gathers that make
            # this path hot are full-bucket-sized by construction.
            hier = self._hier_ag_decision(e0)
        elif e0.ctype == CollectiveType.BROADCAST:
            # Two-level broadcast verdict (ISSUE 19 satellite): per-entry,
            # purely topological like allgather — the serving weight
            # fan-out that makes this path hot is whole-model-sized.
            hier = self._hier_bcast_decision(e0)
        else:
            hier = self._hier_decision(e0, self._batch_payload_bytes(batch))
        if hier and e0.ctype == CollectiveType.ALLGATHER:
            self.hier_ag_dispatches += 1
            self.hier_ag_intra_legs += 1  # intra-slice gather (ICI)
            self.hier_ag_cross_legs += 1  # cross-slice leader exchange (DCN)
        elif hier and e0.ctype == CollectiveType.BROADCAST:
            self.hier_bcast_dispatches += 1
            self.hier_bcast_cross_legs += 1  # root → slice leaders (DCN)
            self.hier_bcast_intra_legs += 1  # leader → slice fan-out (ICI)
        elif hier:
            self.hier_dispatches += 1
            self.hier_intra_legs += 2     # reduce-scatter + allgather (ICI)
            self.hier_cross_legs += 1     # leader-ring allreduce (DCN)
            tr = self.tracer
            if tr is not None:
                st = self._slice_topology(e0.process_set_id)
                from ..parallel.topology import cross_fraction
                frac = cross_fraction(self._batch_payload_bytes(batch),
                                      st.world, st.local_size)
                for e in batch:
                    sp = _live_span(e)
                    if sp is not None:
                        sp.cross_frac = frac
        if e0.fast_lane and len(batch) == 1:
            fast = self._execute_fast_lane(e0, hier)
            if fast is not None:
                return fast
        mesh, axis, world = self._mesh_axis(e0.process_set_id)
        shapes = tuple(tuple(e.tensor.shape) for e in batch)
        dtypes = tuple(str(e.tensor.dtype) for e in batch)
        donate = tuple(e.donate for e in batch)
        plan = self._chunk_plan(e0.ctype, shapes, dtypes)
        key = (_fusion_key(e0), shapes, dtypes, donate, hier, plan)
        fn, hit = self.cache.get_or_build2(
            key, lambda: self._build_program(e0, shapes, dtypes, mesh, axis,
                                             world, donate, plan,
                                             hier=hier))
        if e0.fast_lane and len(batch) == 1:
            # Pin the program for the next submission of this tensor: the
            # record stores exactly the inputs the program was built from,
            # so the warm-path validity check is a few scalar compares.
            pin = self._fast_programs
            pin[self._fast_pin_key(e0)] = (
                key[0], e0.tensor.shape, e0.tensor.dtype, e0.donate,
                self.pipeline_chunk_bytes, hier,
                fn, sum(plan) if plan else 1)
            if e0.cache_slot >= 0:
                # Cold start pinned under the NAME (the slot was still
                # unlearned at that dispatch); now that the slot-keyed pin
                # exists, drop the orphan — it would never be probed again
                # but would hold a compiled-program reference and crowd
                # live pins out of the capacity bound.
                pin.pop(e0.name, None)
            while len(pin) > max(16, self.cache.capacity):
                pin.pop(next(iter(pin)))
        if hit:
            outs = fn(*[e.tensor for e in batch])
        else:
            # First invocation compiles; donation is best-effort and ops
            # whose output cannot alias the input (e.g. allgather) make XLA
            # warn at compile time.  Suppress only around this cold-path
            # compile — steady-state dispatch stays untouched and user
            # code keeps its own donation diagnostics.
            import warnings
            with warnings.catch_warnings():
                warnings.filterwarnings(
                    "ignore", message="Some donated buffers were not usable")
                outs = fn(*[e.tensor for e in batch])
        if not isinstance(outs, (list, tuple)):
            outs = [outs]
        if self._serialize_launches:
            jax.block_until_ready(outs)
        return list(outs), (sum(plan) if plan else 1)

    # Builders: one jitted micro-program per (fusion key, shape set).  The
    # fused allreduce flattens every tensor's per-rank shard, concatenates
    # into one [world, total] buffer (the fusion buffer, living purely as an
    # XLA temporary in HBM — reference N7 without the memcpy machinery),
    # runs ONE collective, and splits results out.
    def _build_program(self, proto: TensorTableEntry, shapes, dtypes, mesh,
                       axis, world, donate=(), plan=(), hier=None):
        ctype = proto.ctype
        # Engine-owned input buffers are donated to XLA so the fused
        # program may alias them in HBM instead of allocating fresh
        # outputs (reference N7's in-place fusion buffer, the XLA way;
        # SURVEY.md §7 hard-part #2).  XLA ignores unusable donations.
        dargs = tuple(i for i, d in enumerate(donate) if d)

        def _jit(fn):
            return jax.jit(fn, donate_argnums=dargs)

        if ctype == CollectiveType.ALLREDUCE:
            if hier is None:
                # Direct callers carry no dispatch-time crossover verdict:
                # the engine knob decides, threshold treated as met (the
                # pre-crossover contract for knob-armed builds).
                hier = self._hier_decision(proto, self.hier_threshold_bytes)
            if hier:
                # The crossover verdict already proved the slice topology
                # exists and the op is eligible (_hier_decision).
                hmesh = self._hier_mesh(proto.process_set_id)
                if hmesh is not None:
                    return self._build_hier_allreduce(
                        proto, shapes, dtypes, hmesh, world, _jit, plan)
            return self._build_allreduce(proto, shapes, dtypes, mesh, axis,
                                         world, _jit, plan)
        if ctype == CollectiveType.BROADCAST:
            if hier is None:
                # Direct callers carry no dispatch-time verdict.
                hier = self._hier_bcast_decision(proto)
            if hier:
                # The verdict already proved the slice topology exists.
                hmesh = self._hier_mesh(proto.process_set_id)
                if hmesh is not None:
                    return self._build_hier_broadcast(
                        proto, shapes, hmesh, world, _jit)
            return self._build_broadcast(proto, shapes, mesh, axis, world,
                                         _jit)
        if ctype == CollectiveType.ALLGATHER:
            if hier is None:
                # Direct callers carry no dispatch-time verdict.
                hier = self._hier_ag_decision(proto)
            if hier:
                # The verdict already proved the slice topology exists.
                hmesh = self._hier_mesh(proto.process_set_id)
                if hmesh is not None:
                    return self._build_hier_allgather(
                        proto, shapes, hmesh, world, _jit)
            return self._build_allgather(proto, shapes, mesh, axis, world,
                                         _jit)
        if ctype == CollectiveType.REDUCESCATTER:
            return self._build_reducescatter(proto, shapes, mesh, axis,
                                             world, _jit)
        if ctype == CollectiveType.ALLTOALL:
            return self._build_alltoall(proto, shapes, mesh, axis, world,
                                        _jit)
        raise ValueError(f"Unsupported collective: {ctype}")

    def _build_fused_reduce(self, proto, shapes, dtypes, mesh_, in_spec,
                            reduce_flat, _jit, plan=()):
        """Shared fused-reduction scaffold (flat + hierarchical allreduce):
        flatten each tensor's per-rank shard, concatenate per dtype (one
        reduce per distinct dtype — XLA's collective combiner merges them
        into a single wire transfer, keeping mixed-dtype groups atomic
        without promotion), apply pre/post scaling around ``reduce_flat``,
        and slice results back out.

        Wire compression (``proto.compression``): floating dtype groups are
        cast down to the wire dtype right before ``reduce_flat`` and cast
        back up right after, INSIDE the jitted program — XLA fuses both
        casts into the collective's producer/consumer, so the bytes over
        ICI halve with zero extra launches.  Prescale happens in the
        original dtype (before the down-cast) and postscale after the
        up-cast, keeping the lossy window as narrow as possible.

        Chunked pipelining (``plan``, one chunk count per dtype group in
        first-occurrence order): the fused flat buffer is split into even
        chunks and each chunk rides its own cast-down → reduce → cast-up
        stage, so XLA overlaps chunk i+1's casts with chunk i's collective
        (software-pipelined ICI).  Chunk boundaries never change which
        ranks reduce which element, so results are bitwise-identical to
        the single-chunk program."""
        pre, post = proto.prescale_factor, proto.postscale_factor
        wire = {"bf16": jnp.bfloat16, "fp16": jnp.float16}.get(
            proto.compression)
        per_rank_shapes = [s[1:] for s in shapes]
        sizes = [int(np.prod(s)) if s else 1 for s in per_rank_shapes]
        dtype_groups: Dict[str, List[int]] = {}
        for i, dt in enumerate(dtypes):
            dtype_groups.setdefault(dt, []).append(i)
        chunk_counts = list(plan) if plan else [1] * len(dtype_groups)

        def reduce_wire(flat):
            if (wire is not None and flat.dtype != wire
                    and jnp.issubdtype(flat.dtype, jnp.floating)):
                return reduce_flat(flat.astype(wire)).astype(flat.dtype)
            return reduce_flat(flat)

        def reduce_chunked(flat, nch):
            if nch <= 1 or flat.shape[0] <= 1:
                return reduce_wire(flat)
            per = -(-flat.shape[0] // nch)     # ceil; last chunk shorter
            return jnp.concatenate(
                [reduce_wire(flat[i * per:(i + 1) * per])
                 for i in range(nch)])

        def per_shard(*xs):
            # xs: per-rank values, each [*S] — flatten, fuse per dtype.
            outs: List[Any] = [None] * len(xs)
            for (dt, idxs), nch in zip(dtype_groups.items(), chunk_counts):
                flat = jnp.concatenate([xs[i].reshape(-1) for i in idxs]) \
                    if len(idxs) > 1 else xs[idxs[0]].reshape(-1)
                red = C._scale(reduce_chunked(C._scale(flat, pre), nch),
                               post)
                off = 0
                for i in idxs:
                    outs[i] = red[off:off + sizes[i]].reshape(per_rank_shapes[i])
                    off += sizes[i]
            return tuple(outs)

        def wrapper(*xs):
            # Each stacked input [world, *S] → shard [1, *S]; reshape inside.
            def body(*shards):
                return per_shard(*[s.reshape(s.shape[1:]) for s in shards])
            return shard_map(body, mesh=mesh_,
                             in_specs=tuple(in_spec for _ in shapes),
                             out_specs=tuple(P() for _ in shapes),
                             check_vma=False)(*xs)

        return _jit(wrapper)

    def _build_allreduce(self, proto, shapes, dtypes, mesh, axis, world,
                         _jit=jax.jit, plan=()):
        op = proto.reduce_op

        def reduce_flat(flat):
            if op in (C.ReduceOp.AVERAGE, C.ReduceOp.SUM):
                red = lax.psum(flat, axis)
                if op == C.ReduceOp.AVERAGE:
                    red = red / jnp.asarray(world, red.dtype) if jnp.issubdtype(
                        red.dtype, jnp.floating) else red // world
            elif op == C.ReduceOp.MIN:
                red = lax.pmin(flat, axis)
            elif op == C.ReduceOp.MAX:
                red = lax.pmax(flat, axis)
            elif op == C.ReduceOp.PRODUCT:
                g = lax.all_gather(flat, axis)
                red = jnp.prod(g, axis=0)
            elif op == C.ReduceOp.ADASUM:
                if world & (world - 1) == 0 and world > 1:
                    # Power-of-two world: true vector-halving-doubling over
                    # collective-permute — log2(n) rounds riding ICI
                    # neighbor links, ~2·|x| bytes per rank instead of the
                    # gather tree's n·|x| (reference adasum_mpi_operations
                    # VHDD; SURVEY.md §2c "re-derive halving-doubling on
                    # the torus axes").  Rounds walk physical torus axes
                    # innermost-first when coords exist.
                    from ..common.topology import torus_dims
                    from ..parallel.adasum import (adasum_allreduce_hd,
                                                   torus_bit_order)
                    try:
                        dims = torus_dims(list(mesh.devices.flat))
                    except Exception:  # pragma: no cover - cpu meshes
                        dims = None
                    red = adasum_allreduce_hd(
                        flat, axis, bit_order=torus_bit_order(world, dims))
                else:
                    # Non-power-of-two fallback: gather + pairwise tree.
                    from ..parallel.adasum import adasum_allreduce
                    red = adasum_allreduce(flat, axis)
            else:
                raise ValueError(f"Unknown ReduceOp {op}")
            return red

        return self._build_fused_reduce(proto, shapes, dtypes, mesh, P(axis),
                                        reduce_flat, _jit, plan)

    def _build_broadcast(self, proto, shapes, mesh, axis, world,
                         _jit=jax.jit):
        root = proto.root_rank

        def body(*shards):
            outs = []
            for s in shards:
                x = s.reshape(s.shape[1:])
                idx = lax.axis_index(axis)
                if jnp.issubdtype(x.dtype, jnp.bool_):
                    m = jnp.where(idx == root, x, False)
                    outs.append(lax.psum(m.astype(jnp.int32), axis).astype(jnp.bool_))
                else:
                    m = jnp.where(idx == root, x, jnp.zeros_like(x))
                    outs.append(lax.psum(m, axis))
            return tuple(outs)

        return _jit(shard_map(
            body, mesh=mesh,
            in_specs=tuple(P(axis) for _ in shapes),
            out_specs=tuple(P() for _ in shapes), check_vma=False))

    def _build_hier_broadcast(self, proto, shapes, hmesh, world,
                              _jit=jax.jit):
        """Two-level broadcast: leader exchange (cross/DCN) → intra
        fan-out (local/ICI).

        The root masks everyone else to zero (same trick as the flat
        builder), then ``psum("cross")`` lands the payload on the one
        rank per slice that shares the root's local index (the DCN leg —
        only L-1 slice leaders receive across the slow links), and
        ``psum("local")`` fans it out within each slice over ICI.  Only
        zeros are ever summed with the payload, so the result is
        bitwise-identical to flat for every dtype.
        """
        root = proto.root_rank
        local_size = int(hmesh.devices.shape[1])
        root_cross, root_local = divmod(root, local_size)

        def body(*shards):
            outs = []
            at_root = jnp.logical_and(
                lax.axis_index("cross") == root_cross,
                lax.axis_index("local") == root_local)
            for s in shards:
                x = s.reshape(s.shape[1:])
                if jnp.issubdtype(x.dtype, jnp.bool_):
                    m = jnp.where(at_root, x, False).astype(jnp.int32)
                    m = lax.psum(m, "cross")      # root → slice leaders
                    m = lax.psum(m, "local")      # leaders → slice fan-out
                    outs.append(m.astype(jnp.bool_))
                else:
                    m = jnp.where(at_root, x, jnp.zeros_like(x))
                    m = lax.psum(m, "cross")      # root → slice leaders
                    outs.append(lax.psum(m, "local"))
            return tuple(outs)

        return _jit(shard_map(
            body, mesh=hmesh,
            in_specs=tuple(P(("cross", "local")) for _ in shapes),
            out_specs=tuple(P() for _ in shapes), check_vma=False))

    def _build_allgather(self, proto, shapes, mesh, axis, world,
                         _jit=jax.jit):
        def body(*shards):
            outs = []
            for s in shards:
                x = s.reshape(s.shape[1:])
                outs.append(lax.all_gather(x, axis, axis=0, tiled=True))
            return tuple(outs)

        return _jit(shard_map(
            body, mesh=mesh,
            in_specs=tuple(P(axis) for _ in shapes),
            out_specs=tuple(P() for _ in shapes), check_vma=False))

    def _build_hier_allreduce(self, proto, shapes, dtypes, hmesh, world,
                              _jit=jax.jit, plan=()):
        """Two-level fused allreduce: RS(local) → AR(cross) → AG(local).

        Same fusion/dtype-grouping contract as ``_build_allreduce`` (via the
        shared ``_build_fused_reduce``), but the reduction runs over a
        (cross, local) mesh so bytes over the slow cross links drop by
        1/local_size (reference N17's hierarchical path; SURVEY.md §2c).

        SUM/AVERAGE ride psum_scatter→psum→all_gather; MIN/MAX gather the
        slice, reduce elementwise, and cross only their 1/local shard
        (both exact in any association order, so results are
        bitwise-identical to flat whenever the arithmetic is — min/max
        always, sums for exactly-representable values); ADASUM maps its
        vector-halving-doubling onto the torus axes at both levels
        (``adasum_allreduce_hier``) — halving rounds ride ICI first, only
        the fully-halved shards touch DCN.
        """
        from ..parallel.hierarchical import (hierarchical_allreduce,
                                             hierarchical_allreduce_minmax)
        op = proto.reduce_op

        if op in (C.ReduceOp.MIN, C.ReduceOp.MAX):
            mm = "min" if op == C.ReduceOp.MIN else "max"

            def reduce_flat(flat):
                return hierarchical_allreduce_minmax(flat, mm, "cross",
                                                     "local")
        elif op == C.ReduceOp.ADASUM:
            from ..common.topology import torus_dims
            from ..parallel.adasum import adasum_allreduce_hier
            from ..parallel.topology import hier_bit_orders
            st = self._slice_topology(proto.process_set_id)
            orders = hier_bit_orders(st.local_size, st.num_slices)
            local_bits, cross_bits = orders

            def reduce_flat(flat):
                return adasum_allreduce_hier(flat, "cross", "local",
                                             local_bits=local_bits,
                                             cross_bits=cross_bits)
        else:
            def reduce_flat(flat):
                avg = (op == C.ReduceOp.AVERAGE
                       and jnp.issubdtype(flat.dtype, jnp.floating))
                red = hierarchical_allreduce(flat, "cross", "local",
                                             average=avg)
                if op == C.ReduceOp.AVERAGE and not avg:
                    red = red // world
                return red

        return self._build_fused_reduce(proto, shapes, dtypes, hmesh,
                                        P(("cross", "local")), reduce_flat,
                                        _jit, plan)

    def _build_hier_allgather(self, proto, shapes, hmesh, world,
                              _jit=jax.jit):
        """Two-level allgather: AG(local) → AG(cross).

        Rank order is cross-major × local-minor, matching the flat world
        order (devices are reshaped (cross, local) from the same ordered
        list), so results are byte-identical to the flat path.
        """
        def body(*shards):
            outs = []
            for s in shards:
                x = s.reshape(s.shape[1:])
                x = lax.all_gather(x, "local", axis=0, tiled=True)
                outs.append(lax.all_gather(x, "cross", axis=0, tiled=True))
            return tuple(outs)

        return _jit(shard_map(
            body, mesh=hmesh,
            in_specs=tuple(P(("cross", "local")) for _ in shapes),
            out_specs=tuple(P() for _ in shapes), check_vma=False))

    def _build_reducescatter(self, proto, shapes, mesh, axis, world,
                             _jit=jax.jit):
        op = proto.reduce_op
        if op not in (C.ReduceOp.SUM, C.ReduceOp.AVERAGE, C.ReduceOp.MIN,
                      C.ReduceOp.MAX, C.ReduceOp.PRODUCT):
            raise ValueError(f"reducescatter does not support ReduceOp {op}")

        def body(*shards):
            outs = []
            for s in shards:
                x = s.reshape(s.shape[1:])
                if op in (C.ReduceOp.SUM, C.ReduceOp.AVERAGE):
                    r = lax.psum_scatter(x, axis, scatter_dimension=0,
                                         tiled=True)
                    if op == C.ReduceOp.AVERAGE:
                        r = r / jnp.asarray(world, r.dtype)
                else:
                    # MIN/MAX/PRODUCT: no native scatter-reduce; gather,
                    # reduce elementwise, keep this rank's slice.
                    g = lax.all_gather(x, axis)          # [world, S0, ...]
                    if op == C.ReduceOp.MIN:
                        full = jnp.min(g, axis=0)
                    elif op == C.ReduceOp.MAX:
                        full = jnp.max(g, axis=0)
                    else:
                        full = jnp.prod(g, axis=0)
                    chunk = full.shape[0] // world
                    idx = lax.axis_index(axis)
                    r = lax.dynamic_slice_in_dim(full, idx * chunk, chunk, 0)
                outs.append(r[None])  # re-stack: [1, S0/world, ...]
            return tuple(outs)

        return _jit(shard_map(
            body, mesh=mesh,
            in_specs=tuple(P(axis) for _ in shapes),
            out_specs=tuple(P(axis) for _ in shapes), check_vma=False))

    def _build_alltoall(self, proto, shapes, mesh, axis, world,
                        _jit=jax.jit):
        def body(*shards):
            outs = []
            for s in shards:
                x = s.reshape(s.shape[1:])
                y = lax.all_to_all(x, axis, split_axis=0, concat_axis=0,
                                   tiled=True)
                outs.append(y[None])
            return tuple(outs)

        return _jit(shard_map(
            body, mesh=mesh,
            in_specs=tuple(P(axis) for _ in shapes),
            out_specs=tuple(P(axis) for _ in shapes), check_vma=False))
