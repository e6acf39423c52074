"""TCP controller client: multi-process negotiation for the engine.

The Python face of ``csrc/coordinator.cc`` — plays the role of the
reference's ``Controller::ComputeResponseList`` transport half (SURVEY.md
§3.2 step 2): every coordinator cycle, announce newly-pending tensor names,
receive the globally-ready ordered name list, and hand ready entries back to
the engine (which batches and executes them identically on every process).

Rank 0 additionally hosts the server thread (native, lock-step rounds).
"""

from __future__ import annotations

import ctypes
import dataclasses
import itertools
import struct
import threading
from typing import Dict, List, Optional, Sequence, Tuple

from . import native
from .exceptions import (
    HorovodInternalError, JoinTimeoutError, PeerFailureError,
    RoundTimeoutError,
)
from .net import retry_with_backoff
from ..testing import faults as _faults
from ..utils.logging import get_logger

log = get_logger()

_RESP_CAP = 4 * 1024 * 1024

# Monitor side-channel section marker ("MON1" little-endian) — protocol v3.
# Matches kMonMagic in csrc/coordinator.cc.
_MON_MAGIC = 0x314E4F4D
# Fault-tolerance capability section marker ("FLT1") — protocol v4; rides
# the first request/response only (warm rounds carry zero extra bytes).
_FLT_MAGIC = 0x31544C46
# Hierarchical control plane capability marker ("AGG5") — protocol v5;
# round 1 only in both directions, exactly the FLT1 pattern.  On the
# request side it rides BEFORE FLT1: the server's pre-processing FLT1
# salvage reads the round-1 frame's final 8 bytes, so FLT1 stays last.
_AGG_MAGIC = 0x35474741
# Typed abort frame: escape word + magic ("ABT4").  Matches kAbortEscape /
# kAbortMagic in csrc/coordinator.cc.
_ABORT_ESCAPE = 0xFFFFFFFF
_ABORT_MAGIC = 0x34544241
# Clean-LEAVE (protocol v6): request-side escape word (an impossible
# n_announce) + "LVE6" magic, which doubles as the round-1 capability ad in
# both directions and as the response-side leave-notice section marker.
# Matches kLeaveEscape / kLeaveMagic in csrc/coordinator.cc.
_LEAVE_ESCAPE = 0xFFFFFFFE
_LVE_MAGIC = 0x3645564C
# Zero-RTT warm path (protocol v7): "ZRT7" is the round-1 capability ad in
# both directions, the response-side next-round prediction section, and
# the request-side one-byte speculation confirm.  Matches kZrtMagic in
# csrc/coordinator.cc.
_ZRT_MAGIC = 0x3754525A


@dataclasses.dataclass
class ResponseCacheStats:
    """Client-side response-cache telemetry (timeline, /metrics, tests).

    ``hits``/``misses`` count per-tensor announces by wire form (bitvector
    vs full metadata); ``invalidations`` counts slots dropped for any
    reason — server-coordinated evictions, ``forget()``, or local capacity
    trims; ``full_announces``/``bit_announces`` are the cumulative frame
    contents the tier-1 regression guard asserts on."""
    hits: int = 0
    misses: int = 0
    invalidations: int = 0       # slots this client actually dropped
    evictions: int = 0           # server eviction broadcasts seen (counted
                                 # even when a local trim got there first)
    full_announces: int = 0
    bit_announces: int = 0

    def hit_rate(self) -> Optional[float]:
        total = self.hits + self.misses
        return (self.hits / total) if total else None


class NegotiationError(RuntimeError):
    """A collective was submitted inconsistently across ranks (shape/dtype/
    op divergence).  Per-tensor: raised from ``synchronize()`` of the
    offending collective only; the runtime stays alive (reference: the
    controller's per-tensor error Responses, SURVEY.md N2/§5).

    Deliberately NOT a HorovodInternalError — an elastic wrapper must not
    respond to an application bug by resetting the world."""


class TCPController:
    """Engine-facing controller (engine calls ``negotiate`` each cycle)."""

    def __init__(self, addr: str, port: int, rank: int, world: int,
                 stall_warn_s: float = 60.0, connect_timeout_ms: int = 60000,
                 cache_capacity: int = 2048, round_timeout_s: float = 0.0,
                 connect_retries: int = 3,
                 connect_backoff_ms: float = 500.0,
                 server_port: Optional[int] = None,
                 spec_ready_after: int = 0,
                 round_pipeline: int = 1,
                 zero_rtt: bool = True,
                 spec_seed: int = 0,
                 spec_streak_hint: int = 0):
        # server_port: where rank 0 binds the root coordinator when that
        # differs from where this client connects — the hierarchical
        # control plane (protocol v5) points every client at its local
        # HostAgent while the root server keeps the launcher-advertised
        # port.  None (default, flat mode) = same port for both.
        self._lib = native.load()
        self.rank = rank
        self.world = world
        self._server = None
        # Control-plane fault tolerance (protocol v4, HOROVOD_ROUND_
        # TIMEOUT_S): the server declares a rank dead when its socket dies
        # or it misses the per-round deadline, and broadcasts a typed
        # ABORT; this client additionally bounds its own response wait at
        # 2x the deadline (the server's verdict — armed at the round's
        # first frame, i.e. no later than our own send — must win the race
        # so failures carry dead-rank attribution; the client timeout is
        # the backstop for a wedged coordinator).  0 disables both
        # deadlines; dead-socket detection is always on.
        self.round_timeout_s = max(0.0, float(round_timeout_s))
        # Monitor-installed attribution hook: called with the dead-rank
        # list (or None for unattributed timeouts) to enrich HVD303 errors
        # with snapshot ages / ledger tails.  Telemetry only — guarded.
        self.fault_enricher = None
        # Latches once the server advertises protocol v4 (FLT1 section in
        # round 1's response) — the fault-frame analogue of
        # peer_monitor_proto below.
        self.peer_fault_proto = False
        # Latches once the server advertises protocol v5 (AGG5 section in
        # round 1's response): the coordinator understands per-host agent
        # connections, so a HostAgent between this client and the root is
        # known-compatible.  Purely observational on the rank client — its
        # own wire bytes are IDENTICAL either way (the frame guard pins
        # this), which is what lets the agent forward them verbatim.
        self.peer_hier_proto = False
        # Latches once the server advertises protocol v6 (LVE6 section):
        # this client may announce its own clean departure with a typed
        # LEAVE frame instead of a blind socket sever — see leave().
        self.peer_leave_proto = False
        # Zero-RTT warm path (protocol v7, docs/performance.md "Zero-RTT
        # warm path").  spec_ready_after mirrors the server knob (rank 0
        # starts the server with it); on the CLIENT it gates consuming
        # predictions — 0 keeps every round lock-step.  round_pipeline is
        # the client-side in-flight round window: 1 = today's lock-step,
        # >1 sends round N+1's request before round N's response is read
        # (the response is drained — bounded by the window — at the start
        # of a later _round call, where v4 aborts and LVE6 notices it may
        # carry are honored).  zero_rtt=False emulates a pre-v7 client:
        # no ZRT7 ad, predictions ignored (the downgrade-matrix tests
        # ride this).  Both knobs are runtime-tunable
        # (autotune coordinates in multi-process mode).
        self.spec_ready_after = max(0, int(spec_ready_after))
        self.round_pipeline = max(1, int(round_pipeline))
        self.zero_rtt = bool(zero_rtt)
        # Latches once the server advertises protocol v7 (ZRT7 section).
        self.peer_zero_rtt_proto = False
        # Dispatch-safety gate, owned by the ENGINE: consuming a predicted
        # verdict means dispatching a collective BEFORE peers have seen
        # its real verdict, so the dispatch path must never block this
        # thread on device completion — a peer that still needs our next
        # round frame to learn the verdict would deadlock against our
        # blocked cycle thread.  The engine clears this when its launches
        # are synchronous (the CPU tier's serialized-launch mode, or an
        # inline-settling window); test-harness controllers, which
        # dispatch nothing, keep the default True.
        self.spec_dispatch_ok = True
        # Slots the server predicted ready for the NEXT round (one-round
        # validity: replaced — or cleared — by every processed response),
        # and the client-side engagement streak: consecutive responses
        # that carried a usable prediction.  Consumption requires the
        # streak to reach spec_ready_after — the knob's CLIENT meaning
        # (the server's streak threshold is fixed at start): larger
        # values re-engage more conservatively after any instability,
        # since a mispredict resets the streak to zero.  This is the
        # axis the autotune coordinate actually walks.
        self._predicted: set = set()
        # Elastic streak carryover (ISSUE 12): a re-rendezvous survivor
        # seeds the consumption gate from the PREVIOUS generation's
        # engagement (spec_carry_hint()), so warm speculation re-engages
        # after the first prediction-bearing response instead of
        # relearning spec_ready_after responses from zero.  spec_seed is
        # the server-side twin (initial streak for fresh slots, rank 0
        # only).  Both default to 0 — the non-elastic behavior unchanged.
        self._pred_streak = max(0, min(int(spec_streak_hint),
                                       self.spec_ready_after))
        # Requests sent whose responses are not yet read, oldest first:
        # the consumed prediction (frozenset of slots) for speculative
        # rounds, None for plain pipelined rounds.  Never longer than
        # max(round_pipeline, 1) after a _round call returns.
        self._outstanding: List[Optional[frozenset]] = []
        # Speculation observability (/metrics, the timeline counter
        # track, tests/test_zero_rtt.py): hits/mispredicts resolve when the
        # deferred response validates; spec_rounds counts verdicts
        # returned without waiting (round trips saved).
        self.spec_hits = 0
        self.spec_mispredicts = 0
        self.spec_rounds = 0
        self.inflight_high_water = 0
        self.last_round_speculative = False
        # Whether the responses the last _round read carried no verdict
        # for ANY rank (ready, error, slot assignment: the server
        # broadcasts them all).  The engine's idle back-off reads it.
        self.last_round_quiet = True
        # Ranks the server reported as cleanly departed (LVE6 notice
        # sections), cumulative for this controller generation.  A
        # non-empty list means the world SHRANK without a fault: the
        # engine fails world-level work with PeerLeftInterrupt (the
        # data-plane world is still the old fixed size) and the elastic
        # wrapper re-rendezvouses.  peer_leave_hook (installed by the
        # monitor agent) is called with each notice's rank list — guarded,
        # telemetry must never fail a round.
        self.left_ranks: List[int] = []
        self.peer_leave_hook = None
        # True once leave() actually put the LEAVE frame on the wire —
        # basics.shutdown() keys the elastic abrupt-teardown path off it.
        self.leave_sent = False
        # Set by interrupt() before it severs the lock-step socket: an
        # expected local teardown whose round failure must NOT be treated
        # as a peer death (engine checks it before aborting).
        self.interrupted = False
        # Deterministic fault injection (HVD_TPU_FAULT, horovod_tpu.testing
        # .faults): cached as a bound callable ONLY when armed, so the
        # unarmed hot path costs one attribute check per site.
        self._fault_fire = _faults.fire if _faults.armed() else None
        if rank == 0:
            srv_port = port if server_port is None else int(server_port)
            self._server = self._lib.hvdtpu_server_start(
                srv_port, world, ctypes.c_double(stall_warn_s),
                int(cache_capacity),
                int(self.round_timeout_s * 1000),
                self.spec_ready_after, max(0, int(spec_seed)))
            if not self._server:
                raise RuntimeError(f"Failed to start controller server on "
                                   f"port {srv_port}")
        if self._fault_fire is not None:
            self._fault_fire("connect", rank)
        # Bounded connect retries with exponential backoff + jitter
        # (HOROVOD_CONNECT_RETRIES / HOROVOD_CONNECT_BACKOFF_MS): workers
        # may start before the coordinator's server exists.  The overall
        # connect_timeout_ms budget is split across attempts; each native
        # attempt itself re-resolves DNS and re-tries the TCP connect.
        retries = max(0, int(connect_retries))
        per_ms = (connect_timeout_ms if retries == 0
                  else max(1000, int(connect_timeout_ms / (retries + 1))))

        self.connect_attempts = 0       # the start-up record's ``attempts``

        def _connect():
            self.connect_attempts += 1
            handle = self._lib.hvdtpu_client_connect(
                addr.encode(), port, rank, per_ms)
            if not handle:
                raise ConnectionError(
                    f"controller at {addr}:{port} not reachable")
            return handle

        def _on_retry(attempt, exc, delay_s):
            log.warning(
                "rank %d: %s (attempt %d/%d); retrying in %.1fs",
                rank, exc, attempt + 1, retries + 1, delay_s)

        try:
            self._client = retry_with_backoff(
                _connect, retries=retries, base_ms=connect_backoff_ms,
                exceptions=(ConnectionError,), on_retry=_on_retry)
        except ConnectionError as exc:
            self._client = None
            if self._server:
                self._lib.hvdtpu_server_stop(self._server)
            raise RuntimeError(
                f"rank {rank}: failed to connect to controller at "
                f"{addr}:{port} after {retries + 1} attempt(s)") from exc
        self._announced: set = set()
        # Response cache (reference N8 response_cache.cc): slot table
        # replicated across ranks.  (name, digest, required, datadep,
        # grouped) -> server-assigned uint32 slot; once learned, steady-
        # state announces ride a fixed-size bitvector (bit = slot pending)
        # instead of per-tensor metadata frames.  Any miss — shape/dtype
        # change (new digest), grouped<->ungrouped flip, forget(), or a
        # coordinated eviction — falls back to a full announce, which
        # (re)learns the slot.  Insertion order doubles as LRU order:
        # hits reinsert at the end, capacity trims pop from the front.
        self.cache_capacity = max(0, int(cache_capacity))
        self.cache_enabled = self.cache_capacity > 0
        self.cache_stats = ResponseCacheStats()
        self._slots: Dict[tuple, int] = {}
        self._slot_keys: Dict[int, tuple] = {}
        # Persistent-program invalidation (engine hook, ISSUE 8): called
        # with each slot id this client drops — eviction broadcast,
        # forget(), capacity trim, or slot-id reuse via a fresh adoption —
        # so the engine's slot-pinned compiled programs can never outlive
        # (or cross-serve) the slot they were pinned to.  Guarded: the
        # data-plane cache must never fail a negotiation round.
        self.slot_drop_hook = None
        # Full key tuples announced in full and awaiting a server slot.
        # The server echoes the full key in the assignment broadcast, so
        # adoption matches exactly the announced tuple — same (name,
        # digest) under a different process set (different required/
        # datadep) or grouped-ness can't cross-adopt slots.  Every full
        # announce MUST register here: a slot-bit ready verdict is only
        # resolvable if the announcer adopted the slot in the same round
        # the server learned it.
        self._awaiting_assign: set = set()
        self.bytes_sent = 0                      # telemetry (tests/timeline)
        # Monitor side-channel (protocol v3, horovod_tpu.monitor): when a
        # MonitorAgent is attached, `monitor_source()` may yield an opaque
        # snapshot blob to append to this round's request (interval-gated
        # by the agent — absent on most rounds), and `monitor_sink(blobs)`
        # receives the server's re-broadcast of every rank's fresh blobs.
        # `peer_monitor_proto` latches once the server advertises the v3
        # monitor section in a response — the agent's version gate: against
        # a pre-v3 server it stops paying frame bytes after a grace window.
        # Telemetry must NEVER fail negotiation: both callbacks are guarded.
        self.monitor_source = None
        self.monitor_sink = None
        self.on_join_epoch = None     # monitor aggregation-table flush hook
        self.monitor_bytes_sent = 0   # subset of bytes_sent (frame guard
                                      # tests subtract it)
        self.peer_monitor_proto = False
        self.rounds = 0
        self._early_ready: List[tuple] = []       # (name, digest)
        self._early_errors: Dict[str, str] = {}
        self._resp_buf = (ctypes.c_uint8 * _RESP_CAP)()
        # join protocol state (reference: hvd.join semantics).  While this
        # rank is joined, `synthesizer(name, digest)` — installed by the
        # engine — builds a zero-contribution entry for peers' collectives.
        self._join_pending = False
        self._joined = False
        self._join_event = threading.Event()
        self._join_last_rank = -1
        self._join_error: Optional[BaseException] = None
        self.synthesizer = None
        # Peer group tags → local ids, in a high id range so a synthesized
        # group can never collide with this rank's own group ids (a joining
        # rank may still have un-synchronized local entries in flight).
        self._group_tags: Dict[str, int] = {}
        self._group_tag_counter = itertools.count(1 << 30)

    # ------------------------------------------------------------- protocol
    @property
    def inflight_rounds(self) -> int:
        """Requests on the wire whose responses are not yet read (>0 only
        under speculation or ``round_pipeline > 1``)."""
        return len(self._outstanding)

    @property
    def join_open(self) -> bool:
        """This rank asked to join, or is joined and synthesizing its
        peers' collectives, until every rank has joined."""
        return self._joined or self._join_pending

    def _round(self, announces: Sequence) -> tuple:
        """announces: (name, required_ranks, digest, group, datadep, tag
        [, entry]) tuples; required 0 = world.  Tuples whose slot is known
        ride the fixed-size bitvector (the steady-state fast path); the
        sanitizer tag — when present — travels in the sparse side-channel
        so order divergence is still caught on the cached path.  The
        optional trailing entry (never on the wire) gets its learned slot
        stamped as ``cache_slot`` — the engine's persistent-program pin
        key, obtained here where the slot lookup already happened so the
        hot dispatch path never rebuilds the announce key.

        Zero-RTT warm path (protocol v7): a round whose entire announce is
        exactly the server's prediction returns the predicted verdict
        WITHOUT waiting for the response — the response is drained at the
        start of a later call, where it validates the prediction (and
        delivers any abort/leave/monitor payload one round late, bounded
        by the in-flight window).  ``round_pipeline > 1`` defers the read
        the same way without needing a prediction: the verdict then lands
        one call later, off the critical path."""
        acc_ready: List[tuple] = []
        acc_warns: List[str] = []
        acc_errors: List[tuple] = []
        acc = (acc_ready, acc_warns, acc_errors)
        self.last_round_quiet = True        # until a response says otherwise
        depth = max(1, int(self.round_pipeline))
        # Deferred responses first: bound the in-flight window, then
        # opportunistically consume anything already buffered (refreshes
        # the prediction at ~zero wait — in the steady state the previous
        # round's response arrived while this rank computed).
        while len(self._outstanding) >= depth:
            self._drain_one(acc)
        while self._outstanding and \
                self._lib.hvdtpu_client_pending(self._client):
            self._drain_one(acc)
        full, bits, tags = [], [], []
        stats = self.cache_stats
        for a in announces:
            n, required, digest, group, datadep, tag = a[:6]
            key = (n, digest, required, datadep, group != "-1")
            slot = self._slots.get(key) if self.cache_enabled else None
            if slot is None:
                full.append(a[:6])
                if not n.startswith("\x1f"):
                    stats.misses += 1
                    # EVERY cacheable full announce registers for adoption
                    # (see _awaiting_assign comment) — even with the local
                    # cache disabled: the server may still answer through a
                    # slot bit (peers use the fast path), and resolving it
                    # needs the mapping.  cache_enabled only gates the
                    # bit-ANNOUNCE path above.  The soft cap bounds
                    # pathological digest churn; the slot table itself is
                    # LRU-bounded by cache_capacity.
                    if len(self._awaiting_assign) < (1 << 20):
                        self._awaiting_assign.add(key)
            else:
                # LRU touch: reinsert at the end of the dict order.
                self._slots.pop(key)
                self._slots[key] = slot
                bits.append(slot)
                if tag:
                    tags.append((slot, tag))
                stats.hits += 1
                if len(a) > 6 and a[6] is not None:
                    a[6].cache_slot = slot
        req = bytearray(struct.pack("<I", len(full)))
        for n, required, digest, group, datadep, tag in full:
            req += struct.pack("<H", required)
            for field in (n, digest, group, datadep, tag):
                fb = field.encode()
                req += struct.pack("<H", len(fb)) + fb
        if bits:
            nb = max(bits) // 8 + 1
            bv = bytearray(nb)
            for s in bits:
                bv[s // 8] |= 1 << (s % 8)
        else:
            nb, bv = 0, b""
        req += struct.pack("<I", nb) + bytes(bv)
        req += struct.pack("<I", len(tags))
        for slot, tag in tags:
            tb = tag.encode()
            req += struct.pack("<IH", slot, len(tb)) + tb
        # Monitor side-channel (absent on most rounds — the agent interval-
        # gates it).  A pre-v3 server stops parsing after the tag section,
        # so the trailing bytes are simply ignored there.
        self.rounds += 1
        if self.monitor_source is not None:
            try:
                blob = self.monitor_source()
            except Exception:  # noqa: BLE001 - telemetry never fails a round
                log.exception("monitor source failed")
                blob = None
            if blob:
                req += struct.pack("<II", _MON_MAGIC, len(blob)) + blob
                self.monitor_bytes_sent += 8 + len(blob)
        # Speculation decision (protocol v7): the verdict may be returned
        # without waiting only when this client's ENTIRE outstanding
        # negotiation state is a SUBSET of the predicted warm set (each
        # predicted slot is an independent "ready next round" claim, so a
        # round announcing only part of the working set — the sequential
        # per-tensor submit pattern — still qualifies) — and no full
        # announces, no sanitizer tags, no older announced-but-unresolved
        # names (whose verdict could interleave and reorder dispatch
        # across ranks), no join in any form, no unread responses (the
        # prediction would be stale).  Everything else falls back to the
        # lock-step (or plain pipelined) round.
        spec_slots = None
        if (self.zero_rtt and self.spec_ready_after > 0 and self._predicted
                and self.spec_dispatch_ok
                and self._pred_streak >= self.spec_ready_after
                and not full and not tags and bits
                and not self._outstanding
                and not self._joined and not self._join_pending
                and set(bits) <= self._predicted
                and len(bits) == len(set(bits))):
            names = set()
            for s in bits:
                key = self._slot_keys.get(s)
                if key is None:
                    names = None
                    break
                names.add(key[0])
            if names is not None and names == self._announced:
                spec_slots = frozenset(bits)
        # v5 + v6 + v7 + v4 capability hellos: FIRST request only, so
        # warm-path frames carry zero extra bytes (the frame guard asserts
        # this).  AGG5/LVE6/ZRT7 ride before FLT1 — the server's
        # abort-path capability salvage reads the frame's FINAL 8 bytes as
        # the FLT1 ad, so FLT1 must stay last.
        if self.rounds == 1:
            req += struct.pack("<II", _AGG_MAGIC, 0)
            req += struct.pack("<II", _LVE_MAGIC, 0)
            if self.zero_rtt:
                req += struct.pack("<II", _ZRT_MAGIC, 0)
            req += struct.pack("<II", _FLT_MAGIC, 0)
        if spec_slots is not None:
            # One-byte speculation confirm: this round's verdict was
            # consumed from the prediction (the announce itself still
            # rides the ordinary bitvector section above).
            req += struct.pack("<IIB", _ZRT_MAGIC, 1, 1)
        stats.full_announces += sum(1 for a in full
                                    if not a[0].startswith("\x1f"))
        stats.bit_announces += len(bits)
        self.bytes_sent += len(req)
        if self._fault_fire is not None:
            self._fault_fire("round_send", self.rank, sever=self._sever)
        # Drain a queued ABORT before sending: the server may have posted
        # the typed verdict behind the previous round's response, and a
        # send into an already-reset socket would make the kernel discard
        # the buffered frame (losing the attribution).  With responses
        # legitimately in flight (speculation/pipelining) a readable frame
        # is EXPECTED — the entry drain above already consumed what it
        # could, so skip the desync check entirely.
        if not self._outstanding and \
                self._lib.hvdtpu_client_pending(self._client):
            # NB: poll() also reports readable on EOF/POLLHUP — a dead
            # socket lands here too, and must be reported as the ordinary
            # peer-death failure, not as a protocol bug.
            rc, _ = self._recv_salvaging_abort(1000)
            if rc == -2:
                self._raise_overflow()
            if rc < 0:
                self._raise_unattributed_failure(f"rc={rc}")
            raise HorovodInternalError(
                "controller protocol desync: unsolicited frame before the "
                "round request (rc={})".format(rc))
        buf = (ctypes.c_uint8 * len(req)).from_buffer(req) if req else \
            (ctypes.c_uint8 * 0)()
        rc = self._lib.hvdtpu_client_send(self._client, buf, len(req))
        if rc < 0:
            # Send failed — the socket died between rounds.  A typed abort
            # may still be buffered locally; salvage it for attribution.
            self._recv_salvaging_abort(250)
            self._raise_unattributed_failure(f"send rc={rc}")
        if self._fault_fire is not None:
            self._fault_fire("mid_round_exit", self.rank,
                             sever=self._sever)
            self._fault_fire("round_recv", self.rank, sever=self._sever)
        self._outstanding.append(spec_slots)
        self.last_round_speculative = spec_slots is not None
        if spec_slots is not None:
            # Zero-RTT: return the predicted verdict NOW; the response is
            # validated at the start of a later round.  Verdict order is
            # slot-ascending — identical to the ready-bitvector
            # reconstruction rule every rank applies, so speculating and
            # lock-stepping ranks dispatch in the same order.
            self.spec_rounds += 1
            self._predicted = set()            # one-round validity: consumed
            for s in sorted(spec_slots):
                key = self._slot_keys.get(s)
                if key is not None:
                    acc_ready.append((key[0], key[1], "-1"))
            if len(self._outstanding) > self.inflight_high_water:
                self.inflight_high_water = len(self._outstanding)
            return acc
        # Lock-step (depth 1): read this round's response now.  Pipelined
        # (depth > 1): leave up to depth-1 responses in flight — their
        # verdicts land at a later call, off the critical path.
        while len(self._outstanding) >= depth:
            self._drain_one(acc)
        # High-water of the DEFERRED window: what is still unread when the
        # round returns (a lock-step round always returns at 0).
        if len(self._outstanding) > self.inflight_high_water:
            self.inflight_high_water = len(self._outstanding)
        return acc

    def _drain_one(self, acc, timeout_ms: Optional[int] = None):
        """Read and process the OLDEST outstanding response, folding its
        verdicts into ``acc`` = (ready, warns, errors).  All the
        lock-step recv classification (typed abort salvage, round
        timeout, overflow, unattributed death) lives here so deferred
        reads fail exactly like synchronous ones — just up to one round
        later, bounded by the in-flight window."""
        spec_slots = self._outstanding[0]
        # Client-side wall-clock deadline (2x the server's per-round
        # deadline — see __init__): the backstop for a wedged coordinator.
        if timeout_ms is None:
            timeout_ms = int(self.round_timeout_s * 2000)
        rc, data = self._recv_salvaging_abort(timeout_ms)
        if rc == -3:
            msg = (f"HVD303 negotiation round timed out after "
                   f"{self.round_timeout_s * 2:g}s (HOROVOD_ROUND_TIMEOUT_S"
                   f"={self.round_timeout_s:g}); the coordinator or a peer "
                   f"rank is wedged")
            extra = self._enrich(None)
            if extra:
                msg += "\n" + extra
            raise RoundTimeoutError(msg, timeout_s=self.round_timeout_s * 2)
        if rc == -2:
            self._raise_overflow()
        if rc < 0:
            # ControlPlaneError subclasses HorovodInternalError, so elastic
            # run wrappers still catch-and-restore (SURVEY.md §3.4).
            self._raise_unattributed_failure(f"rc={rc}")
        self._outstanding.pop(0)
        ready, warns, errors = self._parse_response(data, spec_slots)
        acc[0].extend(ready)
        acc[1].extend(warns)
        acc[2].extend(errors)

    def _parse_response(self, data: bytes,
                        spec_slots: Optional[frozenset] = None) -> tuple:
        """Decode one response frame, applying every side effect (slot
        adoption, coordinated evictions, capability latches, monitor
        sink, leave notices, next-round prediction).  ``spec_slots``
        non-None marks the round as speculatively consumed: its slot
        verdicts were already delivered at send time, so they are
        filtered here and only VALIDATE the prediction."""
        off = 0

        def read_list():
            nonlocal off
            (n,) = struct.unpack_from("<I", data, off)
            off += 4
            out = []
            for _ in range(n):
                (ln,) = struct.unpack_from("<H", data, off)
                off += 2
                out.append(data[off:off + ln].decode())
                off += ln
            return out

        def read_tuple(k):
            nonlocal off
            (n,) = struct.unpack_from("<I", data, off)
            off += 4
            out = []
            for _ in range(n):
                fields = []
                for _f in range(k):
                    (ln,) = struct.unpack_from("<H", data, off)
                    off += 2
                    fields.append(data[off:off + ln].decode())
                    off += ln
                out.append(tuple(fields))
            return out

        # ready: (name, digest, group) — digest + group feed the joined
        # rank's synthesized entries; errors: (name, message).
        ready = read_tuple(3)
        warns = read_list()
        errors = read_tuple(2) if off < len(data) else []
        # Slot assignments: adopt those matching a tuple this client
        # announced in full (the server broadcasts to every rank).
        # Processed BEFORE the ready bitvector so a slot assigned and made
        # ready in the same round resolves.
        n_assign = 0
        if off < len(data):
            (n_assign,) = struct.unpack_from("<I", data, off)
            off += 4
            for _ in range(n_assign):
                fields = []
                for _f in range(3):
                    (ln,) = struct.unpack_from("<H", data, off)
                    off += 2
                    fields.append(data[off:off + ln].decode())
                    off += ln
                (required, grouped, slot) = struct.unpack_from(
                    "<HHI", data, off)
                off += 8
                name, digest, datadep = fields
                key = (name, digest, required, datadep, bool(grouped))
                if key in self._awaiting_assign:
                    self._awaiting_assign.discard(key)
                    self._adopt_slot(key, slot)
        # Ready bitvector: slot verdicts, appended after the string
        # verdicts in increasing slot order.  Every client applies the
        # same rule, so the reconstructed order is identical on all ranks
        # (which is all the engine's deterministic batching needs).
        # Unknown slots are other process sets' tensors — not ours.
        # Speculatively consumed slots (protocol v7) were delivered at
        # send time: here they only validate the prediction.
        actual_bits: set = set()
        if off < len(data):
            (nb,) = struct.unpack_from("<I", data, off)
            off += 4
            bv = data[off:off + nb]
            off += nb
            for i in range(nb * 8):
                if not (bv[i // 8] >> (i % 8)) & 1:
                    continue
                actual_bits.add(i)
                if spec_slots is not None and i in spec_slots:
                    continue
                key = self._slot_keys.get(i)
                if key is not None:
                    ready.append((key[0], key[1], "-1"))
        if ready or errors or n_assign or actual_bits:
            self.last_round_quiet = False
        if spec_slots is not None:
            if spec_slots <= actual_bits:
                self.spec_hits += 1
            else:
                # Mispredict: a predicted slot did not go ready (a rank
                # skipped a cycle, or a slot-invalidation event landed).
                # The early-consumed verdict needs no repair — our announce
                # stays pending server-side and the late real verdict is
                # absorbed by this name's next entry — but speculation
                # disengages (the server reset the slot's streak; we drop
                # any stale prediction) until the streak rebuilds through
                # normal full rounds.
                self.spec_mispredicts += 1
                self._predicted = set()
                self._pred_streak = 0
        # Coordinated evictions: drop the named slots so this table can
        # never diverge from the server's (or any peer's).
        if off < len(data):
            (n_evict,) = struct.unpack_from("<I", data, off)
            off += 4
            for _ in range(n_evict):
                (slot,) = struct.unpack_from("<I", data, off)
                off += 4
                # Server-authoritative count: a local capacity trim may
                # have dropped the slot already (invalidations covered
                # that); the eviction still happened fleet-wide.
                self.cache_stats.evictions += 1
                self._predicted.discard(slot)
                key = self._slot_keys.pop(slot, None)
                if key is not None:
                    self._slots.pop(key, None)
                    self.cache_stats.invalidations += 1
                self._notify_slot_drop(slot)
        # Trailing sections, walked order-agnostically (mirroring the
        # server's generic request-side walk, so MON1 and FLT1 compose in
        # either order).  MON1 (protocol v3): the server's re-broadcast of
        # this round's fleet snapshots.  FLT1 (protocol v4, round 1's
        # response only): the server can send us typed ABORT frames
        # instead of blind socket severs.  Each magic doubles as the
        # capability advertisement its version gate latches on.  An
        # unknown magic stops the walk: MON1 carries no section-length
        # field, so a client this old cannot skip sections it does not
        # understand (a future section must be appended after these).
        saw_prediction = False
        while off + 8 <= len(data):
            (magic,) = struct.unpack_from("<I", data, off)
            if magic == _MON_MAGIC:
                off += 4
                (n_blob,) = struct.unpack_from("<I", data, off)
                off += 4
                blobs = []
                for _ in range(n_blob):
                    (mr, ln) = struct.unpack_from("<II", data, off)
                    off += 8
                    blobs.append((mr, data[off:off + ln]))
                    off += ln
                self.peer_monitor_proto = True
                if blobs and self.monitor_sink is not None:
                    try:
                        self.monitor_sink(blobs)
                    except Exception:  # noqa: BLE001 - telemetry only
                        log.exception("monitor sink failed")
            elif magic == _FLT_MAGIC:
                off += 8  # magic + reserved u32 (always 0)
                self.peer_fault_proto = True
            elif magic == _AGG_MAGIC:
                off += 8  # magic + reserved u32 (always 0)
                self.peer_hier_proto = True
            elif magic == _LVE_MAGIC:
                # Clean-LEAVE section (protocol v6): the payload-bearing
                # form — (magic, len, n_left, ranks) — unlike the bare
                # v4/v5 ads, so an empty round-1 section IS the server's
                # capability ad and a non-empty one is a leave notice.
                (ln,) = struct.unpack_from("<I", data, off + 4)
                off += 8
                end = off + ln
                self.peer_leave_proto = True
                n_left = 0
                if ln >= 4:
                    (n_left,) = struct.unpack_from("<I", data, off)
                    off += 4
                ranks = []
                for _ in range(n_left):
                    (r,) = struct.unpack_from("<I", data, off)
                    ranks.append(r)
                    off += 4
                off = end
                if ranks:
                    self.left_ranks = sorted(set(self.left_ranks) |
                                             set(ranks))
                    h = self.peer_leave_hook
                    if h is not None:
                        try:
                            h(ranks)
                        except Exception:  # noqa: BLE001 - telemetry only
                            log.exception("peer-leave hook failed")
            elif magic == _ZRT_MAGIC and self.zero_rtt:
                # Zero-RTT prediction section (protocol v7): the slots the
                # server predicts ready NEXT round (empty on round 1 — the
                # capability ad).  Adopted verbatim: the speculation
                # decision requires an exact match against our own next
                # announce, so an unknown slot in here simply disables
                # speculation for that round.  A pre-v7 client (zero_rtt
                # False) stops its walk here, exactly like an unknown
                # magic.
                (ln,) = struct.unpack_from("<I", data, off + 4)
                off += 8
                end = off + ln
                self.peer_zero_rtt_proto = True
                n_pred = 0
                if ln >= 4:
                    (n_pred,) = struct.unpack_from("<I", data, off)
                    off += 4
                pred = set()
                for _ in range(n_pred):
                    (s,) = struct.unpack_from("<I", data, off)
                    pred.add(s)
                    off += 4
                off = end
                self._predicted = pred
                saw_prediction = bool(pred)
            else:
                break
        if saw_prediction:
            self._pred_streak += 1
        else:
            # Predictions are one-round-valid: a response without a ZRT7
            # section (spec off, streak reset, old server, mixed-version
            # fleet) expires any stale one — and the engagement streak
            # restarts with the next prediction run.
            self._predicted = set()
            self._pred_streak = 0
        return ready, warns, errors

    # ------------------------------------------------- fault handling (v4)
    @staticmethod
    def _parse_abort(data: bytes) -> Optional[tuple]:
        """``(dead_ranks, reason)`` when ``data`` is a typed ABORT frame
        (escape word + "ABT4" magic), else None.  The escape word
        0xFFFFFFFF is an impossible n_ready, so the check is unambiguous
        against every normal response."""
        if len(data) < 12:
            return None
        esc, magic = struct.unpack_from("<II", data, 0)
        if esc != _ABORT_ESCAPE or magic != _ABORT_MAGIC:
            return None
        (n_dead,) = struct.unpack_from("<I", data, 8)
        off = 12
        ranks = []
        for _ in range(n_dead):
            (r,) = struct.unpack_from("<I", data, off)
            ranks.append(r)
            off += 4
        (ln,) = struct.unpack_from("<H", data, off)
        off += 2
        reason = data[off:off + ln].decode(errors="replace")
        return ranks, reason

    def _recv_salvaging_abort(self, timeout_ms: int):
        """One ``client_recv`` that raises the typed ``PeerFailureError``
        when the frame is a v4 ABORT; otherwise returns ``(rc, data)``
        for the caller to classify (``rc < 0``: dead / overflowed /
        timed-out socket — see ``hvdtpu_client_recv``).  All of
        ``negotiate()``'s salvage points (pre-send drain, failed send,
        main response) funnel through here so the abort handling cannot
        drift between them."""
        rc = self._lib.hvdtpu_client_recv(
            self._client, self._resp_buf, _RESP_CAP, timeout_ms)
        data = bytes(self._resp_buf[:rc]) if rc > 0 else b""
        abort = self._parse_abort(data)
        if abort is not None:
            self._raise_peer_failure(*abort)
        return rc, data

    def _enrich(self, dead_ranks: Optional[List[int]]) -> str:
        """Monitor-sourced attribution block (snapshot ages, ledger tails)
        for HVD303 errors; empty without an agent.  Telemetry must never
        mask the original failure — guarded."""
        if self.fault_enricher is None:
            return ""
        try:
            return self.fault_enricher(dead_ranks) or ""
        except Exception:  # noqa: BLE001 - attribution is best-effort
            log.exception("fault enricher failed")
            return ""

    def _raise_peer_failure(self, ranks: List[int], reason: str):
        msg = (f"HVD303 control-plane peer failure: the coordinator "
               f"declared rank(s) {sorted(ranks)} dead: {reason}")
        extra = self._enrich(ranks)
        if extra:
            msg += "\n" + extra
        raise PeerFailureError(msg, dead_ranks=ranks, reason=reason)

    def _raise_overflow(self):
        """A response larger than the fixed receive buffer (native rc=-2)
        is a protocol/sizing bug, NOT a peer failure: deliberately a plain
        RuntimeError — a ControlPlaneError (or any HorovodInternalError)
        would send the elastic run wrapper into a restore loop that hits
        the identical overflow every round, while telling the operator
        peers are dying."""
        raise RuntimeError(
            f"negotiation response exceeded the fixed "
            f"{_RESP_CAP // (1024 * 1024)}MB receive buffer (_RESP_CAP); "
            f"this is a protocol/sizing bug, not a peer failure — reduce "
            f"the per-round announce burst or raise _RESP_CAP")

    def _raise_unattributed_failure(self, detail: str):
        """Peer death inferred from a severed socket with no salvageable
        abort verdict naming the culprit.  Still typed (ControlPlaneError,
        so the engine runs its clean abort instead of leaving the
        InflightRing waiting on a dead world) and still monitor-enriched —
        with no dead-rank list, the stalest snapshot is the prime suspect."""
        msg = (f"HVD303 controller round failed ({detail}); a peer likely "
               f"died mid-negotiation (unattributed: no abort verdict was "
               f"salvageable)")
        extra = self._enrich(None)
        if extra:
            msg += "\n" + extra
        raise PeerFailureError(msg, dead_ranks=[])

    def _notify_slot_drop(self, slot: int):
        h = self.slot_drop_hook
        if h is not None:
            try:
                h(slot)
            except Exception:  # noqa: BLE001 - data-plane cache only
                log.exception("slot-drop hook failed")

    def _adopt_slot(self, key: tuple, slot: int):
        old = self._slot_keys.pop(slot, None)
        if old is not None:
            self._slots.pop(old, None)
            # Slot-id reuse: a program pinned to the OLD tuple must not
            # serve the new one (its digest differs by construction) —
            # nor may a prediction made for the old tuple (v7).
            self._predicted.discard(slot)
            self._notify_slot_drop(slot)
        self._trim_slots(len(self._slots) + 1)
        self._slots[key] = slot
        self._slot_keys[slot] = key

    def _trim_slots(self, size: Optional[int] = None):
        """Enforce the (runtime-tunable) local capacity, LRU-first.  Slots
        whose tensor is still in flight are skipped: dropping one would
        make a later slot-bit ready verdict unresolvable."""
        if size is None:
            size = len(self._slots)
        if size <= max(1, self.cache_capacity):
            return
        excess = size - max(1, self.cache_capacity)
        for lru_key in list(self._slots):
            if excess <= 0:
                break
            if lru_key[0] in self._announced:
                continue
            lru_slot = self._slots.pop(lru_key)
            self._slot_keys.pop(lru_slot, None)
            self._predicted.discard(lru_slot)
            self.cache_stats.invalidations += 1
            self._notify_slot_drop(lru_slot)
            excess -= 1

    # ---------------------------------------------------------- engine API
    @staticmethod
    def _wire_name(e) -> str:
        # Namespace by process set so the same tensor name used concurrently
        # by two disjoint sets can't merge their readiness on the server
        # (which keys pending state by wire name alone).
        ps_id = getattr(e, "process_set_id", 0)
        return f"{ps_id}\x1f{e.name}" if ps_id else e.name

    @staticmethod
    def _digest(e) -> str:
        """Submission consistency digest: op kind, dtype, per-rank shape,
        reduce op, root, scale factors, wire compression — what the
        reference's Request carries for the controller's shape/dtype checks
        (SURVEY.md N2/N5).  Step-invariant by construction: the sanitizer's
        per-submission tag travels in the announce's separate ``tag`` field
        (the server folds it back into its mismatch comparison), so the
        digest can key a response-cache slot that stays valid across
        steps even in sanitizer mode."""
        t = getattr(e, "tensor", None)
        if t is None:
            return "barrier"
        shape = tuple(t.shape[1:]) if len(t.shape) else ()
        ct = getattr(e, "ctype", None)
        op = getattr(e, "reduce_op", None)
        parts = [ct.value if ct is not None else "op",
                 str(t.dtype), str(shape)]
        if op is not None:
            parts.append(op.name)
        parts.append(str(getattr(e, "root_rank", 0)))
        # Scale factors shape the fused program (they are in the engine's
        # fusion key), so divergence would desync batching across ranks.
        # Deliberately NOT here: group_id — local group counters can drift
        # across ranks (uneven join epochs), so it travels in the announce's
        # separate `group` field, outside the mismatch comparison.
        parts.append(str(getattr(e, "prescale_factor", None)))
        parts.append(str(getattr(e, "postscale_factor", None)))
        # Wire compression shapes the fused program (cast-down before the
        # reduce, cast-up after): divergence across ranks would execute
        # mismatched programs, so it is part of the consistency check.
        # Joined ranks parse digest fields positionally and rely on this
        # slot being parts[7] (see engine._synthesize_join_entry).
        parts.append(str(getattr(e, "compression", None) or "none"))
        # ZeRO-sharded dimension (ISSUE 15): appended ONLY when set, so
        # every flat digest stays byte-identical to the established
        # protocol (and pinned response-cache slots survive the upgrade).
        # A sharded reduce-scatter/allgather program differs from the
        # ordinary one of the same shapes, so flag divergence across
        # ranks must fail the consistency check, not execute.  Joined
        # ranks read it positionally at parts[8].
        # "sharded-full" (ISSUE 18) is the FSDP plane's token: the full-
        # parameter-sharded reduce-scatter/allgather programs must never
        # cross-serve the state-only-sharded (ISSUE 15) ones.  The
        # prefetch/hierarchical flags deliberately do NOT ride the digest
        # (fusion-key-only, results bitwise-identical either way).
        sh = getattr(e, "sharded", False)
        if sh == "full":
            parts.append("sharded-full")
        elif sh:
            parts.append("sharded")
        return "|".join(parts)

    @staticmethod
    def _datadep(e) -> str:
        """Which ranks' REAL data this collective needs: '-1' none
        (reductions/barrier — identity contributions are valid), '-2' every
        rank (allgather/alltoall), or the broadcast root.  The server
        errors instead of granting joined-credit when the needed rank has
        joined."""
        ct = getattr(e, "ctype", None)
        v = getattr(ct, "value", "")
        if v in ("allgather", "alltoall"):
            return "-2"
        if v == "broadcast":
            return str(getattr(e, "root_rank", 0))
        return "-1"

    def negotiate(self, entries: List) -> tuple:
        """One negotiation round.  Takes this cycle's drained entries (they
        may include requeued ones), announces the new names + digests, and
        returns ``(ready, errored)``: the subset ready everywhere in the
        server's global order, and ``(entry, message)`` pairs for per-tensor
        negotiation failures (digest mismatch across ranks)."""
        if self._fault_fire is not None:
            self._fault_fire("pre_announce", self.rank, sever=self._sever)
        by_name: Dict[str, object] = {self._wire_name(e): e for e in entries}
        new = []
        for n, e in by_name.items():
            if n in self._announced:
                continue
            required = 0
            ps_id = getattr(e, "process_set_id", 0)
            if ps_id:
                # Sub-process-set collectives are only announced by member
                # ranks; the server readiness threshold is the set size.
                from .basics import _get_state
                required = _get_state().process_set_table.get(ps_id).size()
            new.append((n, required, self._digest(e),
                        str(getattr(e, "group_id", -1)), self._datadep(e),
                        getattr(e, "sanitizer_tag", None) or "", e))
        self._announced.update(n for n, *_ in new)
        self._trim_slots()
        if self._join_pending:
            self._join_pending = False
            self._joined = True
            new.append(("\x1f__join__", 0, "", "-1", "-1", ""))
        ready, warns, errors = self._round(new)
        for w in warns:
            log.warning("controller: %s", w)
        # The engine requeues not-ready entries, so every announced name
        # reappears in `entries` each cycle; _early_ready only fills in the
        # (defensive) case of a ready verdict arriving before the local
        # requeue is drained.
        ready = self._early_ready + ready
        self._early_ready = []
        out = []
        for name, digest, group in ready:
            if name == "\x1f__all_joined__":
                # Every rank joined: end the join epoch (digest = last
                # joining rank) and unblock the join() caller.
                self._joined = False
                self._join_last_rank = int(digest)
                if self.on_join_epoch is not None:
                    # Monitor aggregation-table flush: snapshots captured
                    # while the world was uneven must not survive the
                    # epoch (mirrors the server's slot-table flush).
                    try:
                        self.on_join_epoch(self._join_last_rank)
                    except Exception:  # noqa: BLE001 - telemetry only
                        log.exception("join-epoch monitor hook failed")
                self._join_event.set()
                continue
            e = by_name.pop(name, None)
            if e is None:
                # The server broadcasts ready verdicts to every rank; a name
                # this rank never announced is either another process set's
                # collective (wire names carry a "\x1f" set prefix — not
                # ours, drop) or — while this rank is JOINED — a world
                # collective peers submitted, for which we synthesize an
                # identity contribution (reference join semantics).
                if name in self._announced:
                    self._early_ready.append((name, digest, group))
                elif self._joined and "\x1f" not in name \
                        and self.synthesizer is not None:
                    out.append(self.synthesizer(name, digest,
                                                self._group_tag_id(group)))
                continue
            self._announced.discard(name)
            out.append(e)
        # Per-tensor errors: fail the local entry (waiters see the exception
        # from synchronize()); re-broadcasts for entries already failed (or
        # another set's tensors) are dropped.  _early_errors covers an error
        # verdict racing ahead of the local requeue drain, like _early_ready.
        errored = []
        for name, msg in dict(self._early_errors).items():
            e = by_name.pop(name, None)
            if e is not None:
                del self._early_errors[name]
                self._announced.discard(name)
                errored.append((e, msg))
        for name, msg in errors:
            e = by_name.pop(name, None)
            if e is None:
                if name in self._announced:
                    self._early_errors[name] = msg
                continue
            self._announced.discard(name)
            errored.append((e, msg))
        return out, errored

    def slot_of(self, e) -> int:
        """The response-cache slot assigned to an entry's announce key, or
        -1 while unlearned.  The compact cross-rank correlation id the
        trace spans carry beside the cycle id (``horovod_tpu.trace``):
        slots are server-assigned, so the same tensor has the same slot on
        every rank.  Read-only — never touches the LRU order."""
        ps_id = getattr(e, "process_set_id", 0)
        required = 0
        if ps_id:
            from .basics import _get_state
            required = _get_state().process_set_table.get(ps_id).size()
        key = (self._wire_name(e), self._digest(e), required,
               self._datadep(e), getattr(e, "group_id", -1) != -1)
        return self._slots.get(key, -1)

    def forget(self, e):
        """Drop all negotiation bookkeeping for an entry failed locally
        (e.g. group-abort) so a retry under the same name renegotiates from
        scratch instead of consuming a stale ready/error verdict.  Also an
        explicit response-cache invalidation: the name's slots are dropped,
        so the retry takes the full-announce path (and relearns)."""
        n = self._wire_name(e)
        self._announced.discard(n)
        self._early_errors.pop(n, None)
        self._early_ready = [t for t in self._early_ready if t[0] != n]
        for key in [k for k in self._slots if k[0] == n]:
            slot = self._slots.pop(key)
            self._slot_keys.pop(slot, None)
            self._predicted.discard(slot)
            self.cache_stats.invalidations += 1
            self._notify_slot_drop(slot)
        self._awaiting_assign = {k for k in self._awaiting_assign
                                 if k[0] != n}

    def _group_tag_id(self, tag: str) -> int:
        """Server group tags ("<first-announcer-rank>:<their gid>"; "-1"
        ungrouped) → local int group ids for the engine's batch clustering.
        Distinct tags get distinct ids, so two different peers' groups can
        never merge on a joined rank."""
        if tag == "-1":
            return -1
        gid = self._group_tags.get(tag)
        if gid is None:
            gid = self._group_tags[tag] = next(self._group_tag_counter)
        return gid

    # --------------------------------------------------------------- join
    def request_join(self):
        """Mark this rank joined as of the next negotiation round
        (reference: hvd.join).  The engine keeps cycling; peers' world
        collectives execute here with synthesized zero contributions until
        every rank has joined."""
        self._join_event.clear()
        self._join_pending = True

    def join_wait(self, timeout: Optional[float] = None) -> int:
        """Block until every rank joined; returns the last rank to join.

        Contract: the return value is always the last joining rank (an
        ``int >= 0``) — never a sentinel.  If the all-joined verdict does
        not arrive within ``timeout`` seconds, raises
        :class:`~.exceptions.JoinTimeoutError` (a ``TimeoutError``
        subclass, so existing handlers keep working); the join stays
        pending and a later ``join_wait`` may still succeed."""
        if not self._join_event.wait(timeout):
            raise JoinTimeoutError(
                f"join() did not complete within {timeout}s: some ranks "
                f"have not joined (the negotiation keeps running; call "
                f"join_wait again to keep waiting)")
        if self._join_error is not None:
            raise self._join_error
        return self._join_last_rank

    def spec_carry_hint(self) -> int:
        """The streak seed a re-rendezvous SURVIVOR carries into the next
        generation (ISSUE 12 elastic streak carryover): non-zero only when
        speculation was armed, advertised by the server, and actually
        engaged (at least one hit) in this generation.  The elastic
        re-init passes it as both the new server's ``spec_seed`` (rank 0)
        and the new client's ``spec_streak_hint``, so the warm path
        re-engages in O(1) rounds instead of relearning from zero."""
        if (self.spec_ready_after <= 0 or not self.peer_zero_rtt_proto
                or self.spec_hits <= 0):
            return 0
        # A live engagement streak carries verbatim; a generation that
        # engaged but was mid-rebuild carries the full threshold anyway —
        # the workload proved stable enough to speculate at least once.
        return max(1, min(self._pred_streak or self.spec_ready_after,
                          self.spec_ready_after))

    def fail_join(self, exc: BaseException):
        """Fail any pending (and every future) ``join_wait`` with ``exc``.

        Part of the engine abort's no-waiter-may-hang invariant: once the
        control plane is down, the all-joined verdict can never arrive —
        a ``hvd.join()`` blocked with ``timeout=None`` would otherwise
        wait forever.  Sticky: this controller generation is dead."""
        self._join_error = exc
        self._join_event.set()

    def leave(self) -> bool:
        """Announce this rank's clean departure (protocol v6): one typed
        LEAVE frame on the lock-step socket, sent IN PLACE of the next
        round frame, immediately before the sever.

        The server drops the rank from the gather with no dead-peer
        verdict — survivors get a leave notice instead of an HVD303 abort
        — and aborts (typed, naming us) only if we still have outstanding
        negotiated work, which is why the frame is refused locally while
        ``_announced`` is non-empty: a LEAVE that would abort the fleet is
        worse than the legacy sever's staggered-shutdown heuristic.

        Caller contract: the engine's cycle thread must be quiesced (no
        lock-step round in flight — ``engine.quiesce()``); version-gated
        on the server's round-1 LVE6 ad, so against a pre-v6 coordinator
        this is a no-op and the sever keeps its legacy semantics.
        Returns True when the frame actually went on the wire."""
        if self._client is None or self.interrupted or self.leave_sent:
            return False
        # Responses still in flight (speculation / round_pipeline > 1) are
        # drained first: the LEAVE frame must be the next thing the server
        # reads from a QUIET socket, and a deferred response may carry the
        # leave-relevant latches (peer_leave_proto on round 1) or a typed
        # abort that makes leaving moot.  Bounded even with the round
        # timeout disabled — a clean shutdown must not block forever on a
        # response a dead coordinator will never finish — and a typed
        # verdict surfacing here is LOGGED with its attribution before the
        # fall-back to the legacy sever: consuming the frame consumed the
        # fleet's only copy of the dead-rank list.
        try:
            acc = ([], [], [])
            while self._outstanding:
                self._drain_one(
                    acc, timeout_ms=int(self.round_timeout_s * 2000) or 5000)
            # Verdicts a deferred response delivered here are parked for
            # the next negotiate (the engine may keep cycling if the
            # leave is refused below) — never dropped.
            for name, digest, group in acc[0]:
                if name in self._announced:
                    self._early_ready.append((name, digest, group))
            for name, msg in acc[2]:
                if name in self._announced:
                    self._early_errors[name] = msg
        except Exception as exc:  # noqa: BLE001 - dead world: legacy sever
            log.warning(
                "clean LEAVE abandoned: draining the in-flight round "
                "window failed (%s); falling back to the legacy sever",
                exc)
            return False
        if (not self.peer_leave_proto
                or self._announced or self._joined or self._join_pending):
            return False
        req = struct.pack("<II", _LEAVE_ESCAPE, _LVE_MAGIC)
        buf = (ctypes.c_uint8 * len(req)).from_buffer_copy(req)
        rc = self._lib.hvdtpu_client_send(self._client, buf, len(req))
        self.leave_sent = rc == 0
        return self.leave_sent

    def interrupt(self):
        """Unblock any thread stuck in a lock-step round (socket shutdown,
        no free) — call before stopping the engine thread.  Sets
        ``interrupted`` first: the severed socket makes the in-flight
        round raise exactly like a peer death, and the engine's cycle
        handler uses the flag to tell expected teardown apart from a
        real HVD303 fault (no spurious abort/log/health flip on every
        clean shutdown)."""
        self.interrupted = True
        self._sever()

    def _sever(self):
        """Abruptly shut down the client socket without marking the
        teardown expected — the fault harness's ``econnreset`` action uses
        this so an injected sever still surfaces as a real HVD303 fault
        on the severed rank."""
        if self._client:
            self._lib.hvdtpu_client_interrupt(self._client)

    def shutdown(self):
        if self._client:
            self._lib.hvdtpu_client_close(self._client)
            self._client = None
        if self._server:
            self._lib.hvdtpu_server_stop(self._server)
            self._server = None
