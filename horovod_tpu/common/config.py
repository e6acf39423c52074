"""Environment-variable configuration surface.

TPU-native equivalent of the reference's env parser
(``horovod/common/utils/env_parser.cc``) and the ``HOROVOD_*`` config surface
described in SURVEY.md §5 ("Config/flag system").  Same two-layer pattern:
env vars are the core config; the launcher (``horovod_tpu/runner``) forwards
CLI/YAML settings to workers as env vars.

We accept both the reference's ``HOROVOD_*`` names (so existing user scripts /
run-books keep working) and ``HVD_TPU_*`` overrides.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional


def _env(name: str, default: Optional[str] = None) -> Optional[str]:
    """Look up HVD_TPU_<name> then HOROVOD_<name>."""
    for prefix in ("HVD_TPU_", "HOROVOD_"):
        val = os.environ.get(prefix + name)
        if val is not None:
            return val
    return default


def _env_int(name: str, default: int) -> int:
    val = _env(name)
    if val is None or val == "":
        return default
    try:
        return int(val)
    except ValueError:
        raise ValueError(f"Invalid integer for HOROVOD_{name}: {val!r}")


def _env_float(name: str, default: float) -> float:
    val = _env(name)
    if val is None or val == "":
        return default
    try:
        return float(val)
    except ValueError:
        raise ValueError(f"Invalid float for HOROVOD_{name}: {val!r}")


def _env_bool(name: str, default: bool) -> bool:
    val = _env(name)
    if val is None or val == "":
        return default
    return val.strip().lower() in ("1", "true", "yes", "on")


@dataclasses.dataclass
class Config:
    """Runtime configuration, parsed once at ``init()``.

    Field-by-field mapping to the reference env vars (SURVEY.md §2a N24, §5):

    - ``fusion_threshold_bytes``   <- HOROVOD_FUSION_THRESHOLD (default 64 MB)
    - ``cycle_time_ms``            <- HOROVOD_CYCLE_TIME
    - ``cache_capacity``           <- HOROVOD_CACHE_CAPACITY (fused program
      cache)
    - ``response_cache_capacity``  <- HOROVOD_RESPONSE_CACHE_CAPACITY
      (negotiation response cache: the steady-state bitvector fast path)
    - ``pipeline_chunk_bytes``     <- HOROVOD_PIPELINE_CHUNK (fused-reduce
      chunk size for pipelined cast/reduce/cast; 0 = single chunk)
    - ``max_inflight``             <- HOROVOD_MAX_INFLIGHT (bounded window
      of dispatched-but-unsettled fused batches, multi-process mode)
    - ``fast_lane_threshold_bytes``<- HOROVOD_FAST_LANE_THRESHOLD (latency
      fast lane: sub-threshold allreduces skip the fusion buffer; 0 = off)
    - ``partition_threshold_bytes``<- HOROVOD_PARTITION_THRESHOLD
      (ByteScheduler-style split of huge tensors into preemptible
      sub-tensors; 0 = off)
    - ``timeline_filename``        <- HOROVOD_TIMELINE
    - ``timeline_mark_cycles``     <- HOROVOD_TIMELINE_MARK_CYCLES
    - ``stall_check_time_s``       <- HOROVOD_STALL_CHECK_TIME
    - ``stall_shutdown_time_s``    <- HOROVOD_STALL_SHUTDOWN_TIME
    - ``stall_check_disable``      <- HOROVOD_STALL_CHECK_DISABLE
    - ``hierarchical_allreduce``   <- HOROVOD_HIERARCHICAL_ALLREDUCE
    - ``hierarchical_allgather``   <- HOROVOD_HIERARCHICAL_ALLGATHER
    - ``hierarchical_broadcast``   <- HOROVOD_HIERARCHICAL_BROADCAST
    - ``hier_threshold_bytes``     <- HOROVOD_HIER_THRESHOLD (flat-vs-
      two-level payload crossover; 0 = always two-level when armed)
    - ``slice_map``                <- HOROVOD_SLICE_MAP (explicit slice
      membership for CPU/simulated worlds; see parallel/topology.py)
    - ``sharded_params``           <- HOROVOD_SHARDED_PARAMS (ZeRO-3/FSDP:
      DistributedOptimizer defaults to sharded="full")
    - ``prefetch_depth``           <- HOROVOD_PREFETCH_DEPTH (FSDP
      parameter-gather buckets in flight ahead of consumption)
    - ``autotune``                 <- HOROVOD_AUTOTUNE
    - ``autotune_log``             <- HOROVOD_AUTOTUNE_LOG
    - ``autotune_warmup_samples``  <- HOROVOD_AUTOTUNE_WARMUP_SAMPLES
    - ``autotune_steps_per_sample``<- HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE
    - ``autotune_max_evals``       <- HOROVOD_AUTOTUNE_MAX_EVALS
    - ``log_level``                <- HOROVOD_LOG_LEVEL
    - ``batch_d2d_memcopies``      <- HOROVOD_BATCH_D2D_MEMCOPIES

    TPU-specific additions:

    - ``num_collective_streams``: number of parallel eager-dispatch lanes
      (analogue of HOROVOD_NUM_NCCL_STREAMS).
    - ``donate_fusion_buffers``: use XLA buffer donation for fused buffers.
    - ``mesh_axis_name``: the mesh axis spanned by the "hvd" world.
    """

    fusion_threshold_bytes: int = 64 * 1024 * 1024
    cycle_time_ms: float = 1.0
    cache_capacity: int = 1024
    cache_enabled: bool = True
    # Negotiation response cache (HOROVOD_RESPONSE_CACHE_CAPACITY, upstream
    # HOROVOD_CACHE_CAPACITY's role): slot-table size for the steady-state
    # bitvector fast path, client-side AND server-side.  0 disables (every
    # cycle does full metadata negotiation).  Runtime-tunable via autotune.
    response_cache_capacity: int = 2048

    # Pipelined data plane (HOROVOD_PIPELINE_CHUNK / HOROVOD_MAX_INFLIGHT).
    # pipeline_chunk_bytes splits each fused reduction buffer into chunks so
    # the cast-down → reduce → cast-up stages overlap across chunks inside
    # the jitted program; 0 (default) = one chunk per fused batch, i.e. the
    # batch-sized single collective (fused batches already split at the
    # fusion threshold).  max_inflight bounds the dispatched-but-unsettled
    # window in multi-process mode: >1 lets the cycle thread negotiate
    # round N+1 while the device executes round N.  Both are autotune
    # coordinates when a controller exists.
    pipeline_chunk_bytes: int = 0
    max_inflight: int = 2

    # Small-message latency war (ISSUE 8, docs/performance.md "Latency
    # fast lane").  fast_lane_threshold_bytes: ungrouped allreduces below
    # this many bytes skip the fusion-buffer batching entirely — direct
    # single-tensor dispatch through a persistent pre-compiled program
    # (still negotiated, still response-cache-slotted, bitwise-identical
    # results); 0 = off.  partition_threshold_bytes: tensors above this
    # many bytes split into priority-inheriting sub-tensors so a small
    # high-priority gradient preempts a huge transfer between parts
    # instead of queueing behind the whole of it (ByteScheduler, Peng et
    # al. SOSP 2019); reassembled transparently at synchronize; 0 = off.
    # Both must be identical on every rank (the launcher forwards them;
    # autotune broadcasts fast-lane moves).
    fast_lane_threshold_bytes: int = 0
    partition_threshold_bytes: int = 0

    # Cross-rank telemetry & health subsystem (horovod_tpu.monitor,
    # docs/monitoring.md).  HOROVOD_MONITOR=1 enables the per-rank metric
    # registry + the coordinator monitor side-channel (protocol v3);
    # HOROVOD_MONITOR_PORT > 0 additionally serves /metrics (Prometheus) +
    # /health (JSON) over HTTP on rank 0; HOROVOD_MONITOR_INTERVAL is the
    # snapshot reporting period in seconds.
    monitor: bool = False
    monitor_port: int = 0
    monitor_interval_s: float = 5.0

    # Control-plane fault tolerance (protocol v4, docs/fault_tolerance.md).
    # round_timeout_s: per-negotiation-round wall-clock deadline — the
    # server declares ranks that miss it dead and broadcasts a typed ABORT
    # to survivors; the client bounds its own response wait at 2x.  Must
    # exceed the worst legitimate inter-rank skew (XLA compiles!); 0
    # disables the deadlines (dead-socket detection is always on).
    # connect_retries / connect_backoff_ms: bounded controller-connect
    # retries with exponential backoff + jitter, so workers may start
    # before the coordinator.
    round_timeout_s: float = 0.0
    connect_retries: int = 3
    connect_backoff_ms: float = 500.0

    # Zero-RTT warm control plane (protocol v7, docs/performance.md
    # "Zero-RTT warm path").  spec_ready_after (HOROVOD_SPEC_READY_AFTER):
    # after a response-cache slot has been ready-on-first-announce for
    # this many consecutive rounds, the root piggybacks a predicted
    # next-round verdict and clients may dispatch it without waiting for
    # the response; 0 (default) = off, every round lock-step.
    # round_pipeline (HOROVOD_ROUND_PIPELINE): client-side in-flight
    # negotiation-round window — 1 (default) = lock-step, >1 sends round
    # N+1's request before round N's response is read.  Both runtime-
    # tunable (autotune coordinates in multi-process mode); results are
    # bitwise-identical either way (a mispredict only delays a verdict by
    # one normal round).
    spec_ready_after: int = 0
    round_pipeline: int = 1

    timeline_filename: str = ""
    timeline_mark_cycles: bool = False

    # Distributed collective tracing (horovod_tpu.trace, docs/timeline.md).
    # HOROVOD_TRACE=<path> arms per-tensor lifecycle spans AND writes this
    # rank's trace file there (the launcher suffixes the base per rank;
    # merge with `python -m horovod_tpu.trace`); HOROVOD_TRACE=1 arms the
    # in-memory recorder only (digests still ride the monitor side-channel;
    # benchmark/ reads the program spans of a --trace 1 run).  Unset =
    # strictly zero cost.
    # HOROVOD_TRACE_RING bounds the preallocated span ring.
    trace: bool = False
    trace_filename: str = ""
    trace_ring: int = 4096

    stall_check_time_s: float = 60.0
    stall_shutdown_time_s: float = 0.0
    stall_check_disable: bool = False

    hierarchical_allreduce: bool = False
    hierarchical_allgather: bool = False
    # Two-level broadcast on the same slice topology (ISSUE 19 satellite):
    # leader exchange across DCN, then intra-slice fan-out over ICI —
    # bitwise-identical to flat (pure data movement).  Like the allgather
    # knob, the decision is purely topological (no payload crossover) and
    # rides the fusion key only, never the negotiation digest.
    hierarchical_broadcast: bool = False
    # Local-axis extent for the two-level (cross x local) collectives; 0 =
    # derive from the topology's per-process device counts (multi-host).
    hierarchical_local_size: int = 0
    # Payload crossover for the two-level data plane (ISSUE 17,
    # docs/performance.md "Hierarchical collectives"): fused allreduce
    # batches whose per-rank payload is at least this many bytes take the
    # RS(ICI) -> AR(DCN) -> AG(ICI) schedule; smaller batches stay flat
    # (the two extra phase latencies outweigh the DCN byte savings for
    # small payloads).  0 = every eligible batch goes two-level once the
    # mode is armed.  An autotune coordinate (``hier_threshold``) when the
    # mode is armed; like HOROVOD_PIPELINE_CHUNK it is NOT part of the
    # negotiation digest, so retunes cost zero control-plane traffic.
    hier_threshold_bytes: int = 0
    # Explicit slice membership for CPU/simulated worlds ("4" = uniform
    # slice size, "4,4" = per-slice sizes); empty = derive from device
    # slice_index attributes / hierarchical_local_size / process counts
    # (parallel/topology.py precedence order).
    slice_map: str = ""

    # Two-level control plane (protocol v5, docs/performance.md "Control
    # plane at scale").  HOROVOD_HIERARCHICAL_CONTROLLER=1: every rank's
    # negotiation client connects to a per-host agent
    # (common/host_agent.py, owned by the local_rank-0 process) instead of
    # the rank-0 root server; the agent collapses its host's warm-path
    # bitvector frames into ONE fixed-size uplink per round, so root-side
    # gather work scales with hosts, not ranks.  Per-rank wire bytes are
    # unchanged (frame-guarded).  Flat single-server mode remains the
    # default.  Elastic worlds compose (ISSUE 12): the agent object
    # survives re-rendezvous generations on a stable per-host port the
    # elastic driver allocates and ships through the rendezvous
    # assignment.  HOROVOD_AGENT_PORT: the agent's listen port on each
    # host (the launcher — or the elastic rendezvous — assigns one per
    # host); 0 = derive deterministically from controller port +
    # cross_rank.
    hierarchical_controller: bool = False
    agent_port: int = 0

    # Preemption-driven drains (ISSUE 12, docs/elastic.md).  When the
    # discovery source posts a preemption notice for a host (e.g.
    # TPUMetadataDiscovery's `preempted-workers` attribute), the elastic
    # driver cordons the host and DRAINs its workers — requesting a state
    # commit first (checkpoint pacing), then the clean-LEAVE departure —
    # instead of waiting for the hardware to vanish and crash the fleet
    # mid-collective.  HOROVOD_PREEMPT_GRACE_S bounds the drain: a worker
    # that has not exited by the deadline is terminated (the legacy sever
    # path), still classified as a departure, never a blacklist.
    preempt_grace_s: float = 30.0

    # Resilient state plane (ISSUE 14, docs/fault_tolerance.md "Resilient
    # state plane").  HOROVOD_CKPT_DIR arms overlap-scheduled sharded
    # checkpoints: on every elastic-state commit each rank streams its
    # 1/N shard of the serialized state through the engine's lowest-
    # priority `checkpoint` dispatch lane (two-phase manifest; gradient
    # dispatch order provably unchanged) and serves the committed epoch
    # to re-joining ranks peer-to-peer (disk is the fallback).
    # HOROVOD_CKPT_CHUNK bounds one lane item's write; HOROVOD_CKPT_
    # LANE_BUDGET bounds chunks per engine cycle.  HOROVOD_COMMIT_MAX_
    # AGE_S is the autoscaler's stale-state guard: evict/scale_in
    # decisions are refused while the fleet's last commit is older than
    # this (0 = off) — shrinking a world whose restore point is stale
    # would convert an orderly drain into lost work.
    ckpt_dir: str = ""
    ckpt_chunk_bytes: int = 1 << 20
    ckpt_lane_budget: int = 2
    commit_max_age_s: float = 0.0

    # ZeRO-sharded optimizer (ISSUE 15, docs/performance.md "Sharded
    # optimizer (ZeRO)").  HOROVOD_SHARDED_OPTIMIZER=1 flips every
    # DistributedOptimizer built without an explicit ``sharded=`` to the
    # reduce-scatter → 1/N shard update → allgather data plane: optimizer
    # state lives 1/world per rank in HBM and gradient bytes ride the
    # scatter at half an allreduce's wire cost.  Must be identical on
    # every rank (the launcher's --sharded forwards it): the sharded flag
    # is part of the negotiation digest, so divergence fails fast.
    sharded_optimizer: bool = False

    # Full parameter sharding (ISSUE 18, ZeRO-3/FSDP — docs/performance.md
    # "Full parameter sharding (FSDP)").  HOROVOD_SHARDED_PARAMS=1 flips
    # every DistributedOptimizer built without an explicit ``sharded=`` to
    # ``sharded="full"``: parameters live 1/world per rank, forward-pass
    # parameters rematerialize through prefetch allgathers on the engine's
    # PREFETCH lane, gradients reduce-scatter straight into the owning
    # shard.  Takes precedence over HOROVOD_SHARDED_OPTIMIZER; must be
    # identical on every rank (part of the negotiation digest as the
    # "sharded-full" token).  HOROVOD_PREFETCH_DEPTH bounds how many
    # buckets of gathered parameters may be in flight ahead of
    # consumption (peak HBM = shard + depth × bucket bytes); a local
    # knob like HOROVOD_PIPELINE_CHUNK — never negotiated, autotunable.
    sharded_params: bool = False
    prefetch_depth: int = 2

    # Closed-loop elastic autoscaling (docs/elastic.md "Closed-loop
    # autoscaling") — consumed by the elastic DRIVER (torovodrun
    # --host-discovery-script), not by workers.  HOROVOD_AUTOSCALE=1
    # turns the policy loop on (requires --monitor-port so the driver can
    # poll rank 0's /health for the aggregation summary); the remaining
    # knobs parameterize elastic/autoscale.ScalePolicy: observation
    # period, scale-out queue thresholds (absolute + EWMA trend),
    # straggler-evict factor vs the peer median, hysteresis persistence
    # (consecutive observations), post-decision cooldown, and the idle
    # window before scale-in.
    autoscale: bool = False
    autoscale_interval_s: float = 5.0
    autoscale_queue_high: float = 16.0
    autoscale_queue_trend: float = 4.0
    autoscale_straggler_factor: float = 3.0
    autoscale_persistence: int = 3
    autoscale_cooldown_s: float = 30.0
    autoscale_idle_s: float = 60.0
    # Request-rate / latency-target autoscaling (ISSUE 19, serving mode;
    # docs/serving.md).  All three are off at 0.  autoscale_rate_high:
    # fleet-aggregate offered QPS per replica above which (with a rising
    # EWMA trend) the policy scales out.  autoscale_latency_target_ms:
    # serving p99 latency SLO — p99 above target counts toward scale_out
    # with the same persistence/cooldown hysteresis as the queue signals.
    # autoscale_idle_qps: offered load below this feeds the idle timer
    # (scale_in after autoscale_idle_s), replacing the training-progress
    # idle test when serving instruments are present.
    autoscale_rate_high: float = 0.0
    autoscale_latency_target_ms: float = 0.0
    autoscale_idle_qps: float = 0.0

    # Data-parallel serving plane (ISSUE 19, horovod_tpu.serve,
    # docs/serving.md).  HOROVOD_SERVE=1 turns a launched worker fleet
    # into inference replicas (torovodrun --serve); HOROVOD_SERVE_PORT is
    # the rank-0 front-door HTTP ingest port (0 = in-process API only).
    # serve_max_batch bounds one forward step's batch; serve_buckets
    # ("1,2,4,8") pins the padded batch shapes the jitted forward may
    # see — batch-size churn rounds up to a bucket so the program cache
    # never recompiles mid-traffic (empty = powers of two up to
    # serve_max_batch).  serve_deadline_ms is the per-request admission
    # deadline (expired requests are failed, never dispatched);
    # serve_max_inflight bounds admitted-but-unsettled batches (the
    # HOROVOD_MAX_INFLIGHT window semantics applied at the front door;
    # 0 = inherit max_inflight); serve_queue_depth bounds the ingest
    # queue — a full queue is backpressure (HTTP 429 + queue-depth
    # signal), the load-balancer/autoscaler signal to shed or grow.
    serve: bool = False
    serve_port: int = 0
    serve_max_batch: int = 8
    serve_buckets: str = ""
    serve_deadline_ms: float = 1000.0
    serve_max_inflight: int = 0
    serve_queue_depth: int = 128
    # Serving fault tolerance (ISSUE 20) — ALL serve-local: consumed by
    # the front door / batcher on this rank only, never negotiated, zero
    # bytes on the warm control-plane frame.  serve_retries bounds the
    # front door's deadline-charged retry loop for RETRYABLE failures;
    # serve_hedge_ms > 0 arms tail-latency hedging (the value is the
    # cold-start delay until an observed p99 exists); the breaker trips
    # after serve_breaker_threshold consecutive retryable failures,
    # fast-fails 503 + Retry-After for serve_breaker_reset_s, then
    # half-opens and closes after serve_breaker_probes good probes;
    # serve_quarantine_after consecutive forward failures of ONE request
    # fail it terminally (poisoned input, not replica fault).
    serve_retries: int = 2
    serve_hedge_ms: float = 0.0
    serve_breaker_threshold: int = 5
    serve_breaker_reset_s: float = 5.0
    serve_breaker_probes: int = 2
    serve_quarantine_after: int = 3

    autotune: bool = False
    autotune_log: str = ""
    autotune_warmup_samples: int = 3
    autotune_steps_per_sample: int = 10
    autotune_max_evals: int = 48

    log_level: str = "warning"
    batch_d2d_memcopies: bool = True

    num_collective_streams: int = 1
    donate_fusion_buffers: bool = True
    mesh_axis_name: str = "hvd"
    # Pod mode (HOROVOD_ONE_PROC_PER_HOST): one launched process drives all
    # of its host's chips.  jax.distributed auto-detects the world, and
    # rank()/local_rank()/local_size() come from the device topology — the
    # launcher's env values describe the PROCESS world (control plane),
    # not the device world.
    one_proc_per_host: bool = False

    # Control plane (multi-process mode). Set by the launcher.
    controller_addr: str = ""
    controller_port: int = 0
    controller_port2: int = 0
    rank_env: int = -1
    size_env: int = -1
    local_rank_env: int = -1
    local_size_env: int = -1
    cross_rank_env: int = -1
    cross_size_env: int = -1

    # Elastic
    elastic: bool = False

    @classmethod
    def from_env(cls) -> "Config":
        cfg = cls(
            fusion_threshold_bytes=_env_int("FUSION_THRESHOLD", 64 * 1024 * 1024),
            cycle_time_ms=_env_float("CYCLE_TIME", 1.0),
            cache_capacity=_env_int("CACHE_CAPACITY", 1024),
            response_cache_capacity=_env_int("RESPONSE_CACHE_CAPACITY", 2048),
            pipeline_chunk_bytes=_env_int("PIPELINE_CHUNK", 0),
            max_inflight=_env_int("MAX_INFLIGHT", 2),
            fast_lane_threshold_bytes=_env_int("FAST_LANE_THRESHOLD", 0),
            partition_threshold_bytes=_env_int("PARTITION_THRESHOLD", 0),
            monitor=_env_bool("MONITOR", False),
            monitor_port=_env_int("MONITOR_PORT", 0),
            monitor_interval_s=_env_float("MONITOR_INTERVAL", 5.0),
            round_timeout_s=_env_float("ROUND_TIMEOUT_S", 0.0),
            connect_retries=_env_int("CONNECT_RETRIES", 3),
            connect_backoff_ms=_env_float("CONNECT_BACKOFF_MS", 500.0),
            spec_ready_after=_env_int("SPEC_READY_AFTER", 0),
            round_pipeline=_env_int("ROUND_PIPELINE", 1),
            timeline_filename=_env("TIMELINE", "") or "",
            timeline_mark_cycles=_env_bool("TIMELINE_MARK_CYCLES", False),
            trace_ring=_env_int("TRACE_RING", 4096),
            stall_check_time_s=_env_float("STALL_CHECK_TIME", 60.0),
            stall_shutdown_time_s=_env_float("STALL_SHUTDOWN_TIME", 0.0),
            stall_check_disable=_env_bool("STALL_CHECK_DISABLE", False),
            hierarchical_allreduce=_env_bool("HIERARCHICAL_ALLREDUCE", False),
            hierarchical_allgather=_env_bool("HIERARCHICAL_ALLGATHER", False),
            hierarchical_broadcast=_env_bool("HIERARCHICAL_BROADCAST", False),
            hierarchical_local_size=_env_int("HIERARCHICAL_LOCAL_SIZE", 0),
            hier_threshold_bytes=_env_int("HIER_THRESHOLD", 0),
            slice_map=_env("SLICE_MAP", "") or "",
            hierarchical_controller=_env_bool("HIERARCHICAL_CONTROLLER",
                                              False),
            agent_port=_env_int("AGENT_PORT", 0),
            preempt_grace_s=_env_float("PREEMPT_GRACE_S", 30.0),
            ckpt_dir=_env("CKPT_DIR", "") or "",
            ckpt_chunk_bytes=_env_int("CKPT_CHUNK", 1 << 20),
            ckpt_lane_budget=_env_int("CKPT_LANE_BUDGET", 2),
            commit_max_age_s=_env_float("COMMIT_MAX_AGE_S", 0.0),
            sharded_optimizer=_env_bool("SHARDED_OPTIMIZER", False),
            sharded_params=_env_bool("SHARDED_PARAMS", False),
            prefetch_depth=_env_int("PREFETCH_DEPTH", 2),
            autoscale=_env_bool("AUTOSCALE", False),
            autoscale_interval_s=_env_float("AUTOSCALE_INTERVAL", 5.0),
            autoscale_queue_high=_env_float("AUTOSCALE_QUEUE_HIGH", 16.0),
            autoscale_queue_trend=_env_float("AUTOSCALE_QUEUE_TREND", 4.0),
            autoscale_straggler_factor=_env_float(
                "AUTOSCALE_STRAGGLER_FACTOR", 3.0),
            autoscale_persistence=_env_int("AUTOSCALE_PERSISTENCE", 3),
            autoscale_cooldown_s=_env_float("AUTOSCALE_COOLDOWN", 30.0),
            autoscale_idle_s=_env_float("AUTOSCALE_IDLE_S", 60.0),
            autoscale_rate_high=_env_float("AUTOSCALE_RATE_HIGH", 0.0),
            autoscale_latency_target_ms=_env_float(
                "AUTOSCALE_LATENCY_TARGET_MS", 0.0),
            autoscale_idle_qps=_env_float("AUTOSCALE_IDLE_QPS", 0.0),
            serve=_env_bool("SERVE", False),
            serve_port=_env_int("SERVE_PORT", 0),
            serve_max_batch=_env_int("SERVE_MAX_BATCH", 8),
            serve_buckets=_env("SERVE_BUCKETS", "") or "",
            serve_deadline_ms=_env_float("SERVE_DEADLINE_MS", 1000.0),
            serve_max_inflight=_env_int("SERVE_MAX_INFLIGHT", 0),
            serve_queue_depth=_env_int("SERVE_QUEUE_DEPTH", 128),
            serve_retries=_env_int("SERVE_RETRIES", 2),
            serve_hedge_ms=_env_float("SERVE_HEDGE_MS", 0.0),
            serve_breaker_threshold=_env_int("SERVE_BREAKER_THRESHOLD", 5),
            serve_breaker_reset_s=_env_float("SERVE_BREAKER_RESET_S", 5.0),
            serve_breaker_probes=_env_int("SERVE_BREAKER_PROBES", 2),
            serve_quarantine_after=_env_int("SERVE_QUARANTINE_AFTER", 3),
            autotune=_env_bool("AUTOTUNE", False),
            autotune_log=_env("AUTOTUNE_LOG", "") or "",
            autotune_warmup_samples=_env_int("AUTOTUNE_WARMUP_SAMPLES", 3),
            autotune_steps_per_sample=_env_int("AUTOTUNE_STEPS_PER_SAMPLE", 10),
            autotune_max_evals=_env_int("AUTOTUNE_MAX_EVALS", 48),
            log_level=(_env("LOG_LEVEL", "warning") or "warning").lower(),
            batch_d2d_memcopies=_env_bool("BATCH_D2D_MEMCOPIES", True),
            num_collective_streams=_env_int("NUM_STREAMS", 1),
            donate_fusion_buffers=_env_bool("DONATE_FUSION_BUFFERS", True),
            one_proc_per_host=_env_bool("ONE_PROC_PER_HOST", False),
            controller_addr=_env("CONTROLLER_ADDR", "") or "",
            controller_port=_env_int("CONTROLLER_PORT", 0),
            controller_port2=_env_int("CONTROLLER_PORT2", 0),
            rank_env=_env_int("RANK", -1),
            size_env=_env_int("SIZE", -1),
            local_rank_env=_env_int("LOCAL_RANK", -1),
            local_size_env=_env_int("LOCAL_SIZE", -1),
            cross_rank_env=_env_int("CROSS_RANK", -1),
            cross_size_env=_env_int("CROSS_SIZE", -1),
            elastic=_env_bool("ELASTIC", False),
        )
        if _env_int("CACHE_CAPACITY", 1024) == 0:
            cfg.cache_enabled = False
        # HOROVOD_TRACE: a bool-ish value arms the in-memory recorder only;
        # anything else is the per-rank trace file path (and arms it).
        raw_trace = (_env("TRACE", "") or "").strip()
        if raw_trace:
            cfg.trace = raw_trace.lower() not in ("0", "false", "no", "off")
            if cfg.trace and raw_trace.lower() not in ("1", "true", "yes",
                                                       "on"):
                cfg.trace_filename = raw_trace
        return cfg
