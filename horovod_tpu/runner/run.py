"""``torovodrun`` argument surface and launch orchestration.

Parity with the reference launcher (``horovod/runner/launch.py``, ``run.py``,
``gloo_run.py``, ``mpi_run.py`` — SURVEY.md §2b P7, §3.3): parse
``-np``/``-H``/``--hostfile``/elastic/timeline/autotune/fusion flags (plus
``--config-file`` YAML mirroring them), compute the rank→host placement, and
spawn per-rank worker processes with the ``HOROVOD_*`` environment injected.

TPU-first differences:
- No mpirun backend: workers are spawned directly (localhost) or over ssh,
  and the distributed world is formed by ``jax.distributed`` against the
  launcher-chosen coordinator (replacing the Gloo HTTP rendezvous).
- ``--tpu-topology-aware`` orders ranks by ICI torus coordinates (the
  reference orders by hostfile slots).
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import shlex
import socket
import subprocess
import sys
import time
from typing import Dict, List, Optional, Sequence, Tuple

from ..utils.timeline import per_rank_filename


@dataclasses.dataclass
class HostSpec:
    hostname: str
    slots: int


def parse_hosts(hosts: str) -> List[HostSpec]:
    """Parse ``-H host1:2,host2:4`` (reference: runner/common/util/hosts.py)."""
    specs = []
    for part in hosts.split(","):
        part = part.strip()
        if not part:
            continue
        if ":" in part:
            name, slots = part.rsplit(":", 1)
            specs.append(HostSpec(name, int(slots)))
        else:
            specs.append(HostSpec(part, 1))
    return specs


def parse_hostfile(path: str) -> List[HostSpec]:
    """Parse a hostfile with ``hostname slots=N`` lines (reference format)."""
    specs = []
    with open(path) as fh:
        for line in fh:
            line = line.split("#", 1)[0].strip()
            if not line:
                continue
            fields = line.split()
            name = fields[0]
            slots = 1
            for f in fields[1:]:
                if f.startswith("slots="):
                    slots = int(f.split("=", 1)[1])
            specs.append(HostSpec(name, slots))
    return specs


def parse_args(argv: Sequence[str]) -> argparse.Namespace:
    p = argparse.ArgumentParser(
        prog="torovodrun",
        description="Launch a horovod_tpu distributed job",
        usage="torovodrun -np NP [options] <command> [args...]")
    p.add_argument("-np", "--num-proc", type=int, dest="np",
                   help="Total number of worker processes")
    p.add_argument("-H", "--hosts", dest="hosts",
                   help="Comma-separated host:slots list")
    p.add_argument("--hostfile", dest="hostfile",
                   help="Hostfile with 'hostname slots=N' lines")
    p.add_argument("--network-interface", dest="nics",
                   help="Network interface(s) for the control plane")
    p.add_argument("--start-timeout", type=int, default=600)
    p.add_argument("--ssh-port", type=int, default=None)
    p.add_argument("--ssh-identity-file", default=None)
    p.add_argument("--verbose", "-v", action="count", default=0)
    p.add_argument("--config-file", dest="config_file",
                   help="YAML config mirroring the CLI flags")
    p.add_argument("--output-filename", dest="output_filename",
                   help="Redirect worker stdout/stderr to "
                        "<dir>/rank.<N>/stdout|stderr")
    # Tuning knobs forwarded as HOROVOD_* env (reference: launch.py does the
    # same forwarding).
    p.add_argument("--fusion-threshold-mb", type=int, default=None)
    p.add_argument("--cycle-time-ms", type=float, default=None)
    p.add_argument("--cache-capacity", type=int, default=None)
    p.add_argument("--pipeline-chunk-mb", type=float, default=None,
                   help="Chunk size (MB) for pipelined fused reductions; "
                        "0 = one chunk per fused batch (no chunking)")
    p.add_argument("--max-inflight", type=int, default=None,
                   help="Bound on dispatched-but-unsettled fused batches "
                        "(1 = settle inline, no overlap)")
    p.add_argument("--fast-lane-threshold-kb", type=float, default=None,
                   help="Latency fast lane: ungrouped allreduces below "
                        "this many KB skip the fusion buffer (persistent "
                        "pre-compiled single-tensor programs); 0 = off")
    p.add_argument("--partition-threshold-mb", type=float, default=None,
                   help="Split tensors above this many MB into priority-"
                        "inheriting sub-tensors (ByteScheduler-style "
                        "preemption); 0 = off")
    p.add_argument("--spec-ready-after", type=int, default=None,
                   help="Zero-RTT warm path (protocol v7): after a "
                        "response-cache slot has been ready-on-first-"
                        "announce for this many consecutive rounds, the "
                        "coordinator predicts the next-round verdict and "
                        "clients dispatch it without waiting; 0 = off")
    p.add_argument("--round-pipeline", type=int, default=None,
                   help="In-flight negotiation-round window per client: "
                        "1 = lock-step (default), >1 sends round N+1's "
                        "request before round N's response is read")
    p.add_argument("--timeline-filename", default=None)
    p.add_argument("--timeline-mark-cycles", action="store_true")
    p.add_argument("--trace-filename", default=None,
                   help="Arm distributed collective tracing and write one "
                        "trace file per rank at <base>.<rank>; merge with "
                        "`python -m horovod_tpu.trace` (docs/timeline.md)")
    p.add_argument("--trace-ring", type=int, default=None,
                   help="Preallocated trace span-ring capacity "
                        "(default 4096)")
    p.add_argument("--monitor", action="store_true",
                   help="Enable the cross-rank telemetry & health "
                        "subsystem (docs/monitoring.md)")
    p.add_argument("--monitor-port", type=int, default=None,
                   help="Serve /metrics (Prometheus) + /health (JSON) "
                        "over HTTP on rank 0 at this port (implies "
                        "--monitor)")
    p.add_argument("--monitor-interval", type=float, default=None,
                   help="Telemetry snapshot period in seconds (default 5)")
    p.add_argument("--stall-check-time", type=float, default=None)
    p.add_argument("--stall-shutdown-time", type=float, default=None)
    p.add_argument("--round-timeout", type=float, default=None,
                   help="Per-negotiation-round wall-clock deadline in "
                        "seconds (docs/fault_tolerance.md): ranks that "
                        "miss it are declared dead and survivors get a "
                        "typed HVD303 abort; 0/unset disables the "
                        "deadline (dead-socket detection is always on)")
    p.add_argument("--connect-retries", type=int, default=None,
                   help="Bounded controller-connect retries (workers may "
                        "start before the coordinator)")
    p.add_argument("--connect-backoff-ms", type=float, default=None,
                   help="Base backoff between connect retries "
                        "(exponential, jittered)")
    p.add_argument("--autotune", action="store_true")
    p.add_argument("--autotune-log-file", default=None)
    p.add_argument("--sharded", action="store_true",
                   help="ZeRO-sharded optimizer data plane (docs/"
                        "performance.md 'Sharded optimizer (ZeRO)'): "
                        "DistributedOptimizer defaults to sharded=True — "
                        "reduce-scatter of gradients, 1/N-per-rank "
                        "optimizer state, allgather of updates.  "
                        "Forwarded as HOROVOD_SHARDED_OPTIMIZER so every "
                        "rank takes the identical data plane")
    p.add_argument("--sharded-params", action="store_true",
                   help="Full parameter sharding (ZeRO-3/FSDP, docs/"
                        "performance.md 'Full parameter sharding "
                        "(FSDP)'): DistributedOptimizer defaults to "
                        'sharded="full" — parameters live 1/N per rank, '
                        "prefetch allgathers rematerialize them ahead of "
                        "use, gradients reduce-scatter into the owning "
                        "shard.  Forwarded as HOROVOD_SHARDED_PARAMS")
    p.add_argument("--prefetch-depth", type=int, default=None,
                   help="FSDP parameter-gather buckets in flight ahead "
                        "of consumption (HOROVOD_PREFETCH_DEPTH; "
                        "default 2)")
    p.add_argument("--hierarchical-allreduce", action="store_true")
    p.add_argument("--hierarchical-allgather", action="store_true",
                   help="Two-level allgather on the slice topology "
                        "(intra-ICI gather after a cross-DCN leader "
                        "exchange) — the gather legs FSDP makes hot; "
                        "bitwise-identical to flat "
                        "(HOROVOD_HIERARCHICAL_ALLGATHER)")
    p.add_argument("--hierarchical-broadcast", action="store_true",
                   help="Two-level broadcast on the slice topology (one "
                        "cross-DCN leader exchange, then intra-ICI "
                        "fan-out) — the leg serving weight fan-out makes "
                        "hot; bitwise-identical to flat "
                        "(HOROVOD_HIERARCHICAL_BROADCAST)")
    p.add_argument("--serve", action="store_true",
                   help="Serving plane (docs/serving.md): each rank runs "
                        "a continuous-batching front door + replica "
                        "forward loop instead of a training loop.  "
                        "Forwarded as HOROVOD_SERVE; knobs via "
                        "HOROVOD_SERVE_* (port, max batch, buckets, "
                        "deadline, inflight window, queue depth)")
    p.add_argument("--serve-port", type=int, default=None,
                   help="Front-door HTTP port base; rank r listens on "
                        "port+r (HOROVOD_SERVE_PORT; 0/unset = ephemeral)")
    p.add_argument("--hierarchical-controller", action="store_true",
                   help="Two-level control plane (docs/performance.md "
                        "'Control plane at scale'): a per-host agent "
                        "aggregates its ranks' warm-path negotiation "
                        "frames into one fixed-size uplink per round, so "
                        "the rank-0 coordinator's gather scales with "
                        "hosts, not ranks")
    p.add_argument("--tpu-topology-aware", action="store_true", default=True)
    # Elastic (reference: _run_elastic)
    p.add_argument("--min-np", type=int, default=None)
    p.add_argument("--max-np", type=int, default=None)
    p.add_argument("--host-discovery-script", default=None)
    p.add_argument("--tpu-metadata-discovery", action="store_true",
                   help="Discover slice membership + preemption notices "
                        "from the TPU-VM metadata service instead of a "
                        "script (elastic mode; URL override via "
                        "HOROVOD_TPU_METADATA_URL)")
    p.add_argument("--slots-per-host", type=int, default=None)
    p.add_argument("--autoscale", action="store_true",
                   help="Closed-loop autoscaling (elastic mode; docs/"
                        "elastic.md): the driver polls rank 0's monitor "
                        "/health and scales the world itself — out on "
                        "rising load, straggler drain-and-evict on "
                        "monitor attribution, in when idle.  Requires "
                        "--monitor-port; knobs via HOROVOD_AUTOSCALE_*")
    p.add_argument("--autoscale-interval", type=float, default=None,
                   help="Seconds between autoscale policy observations "
                        "(default 5)")
    p.add_argument("--scale-command", default=None,
                   help="Operator capacity hook run on scale decisions "
                        "with HVD_AUTOSCALE_ACTION/TARGET/HOST in env; "
                        "it changes what --host-discovery-script reports "
                        "(e.g. resizes an instance group)")
    p.add_argument("--preempt-grace-s", type=float, default=None,
                   help="Drain grace for preemption notices (elastic "
                        "mode): a noticed host's workers get this long "
                        "to commit + clean-LEAVE before the driver falls "
                        "back to termination (default 30)")
    p.add_argument("--ckpt-dir", default=None,
                   help="Resilient state plane (docs/fault_tolerance.md "
                        "'Resilient state plane'): arm overlap-scheduled "
                        "sharded checkpoints under this directory — each "
                        "rank streams its 1/N state shard through the "
                        "engine's lowest-priority checkpoint lane on "
                        "every elastic-state commit, and re-joining "
                        "ranks restore peer-to-peer from survivors")
    p.add_argument("--ckpt-chunk-mb", type=float, default=None,
                   help="Checkpoint-lane chunk size in MB (one bounded "
                        "write per lane dispatch; default 1)")
    p.add_argument("--ckpt-lane-budget", type=int, default=None,
                   help="Checkpoint chunks dispatched per engine cycle "
                        "tail (default 2)")
    p.add_argument("--commit-max-age-s", type=float, default=None,
                   help="Autoscaler stale-state guard: refuse evict/"
                        "scale_in while the fleet's last state-plane "
                        "commit is older than this (0 = off)")
    # Cluster-scheduler backends (reference P7 ships jsrun/mpirun backends;
    # the TPU equivalents live in runner/tpu_vm.py).
    p.add_argument("--tpu", default=None,
                   help="Launch over a (multi-host) TPU-VM slice: broadcast "
                        "the command to every worker via gcloud tpu-vm ssh")
    p.add_argument("--zone", default=None, help="GCE zone of --tpu")
    p.add_argument("--project", default=None, help="GCP project of --tpu")
    p.add_argument("--gke-jobset", default=None,
                   help="Render a TPU-on-GKE JobSet manifest for this "
                        "command (xpk pattern) instead of launching")
    p.add_argument("--container-image", default=None,
                   help="Container image for --gke-jobset")
    p.add_argument("--gke-num-hosts", type=int, default=None,
                   help="Hosts in the GKE slice (with --gke-jobset)")
    p.add_argument("--gke-accelerator", default=None,
                   help="gke-tpu-accelerator node selector, e.g. "
                        "tpu-v5p-slice / tpu-v5-lite-podslice")
    p.add_argument("--gke-topology", default=None,
                   help="gke-tpu-topology node selector, e.g. 2x2x2 (v4/"
                        "v5p are 3-D) or 4x4 (v5e/v6e)")
    p.add_argument("--gke-chips-per-host", type=int, default=None,
                   help="google.com/tpu resource limit per pod (default 4)")
    p.add_argument("command", nargs=argparse.REMAINDER,
                   help="Training command")
    args = p.parse_args(list(argv))

    if args.config_file:
        _apply_config_file(args)
    if not args.command:
        p.error("no training command given")
    if args.command and args.command[0] == "--":
        args.command = args.command[1:]
    if args.tpu and not args.zone:
        p.error("--tpu requires --zone")
    if (args.tpu or args.gke_jobset) and (args.slots_per_host or 1) > 1:
        # One launched process per host is the TPU-VM/GKE model (the host's
        # local chips are auto-detected by jax); advertising SIZE =
        # hosts*slots while starting one process per host would hang every
        # worker at rendezvous waiting for ranks that never launch.
        p.error("--slots-per-host > 1 is not supported with --tpu/"
                "--gke-jobset: these backends launch ONE process per host "
                "and the process drives all local chips")
    if args.gke_jobset and not (args.container_image and args.gke_num_hosts
                                and args.gke_accelerator
                                and args.gke_topology):
        p.error("--gke-jobset requires --container-image, --gke-num-hosts, "
                "--gke-accelerator and --gke-topology (topologies are "
                "generation-specific; this launcher will not guess)")
    elastic = (args.host_discovery_script is not None
               or args.tpu_metadata_discovery)
    if args.np is None and not elastic and not args.tpu \
            and not args.gke_jobset:
        p.error("-np is required (or elastic --host-discovery-script / "
                "--tpu-metadata-discovery, or a cluster backend "
                "--tpu/--gke-jobset)")
    return args


def _apply_config_file(args: argparse.Namespace):
    """YAML config file mirroring flags (reference: --config-file)."""
    import re

    def parse_scalar(v: str):
        v = v.strip()
        if v.lower() in ("true", "yes"):
            return True
        if v.lower() in ("false", "no"):
            return False
        for cast in (int, float):
            try:
                return cast(v)
            except ValueError:
                pass
        return v

    with open(args.config_file) as fh:
        for line in fh:
            line = line.split("#", 1)[0].strip()
            if not line or ":" not in line:
                continue
            key, val = line.split(":", 1)
            key = key.strip().replace("-", "_")
            if hasattr(args, key) and getattr(args, key) in (None, False):
                setattr(args, key, parse_scalar(val))


def placement(args) -> List[HostSpec]:
    if args.hostfile:
        hosts = parse_hostfile(args.hostfile)
    elif args.hosts:
        hosts = parse_hosts(args.hosts)
    else:
        hosts = [HostSpec("localhost", args.np)]
    total = sum(h.slots for h in hosts)
    if args.np is not None and total < args.np:
        raise ValueError(f"Requested -np {args.np} but hosts provide only "
                         f"{total} slots")
    return hosts


def _free_ports(n: int) -> List[int]:
    from ..common.net import free_ports
    return free_ports(n)


def platform_worker_env(base: Optional[Dict[str, str]] = None) -> Dict[str, str]:
    """Env overrides so user scripts need no platform boilerplate when
    launched on CPU (``JAX_PLATFORMS=cpu`` smoke runs): each worker is ONE
    rank with one CPU device (strip any inherited virtual-device count) and
    cross-process collectives run over gloo.  No-op for TPU workers."""
    base = os.environ if base is None else base
    out: Dict[str, str] = {}
    if base.get("JAX_PLATFORMS", "").startswith("cpu"):
        out["JAX_CPU_COLLECTIVES_IMPLEMENTATION"] = base.get(
            "JAX_CPU_COLLECTIVES_IMPLEMENTATION", "gloo")
        out["XLA_FLAGS"] = " ".join(
            f for f in base.get("XLA_FLAGS", "").split()
            if "xla_force_host_platform_device_count" not in f)
    return out


# PCI ids of TPU chips (vendor Google; the device ids jax's own
# hardware_utils knows).  Read from sysfs, so the launcher can tell a TPU
# host without loading libtpu — the chip belongs to one process at a time
# and the launcher must never be that process.
_GOOGLE_PCI_VENDOR = "0x1ae0"
_TPU_PCI_DEVICES = frozenset(
    ("0x0027", "0x0056", "0x005e", "0x0062", "0x0063", "0x006f", "0x0076"))
# One-chip-per-process grid for a whole host, by chip count: a v5e/v4 host
# of four chips is 2x2; eight chips (v5e-8) are 2x4 by the same published
# recipe, which has not run here.  The host's own
# TPU_CHIPS_PER_HOST_BOUNDS wins where the image sets it.
_HOST_CHIP_BOUNDS = {4: "2,2,1", 8: "2,4,1"}
# What the launcher sets per worker; a launch that already carries any of
# these has bound its chips itself and is left alone.
TPU_BINDING_VARS = ("TPU_VISIBLE_CHIPS", "TPU_VISIBLE_DEVICES",
                    "TPU_CHIPS_PER_PROCESS_BOUNDS", "TPU_PROCESS_BOUNDS",
                    "TPU_PROCESS_ADDRESSES", "TPU_PROCESS_PORT",
                    "CLOUD_TPU_TASK_ID")


def local_tpu_chips(sysfs: str = "/sys/bus/pci/devices") -> int:
    """TPU chips attached to this host (sysfs PCI scan; opens nothing)."""
    import glob
    n = 0
    for vendor in glob.glob(os.path.join(sysfs, "*", "vendor")):
        try:
            with open(vendor) as fh:
                if fh.read().strip() != _GOOGLE_PCI_VENDOR:
                    continue
            with open(os.path.join(os.path.dirname(vendor), "device")) as fh:
                n += fh.read().strip() in _TPU_PCI_DEVICES
        except OSError:
            continue
    return n


def tpu_worker_envs(local_size: int, chips: int,
                    base: Optional[Dict[str, str]] = None
                    ) -> List[Dict[str, str]]:
    """Per-local-rank libtpu variables that give each of ``local_size``
    workers ONE chip of a ``chips``-chip TPU host and let them form one
    device world (one launcher-probed port each for libtpu's own mesh
    service).

    Empty — the launch is untouched — off a TPU host (``chips == 0`` or
    ``JAX_PLATFORMS=cpu``), for a single worker (it drives every local
    chip), and where the caller's environment already binds chips.  A TPU
    launch this cannot bind raises instead of letting N workers queue on
    the chips' lock."""
    base = os.environ if base is None else base
    if (chips == 0 or local_size <= 1
            or base.get("JAX_PLATFORMS", "").startswith("cpu")
            or any(v in base for v in TPU_BINDING_VARS)):
        return []
    bounds = (base.get("TPU_CHIPS_PER_HOST_BOUNDS")
              or _HOST_CHIP_BOUNDS.get(chips))
    if local_size != chips or bounds is None:
        raise ValueError(
            f"torovodrun: {local_size} workers on a TPU host with {chips} "
            f"chips. A TPU host runs one worker per chip (-np {chips}) or "
            f"one worker that drives every chip (-np 1, or "
            f"HOROVOD_ONE_PROC_PER_HOST=1 across hosts); other splits "
            f"are not supported.")
    ports = _free_ports(local_size)
    addresses = ",".join(f"localhost:{p}" for p in ports)
    return [{
        "TPU_VISIBLE_CHIPS": str(r),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": bounds,
        "TPU_PROCESS_ADDRESSES": addresses,
        "TPU_PROCESS_PORT": str(ports[r]),
        "CLOUD_TPU_TASK_ID": str(r),
    } for r in range(local_size)]


def tuning_env(args) -> Dict[str, str]:
    """HOROVOD_* env derived from the launcher's tuning flags — shared by
    every backend (local/ssh here, TPU-VM/GKE in tpu_vm.py) so a knob can
    never work on one launch path and silently vanish on another."""
    env: Dict[str, str] = {}
    for flag, var, scale in (
            ("fusion_threshold_mb", "HOROVOD_FUSION_THRESHOLD", 1024 * 1024),
            ("cycle_time_ms", "HOROVOD_CYCLE_TIME", 1),
            ("cache_capacity", "HOROVOD_CACHE_CAPACITY", 1),
            ("pipeline_chunk_mb", "HOROVOD_PIPELINE_CHUNK", 1024 * 1024),
            ("max_inflight", "HOROVOD_MAX_INFLIGHT", 1),
            ("fast_lane_threshold_kb", "HOROVOD_FAST_LANE_THRESHOLD", 1024),
            ("partition_threshold_mb", "HOROVOD_PARTITION_THRESHOLD",
             1024 * 1024),
            ("spec_ready_after", "HOROVOD_SPEC_READY_AFTER", 1),
            ("round_pipeline", "HOROVOD_ROUND_PIPELINE", 1),
            ("stall_check_time", "HOROVOD_STALL_CHECK_TIME", 1),
            ("stall_shutdown_time", "HOROVOD_STALL_SHUTDOWN_TIME", 1),
            ("monitor_port", "HOROVOD_MONITOR_PORT", 1),
            ("monitor_interval", "HOROVOD_MONITOR_INTERVAL", 1),
            ("trace_ring", "HOROVOD_TRACE_RING", 1),
            ("round_timeout", "HOROVOD_ROUND_TIMEOUT_S", 1),
            ("connect_retries", "HOROVOD_CONNECT_RETRIES", 1),
            ("connect_backoff_ms", "HOROVOD_CONNECT_BACKOFF_MS", 1),
            ("ckpt_chunk_mb", "HOROVOD_CKPT_CHUNK", 1024 * 1024),
            ("ckpt_lane_budget", "HOROVOD_CKPT_LANE_BUDGET", 1),
            ("commit_max_age_s", "HOROVOD_COMMIT_MAX_AGE_S", 1)):
        val = getattr(args, flag, None)
        if val is not None:
            env[var] = str(int(val * scale) if scale != 1 else val)
    if getattr(args, "ckpt_dir", None):
        env["HOROVOD_CKPT_DIR"] = args.ckpt_dir
    if getattr(args, "monitor", False) \
            or getattr(args, "monitor_port", None):
        env["HOROVOD_MONITOR"] = "1"
    if getattr(args, "timeline_mark_cycles", False):
        env["HOROVOD_TIMELINE_MARK_CYCLES"] = "1"
    if getattr(args, "autotune", False):
        env["HOROVOD_AUTOTUNE"] = "1"
        if getattr(args, "autotune_log_file", None):
            env["HOROVOD_AUTOTUNE_LOG"] = args.autotune_log_file
    if getattr(args, "sharded", False):
        env["HOROVOD_SHARDED_OPTIMIZER"] = "1"
    if getattr(args, "sharded_params", False):
        env["HOROVOD_SHARDED_PARAMS"] = "1"
    if getattr(args, "prefetch_depth", None) is not None:
        env["HOROVOD_PREFETCH_DEPTH"] = str(int(args.prefetch_depth))
    if getattr(args, "hierarchical_allreduce", False):
        env["HOROVOD_HIERARCHICAL_ALLREDUCE"] = "1"
    if getattr(args, "hierarchical_allgather", False):
        env["HOROVOD_HIERARCHICAL_ALLGATHER"] = "1"
    if getattr(args, "hierarchical_broadcast", False):
        env["HOROVOD_HIERARCHICAL_BROADCAST"] = "1"
    if getattr(args, "hierarchical_controller", False):
        env["HOROVOD_HIERARCHICAL_CONTROLLER"] = "1"
    # Serving plane (ISSUE 19, docs/serving.md): the flag plus the knob
    # table travel as env so the workers' Config.from_env() sees them on
    # every launch path; per-rank ports are derived worker-side from the
    # base (rank r listens on serve_port + r when a base is given).
    if getattr(args, "serve", False):
        env["HOROVOD_SERVE"] = "1"
    if getattr(args, "serve_port", None) is not None:
        env["HOROVOD_SERVE_PORT"] = str(int(args.serve_port))
    return env


def wait_and_reap(procs: List[subprocess.Popen],
                  poll_interval_s: float = 0.2) -> int:
    """Wait for every worker, propagate the first failure, terminate
    stragglers (shared by the local/ssh and TPU-VM backends).

    Polls ALL workers rather than waiting in list order: the moment any
    worker exits nonzero, the survivors are terminated — one crashed rank
    must not leave the rest of a slice running until their own timeouts
    fire (the reference launcher's safe_shell_exec kills the process
    group the same way).
    """
    rc = 0
    live = list(procs)
    try:
        while live:
            still = []
            for p in live:
                code = p.poll()
                if code is None:
                    still.append(p)
                elif code != 0 and rc == 0:
                    rc = code
            live = still
            if rc != 0:
                break
            if live:
                time.sleep(poll_interval_s)
    finally:
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            if p.poll() is None:
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()
                    p.wait()
    return rc


def worker_envs(args, hosts: List[HostSpec],
                coordinator: Tuple[str, int, int],
                agent_ports: Optional[List[Optional[int]]] = None,
                tpu_chips: Optional[int] = None
                ) -> List[Dict[str, str]]:
    """Compute the per-rank env injection (reference §3.3: HOROVOD_RANK,
    HOROVOD_SIZE, HOROVOD_LOCAL_RANK, HOROVOD_CROSS_RANK, rendezvous addr).

    ``agent_ports`` (hierarchical control plane): one launcher-allocated
    listen port per host for that host's aggregation agent, injected as
    HOROVOD_AGENT_PORT so every process on a host agrees where its agent
    lives.  A None entry means no injection for that host (remote hosts:
    a port bind-probed on the launcher proves nothing there — the
    config-side deterministic fallback derives one instead).

    ``tpu_chips``: TPU chips on the launcher's host (default: detected).
    On a TPU host each local worker is bound to one chip
    (:func:`tpu_worker_envs`); the job must then sit on this host alone,
    because a process grid across hosts needs the slice's layout, which
    only pod mode (one process per host) learns from the runtime."""
    from ..common.net import is_local_host
    np_total = args.np
    tpu_chips = local_tpu_chips() if tpu_chips is None else tpu_chips
    envs = []
    rank = 0
    for cross_rank, h in enumerate(hosts):
        local_size = max(0, min(h.slots, np_total - rank))
        tpu_envs = (tpu_worker_envs(local_size, tpu_chips)
                    if tpu_chips and is_local_host(h.hostname) else [])
        if tpu_envs and len(hosts) > 1:
            raise ValueError(
                "torovodrun: one worker per chip is supported on a single "
                "TPU host only; across hosts run one worker per host "
                "(slots=1, HOROVOD_ONE_PROC_PER_HOST=1), each driving its "
                "host's chips")
        for local_rank in range(h.slots):
            if rank >= np_total:
                break
            env = platform_worker_env()
            if tpu_envs:
                env |= tpu_envs[local_rank]
            env |= {
                "HOROVOD_RANK": str(rank),
                "HOROVOD_SIZE": str(np_total),
                "HOROVOD_LOCAL_RANK": str(local_rank),
                "HOROVOD_LOCAL_SIZE": str(local_size),
                "HOROVOD_CROSS_RANK": str(cross_rank),
                "HOROVOD_CROSS_SIZE": str(len(hosts)),
                "HOROVOD_CONTROLLER_ADDR": coordinator[0],
                "HOROVOD_CONTROLLER_PORT": str(coordinator[1]),
                "HOROVOD_CONTROLLER_PORT2": str(coordinator[2]),
                "HOROVOD_HOSTNAME": h.hostname,
            }
            if agent_ports is not None \
                    and agent_ports[cross_rank] is not None:
                env["HOROVOD_AGENT_PORT"] = str(agent_ports[cross_rank])
            env |= tuning_env(args)
            if args.timeline_filename:
                env["HOROVOD_TIMELINE"] = per_rank_filename(
                    args.timeline_filename, rank)
            if getattr(args, "trace_filename", None):
                env["HOROVOD_TRACE"] = per_rank_filename(
                    args.trace_filename, rank)
            envs.append(env)
            rank += 1
    return envs


def ssh_command(host: str, env: Dict[str, str], command: List[str],
                ssh_port: Optional[int] = None,
                identity_file: Optional[str] = None) -> List[str]:
    """Build the remote spawn command (reference: gloo_run's ssh exec via
    safe_shell_exec; tested by asserting on the generated argv, like
    ``test/single/test_run.py``)."""
    exports = " ".join(f"{k}={shlex.quote(v)}" for k, v in sorted(env.items()))
    remote = f"cd {shlex.quote(os.getcwd())} && env {exports} " + \
        " ".join(shlex.quote(c) for c in command)
    cmd = ["ssh", "-o", "StrictHostKeyChecking=no"]
    if ssh_port:
        cmd += ["-p", str(ssh_port)]
    if identity_file:
        cmd += ["-i", identity_file]
    cmd += [host, remote]
    return cmd


def plan_workers(args, hosts: List[HostSpec],
                 addrs: Optional[Dict[str, str]] = None,
                 tpu_chips: Optional[int] = None) -> List[Dict[str, str]]:
    """The ports of the launch and every worker's environment.

    ``addrs`` (from the bootstrap probe phase) overrides the coordinator
    address with host 0's resolved control-plane address — this is what
    makes ``--network-interface`` actually select the control plane."""
    from ..common.net import is_local_host
    # Hierarchical control plane: one extra port per host for its
    # aggregation agent.  Bind-probed HERE only for local/loopback hosts
    # (the CPU test meshes) — a port free on the launcher proves nothing
    # on a remote host, so remote hosts get NO injection and derive their
    # own via the HOROVOD_AGENT_PORT=0 fallback in common/config.py.
    hier = getattr(args, "hierarchical_controller", False)
    agent_ports = None
    if hier:
        local_hosts = [is_local_host(h.hostname) for h in hosts]
        probed = iter(_free_ports(2 + sum(local_hosts)))
        ports = [next(probed), next(probed)]
        agent_ports = [next(probed) if loc else None for loc in local_hosts]
    else:
        ports = _free_ports(2)
    if addrs:
        coord_host = addrs[hosts[0].hostname]
    else:
        coord_host = (hosts[0].hostname if hosts[0].hostname != "localhost"
                      else "127.0.0.1")
    coord = (coord_host, ports[0], ports[1])
    return worker_envs(args, hosts, coord, agent_ports=agent_ports,
                       tpu_chips=tpu_chips)


def spawn_workers(args, envs: List[Dict[str, str]]
                  ) -> List[subprocess.Popen]:
    """One process a worker environment (local, or over ssh)."""
    procs: List[subprocess.Popen] = []
    for rank, env in enumerate(envs):
        host = env["HOROVOD_HOSTNAME"]
        full_env = {**os.environ, **env}
        stdout = stderr = None
        if args.output_filename:
            d = os.path.join(args.output_filename, f"rank.{rank}")
            os.makedirs(d, exist_ok=True)
            stdout = open(os.path.join(d, "stdout"), "w")
            stderr = open(os.path.join(d, "stderr"), "w")
        if host in ("localhost", "127.0.0.1", socket.gethostname()):
            proc = subprocess.Popen(args.command, env=full_env,
                                    stdout=stdout, stderr=stderr)
        else:
            cmd = ssh_command(host, env, args.command, args.ssh_port,
                              args.ssh_identity_file)
            proc = subprocess.Popen(cmd, env=os.environ.copy(),
                                    stdout=stdout, stderr=stderr)
        procs.append(proc)
    return procs


def main(argv: Sequence[str]) -> int:
    entered = time.time()
    args = parse_args(argv)
    if args.gke_jobset:
        from .tpu_vm import render_gke_jobset
        sys.stdout.write(render_gke_jobset(args, args.gke_num_hosts))
        return 0
    if args.tpu:
        from .tpu_vm import run_tpu_vm
        return run_tpu_vm(args)
    if (args.host_discovery_script is not None
            or getattr(args, "tpu_metadata_discovery", False)):
        from ..elastic.driver import run_elastic
        return run_elastic(args)
    # The launcher's part of the start-up record (trace/core.py): from
    # here to the last worker spawned, on the clock its workers share.
    from ..common import compile_cache
    from ..trace import core as trace
    with trace.StartupSpan("hvd/launch", {"np": args.np}, entered) as whole:
        with trace.StartupSpan("hvd/launch/placement", {}, entered) as sp:
            hosts = placement(args)
            whole.set(hosts=len(hosts))
            if args.verbose:
                print(f"[torovodrun] launching np={args.np} over "
                      f"{[(h.hostname, h.slots) for h in hosts]}",
                      file=sys.stderr)
            # Pre-launch bootstrap (reference P8): probe NICs + mutual
            # connectivity whenever a host is remote or an explicit
            # interface was requested — refuse fast with the exact broken
            # pair instead of spawning workers that would hang in
            # rendezvous.
            addrs = None
            from ..common.net import is_local_host
            if args.nics or any(not is_local_host(h.hostname)
                                for h in hosts):
                from .bootstrap import bootstrap_hosts
                try:
                    addrs = bootstrap_hosts(
                        hosts, nic=args.nics, ssh_port=args.ssh_port,
                        identity_file=args.ssh_identity_file,
                        timeout_s=min(args.start_timeout, 120),
                        verbose=args.verbose)
                except RuntimeError as exc:
                    print(f"[torovodrun] {exc}", file=sys.stderr)
                    return 1
            chips = local_tpu_chips()
            sp.set(chips=chips)
            envs = plan_workers(args, hosts, addrs, tpu_chips=chips)
        with trace.startup_span("hvd/launch/spawn", n=len(envs)):
            procs = spawn_workers(args, envs)
    trace.startup_identity(role="launcher", world=len(envs))
    compile_cache.place_process_file(
        accelerator=bool(chips) and not os.environ.get(
            "JAX_PLATFORMS", "").startswith("cpu"))
    rc = wait_and_reap(procs)
    trace.write_startup()       # its line, once the last worker has gone
    return rc
