"""Core runtime state and the ``init``/``rank``/``size`` API family.

TPU-native equivalent of the reference's Python core
(``horovod/common/basics.py`` ``HorovodBasics`` — SURVEY.md §2b P1) fused with
the C++ ``InitializeHorovodOnce`` bootstrap (``horovod/common/operations.cc``
— SURVEY.md §2a N1).  Where the reference ctypes into a C++ global state, we
keep a Python-side ``GlobalState`` that owns the topology, process-set table,
config, timeline and the collective engine; the native TCP controller (multi-
process mode) is attached underneath when launched by ``torovodrun``.

Rank model (see ``topology.py``): a rank is a device.  In multi-process
launches (one process per device, or one per host) ``rank()`` returns this
process's first device's global rank, matching Horovod's process-rank
semantics; in single-process SPMD mode ``rank()`` is 0 and per-rank identity
lives inside ``shard_map`` (``ops.axis_rank``).
"""

from __future__ import annotations

import atexit
import contextlib
import threading
from typing import List, Optional, Sequence

import jax

from ..trace import core as _trace
from .config import Config
from .process_sets import ProcessSet, ProcessSetTable, global_process_set
from .topology import Topology, build_topology


class NotInitializedError(RuntimeError):
    def __init__(self):
        super().__init__("horovod_tpu has not been initialized; call hvd.init() first.")


def _env_has_rendezvous() -> bool:
    import os
    return bool(os.environ.get("HOROVOD_RENDEZVOUS_ADDR"))





class GlobalState:
    def __init__(self):
        self.initialized = False
        self.config: Optional[Config] = None
        self.topology: Optional[Topology] = None
        self.process_set_table = ProcessSetTable()
        self.engine = None          # ops.engine.CollectiveEngine
        self.timeline = None        # utils.timeline.Timeline
        self.controller = None      # multi-process TCP controller client
        self.host_agent = None      # common.host_agent.HostAgent (v5, owned
                                    # by the local_rank-0 process per host)
        self.monitor = None         # monitor.MonitorAgent (HOROVOD_MONITOR)
        self._lock = threading.Lock()


_state = GlobalState()

# Elastic carryover across init/shutdown cycles within ONE worker process
# (ISSUE 12): a re-rendezvous tears the runtime down and re-forms it, but
# some state is keyed on the HOST/process, not the generation — the
# per-host agent object (held on GlobalState across shutdowns so its
# listen port survives) and the zero-RTT engagement hint captured from the
# dying generation's controller (seeds the next generation's server slot
# streaks and client consumption gate, so warm speculation re-engages in
# O(1) rounds instead of relearning from zero).
_elastic_carry = {"spec_seed": 0}


def _get_state() -> GlobalState:
    return _state


def init(process_sets: Optional[Sequence[ProcessSet]] = None,
         devices=None,
         axis_name: str = "hvd") -> None:
    """Initialize the runtime.  Idempotent, like ``hvd.init()``.

    Equivalent call stack in the reference: SURVEY.md §3.1 — env parsing,
    controller selection, background thread spawn.  Here: parse config,
    build the device topology/mesh, register process sets, start the
    collective engine (cycle thread + fusion + cache), connect to the
    launcher's controller when running multi-process.
    """
    st = _state
    with st._lock:
        if st.initialized:
            return
        # The start-up record (trace/core.py): ``hvd/init`` and a span a
        # phase below it, kept whether tracing is armed or not.
        _trace.startup_freeze(False)
        with _trace.startup_span("hvd/init") as whole:
            _init_locked(st, process_sets, devices, axis_name, whole)
        st.initialized = True


def _init_locked(st, process_sets, devices, axis_name, whole) -> None:
    """``init()`` under the state's lock and the ``hvd/init`` span."""
    span = _trace.startup_span
    with span("hvd/init/config") as sp:
        st.config = Config.from_env()

        # Elastic workers fetch rank/size/coordinator from the driver's
        # versioned rendezvous instead of static env (SURVEY.md §3.4).
        if st.config.elastic and _env_has_rendezvous():
            from ..elastic.worker import elastic_bootstrap
            st.config = elastic_bootstrap()
        sp.set(elastic=int(st.config.elastic))

    # Multi-process bootstrap (launched by torovodrun, SURVEY.md §3.3):
    # jax.distributed forms the global device world at controller_port;
    # the native negotiation controller lives at controller_port + 1.
    cfg = st.config
    multi_process = (cfg.controller_addr != ""
                     and (cfg.size_env > 1 or cfg.elastic))
    # NB: must not touch jax.devices()/process_count() before
    # jax.distributed.initialize — any backend query finalizes the
    # single-process world.
    from jax._src import distributed as _jdist
    with span("hvd/init/distributed", processes=(
            cfg.size_env if multi_process else 1)):
        if _jdist.global_state.client is None:
            # torovodrun spawns one process per rank (reference §3.3) and
            # provides the coordinator; in pod mode
            # (HOROVOD_ONE_PROC_PER_HOST) each process drives ALL its
            # local devices — the process world still forms at the
            # launcher's coordinator when one is given (rank/size env are
            # PROCESS values there), and falls back to TPU-metadata
            # auto-detection without one (SPMD-only: the eager engine's
            # negotiation controller needs a launcher; enqueue guards it).
            if multi_process and cfg.elastic:
                # Elastic worlds neutralize the XLA coordination service's
                # own failure detector (it can only abort survivors; our
                # control plane detects dead peers in ms and the driver
                # owns recovery) so a post-fault teardown can park the
                # poisoned world instead of dying in its shutdown barrier.
                from ..elastic.worker import init_distributed_resilient
                init_distributed_resilient(
                    f"{cfg.controller_addr}:{cfg.controller_port}",
                    num_processes=cfg.size_env, process_id=cfg.rank_env)
            elif multi_process:
                jax.distributed.initialize(
                    coordinator_address=(
                        f"{cfg.controller_addr}:{cfg.controller_port}"),
                    num_processes=cfg.size_env,
                    process_id=cfg.rank_env,
                )
            elif cfg.one_proc_per_host and not cfg.controller_addr:
                jax.distributed.initialize()

    from jax._src import xla_bridge
    # ``fresh`` 0: the script asked jax for its devices before
    # ``hvd.init()`` and the client opened there, not here
    with span("hvd/init/backend",
              fresh=int(not xla_bridge.backends_are_initialized())) as sp:
        st.topology = build_topology(axis_name=axis_name, devices=devices)
        platform = st.topology.devices[0].platform
        sp.set(platform=platform, devices=len(st.topology.devices))
    if multi_process and not cfg.one_proc_per_host:
        mine = st.topology.ranks_of_process(st.topology.my_process)
        if len(mine) == 1 and mine[0] != cfg.rank_env:
            # On a TPU host the runtime numbers processes by where
            # their chips sit, and the world mesh is in ICI order, so
            # the launcher's HOROVOD_RANK (which only numbered the
            # rendezvous) need not be this process's place in the
            # mesh.  Eager collectives put a contribution at the
            # device's mesh index, so that index IS the rank: adopt it
            # before the controller and the engine are built.  Never
            # fires on the CPU, where process i owns device i.
            import dataclasses
            local = (mine[0] if cfg.cross_size_env <= 1
                     else cfg.local_rank_env)
            cfg = st.config = dataclasses.replace(
                cfg, rank_env=mine[0], local_rank_env=local)
    with span("hvd/init/cache") as sp:
        from . import compile_cache
        before = jax.config.jax_compilation_cache_dir
        if platform != "cpu":
            # Accelerator compiles are long (a ResNet-50 step ~45 s): keep
            # them across processes and runs.  CPU runs (tests) stay as
            # they were unless the environment places a cache itself.
            compile_cache.enable()
        compile_cache.place_process_file(accelerator=platform != "cpu")
        compile_cache.register_ledger()
        now = jax.config.jax_compilation_cache_dir
        sp.set(dir=now or "", reset=int(now != before))
    _trace.startup_identity(
        role="rank" if multi_process else "single", platform=platform,
        rank=max(cfg.rank_env, 0), world=len(st.topology.devices))
    whole.set(world=len(st.topology.devices), rank=max(cfg.rank_env, 0),
              multi_process=int(multi_process))
    # the runtime's own objects: process sets, timeline, the engine (its
    # threads start last, below: two intervals, one name)
    with span("hvd/init/engine"):
        gs = st.process_set_table.initialize(
            st.topology.devices, axis_name, extra_sets=process_sets)
        # Rebind the module-level global_process_set singleton.
        global_process_set.__dict__.update(gs.__dict__)
        st.process_set_table._sets[0] = global_process_set

        from ..utils.timeline import Timeline
        st.timeline = Timeline(cfg.timeline_filename,
                               mark_cycles=cfg.timeline_mark_cycles)

        # Wire-visible auto-name counters must restart with the runtime so
        # elastic re-inits leave every rank's name sequence aligned.
        from ..ops import eager as _eager
        _eager.reset_name_counters()

        from ..ops.engine import CollectiveEngine
        st.engine = CollectiveEngine(st)
    if multi_process:
        from . import native
        native.load()       # ``hvd/init/native``, before its first user
        with span("hvd/init/controller") as sp:
            hier = _connect_controller(st, cfg)
            sp.set(attempts=st.controller.connect_attempts, hier=int(hier))

    if cfg.monitor:
        with span("hvd/init/monitor"):
            _install_monitor(st, cfg, multi_process)
    with span("hvd/init/engine"):
        st.engine.start()


def _connect_controller(st, cfg) -> bool:
    """The negotiation controller of a launched process (and its host's
    agent where the control plane is two-level); returns whether it is."""
    from .controller import TCPController
    ctrl_port = (cfg.controller_port2 if cfg.controller_port2
                 else cfg.controller_port + 1)
    connect_addr, connect_port = cfg.controller_addr, ctrl_port
    server_port = None
    hier = cfg.hierarchical_controller
    if hier and (cfg.local_rank_env < 0 or cfg.local_size_env <= 0
                 or cfg.cross_rank_env < 0):
        # Manual launches may set only RANK/SIZE/CONTROLLER_ADDR
        # (enough for flat mode).  Deriving a host topology from
        # the -1 defaults would give every process local_rank 0 on
        # cross_rank 0 — each trying to bind its own agent on ONE
        # derived port (EADDRINUSE out of init()).  Fall back to
        # the flat plane loudly instead.
        from ..utils.logging import get_logger
        get_logger().warning(
            "HOROVOD_HIERARCHICAL_CONTROLLER=1 but HOROVOD_"
            "LOCAL_RANK/LOCAL_SIZE/CROSS_RANK are not set (launch "
            "through torovodrun to get them); using the flat "
            "control plane")
        hier = False
    if hier:
        # Two-level control plane (protocol v5): ranks talk to a
        # per-host agent that presents the whole host to the root
        # as ONE connection (common/host_agent.py).  The
        # local_rank-0 process owns its host's agent; rank 0 still
        # hosts the root server at the launcher-advertised port
        # while its own client goes through host 0's agent like
        # everyone else's.  Elastic worlds compose (ISSUE 12): the
        # agent object SURVIVES re-rendezvous generations — keyed
        # on the host, listening on the stable per-host port the
        # elastic driver allocated (HOROVOD_AGENT_PORT via the
        # rendezvous assignment) — and each generation re-forms
        # its uplink/local connections via new_generation.
        from .host_agent import HostAgent
        local_rank = cfg.local_rank_env
        local_size = cfg.local_size_env
        cross_rank = cfg.cross_rank_env
        agent_port = (cfg.agent_port
                      or ctrl_port + 1 + cross_rank)
        if local_rank == 0:
            first = cfg.rank_env - local_rank
            ranks = list(range(first,
                               min(cfg.size_env,
                                   first + local_size)))
            reused = False
            if (st.host_agent is not None and cfg.elastic
                    and st.host_agent.port == agent_port):
                try:
                    st.host_agent.new_generation(
                        cfg.controller_addr, ctrl_port, ranks,
                        host_index=cross_rank)
                    reused = True
                except RuntimeError:
                    # A wedged previous-generation thread: fall
                    # back to a fresh agent on the same port
                    # (stop() closes the listener first).
                    from ..utils.logging import get_logger
                    get_logger().warning(
                        "host agent could not serve a new "
                        "generation; replacing it")
            if not reused:
                if st.host_agent is not None:
                    st.host_agent.stop()
                st.host_agent = HostAgent(
                    agent_port, cfg.controller_addr, ctrl_port,
                    ranks, host_index=cross_rank).start()
        connect_addr, connect_port = "127.0.0.1", agent_port
        if cfg.rank_env == 0:
            server_port = ctrl_port
    # Zero-RTT streak carryover (ISSUE 12): a surviving elastic
    # worker seeds the new generation from the hint captured at
    # the previous shutdown — 0 on the first generation and in
    # non-elastic worlds.
    spec_carry = _elastic_carry["spec_seed"] if cfg.elastic else 0
    st.controller = TCPController(
        connect_addr, connect_port,
        rank=cfg.rank_env, world=cfg.size_env,
        stall_warn_s=cfg.stall_check_time_s
        if not cfg.stall_check_disable else 1e18,
        cache_capacity=cfg.response_cache_capacity,
        round_timeout_s=cfg.round_timeout_s,
        connect_retries=cfg.connect_retries,
        connect_backoff_ms=cfg.connect_backoff_ms,
        server_port=server_port,
        spec_ready_after=cfg.spec_ready_after,
        round_pipeline=cfg.round_pipeline,
        spec_seed=spec_carry,
        spec_streak_hint=spec_carry)
    st.engine.controller = st.controller
    return hier


def _install_monitor(st, cfg, multi_process) -> None:
    # Cross-rank telemetry & health subsystem (docs/monitoring.md):
    # per-rank registry + coordinator side-channel aggregation; the
    # HTTP exporter serves /metrics + /health on rank 0 when a
    # port is configured.  Installed before engine.start() so the
    # very first cycle is observed.
    from ..monitor.agent import MonitorAgent
    mon_rank = cfg.rank_env if cfg.rank_env >= 0 else 0
    mon_world = cfg.size_env if (multi_process
                                 and cfg.size_env > 0) else 1
    st.monitor = MonitorAgent(
        engine=st.engine, controller=st.controller,
        rank=mon_rank, world=mon_world,
        interval_s=cfg.monitor_interval_s, timeline=st.timeline)
    if cfg.monitor_port > 0 and mon_rank == 0:
        try:
            st.monitor.serve_http(cfg.monitor_port)
        except OSError as exc:
            # A taken port must not kill training — the telemetry
            # plane is strictly best-effort.
            from ..utils.logging import get_logger
            get_logger().warning(
                "monitor: could not bind HTTP port %d (%s); "
                "exporter disabled", cfg.monitor_port, exc)


def shutdown() -> None:
    st = _state
    with st._lock:
        if not st.initialized:
            return
        # The start-up record stands as it is now, and its line goes
        # beside the compile cache (once a process; nowhere on a CPU run
        # by itself).
        _trace.startup_freeze(True)
        _trace.write_startup()
        # A control-plane fault (dead peer — HVD303) means the jax world's
        # cooperative teardown can never complete: take the abrupt path
        # below.  Captured before the engine is torn down.
        abrupt = (st.engine is not None
                  and getattr(st.engine, "fault", None) is not None)
        # Peers that departed via clean LEAVE (protocol v6): not a fault,
        # but the cooperative jax teardown barrier can no longer complete
        # either — the survivors must park, exactly like the fault path,
        # just without the HVD303 noise.
        peers_left = bool(getattr(st.controller, "left_ranks", None)) \
            if st.controller is not None else False
        leave_sent = False
        if st.controller is not None and st.engine is not None \
                and not abrupt:
            # Clean departure (protocol v6): quiesce the cycle thread at a
            # round boundary — the in-flight lock-step round completes in
            # a healthy world — then announce the LEAVE on the quiet
            # socket BEFORE the sever, so the coordinator drops this rank
            # from the gather instead of survivors eating a dead-peer
            # verdict.  A wedged thread (a peer already died) falls back
            # to the legacy interrupt-and-sever below; a pre-v6 server
            # makes leave() a no-op.
            if st.engine.quiesce(timeout=5.0) and \
                    getattr(st.engine, "fault", None) is None:
                leave_sent = st.controller.leave()
            else:
                abrupt = abrupt or (
                    getattr(st.engine, "fault", None) is not None)
        if st.controller is not None:
            # Unblock any lock-step round FIRST so the engine thread can't
            # be left inside the native client when we free it.
            st.controller.interrupt()
        if st.engine is not None:
            st.engine.stop()
            st.engine = None
        if st.monitor is not None:
            st.monitor.close()
            st.monitor = None
        elastic_world = (st.config is not None and st.config.elastic
                         and st.config.controller_addr != "")
        if st.controller is not None:
            # Zero-RTT streak carryover (ISSUE 12): capture the dying
            # generation's engagement hint before the controller goes
            # away, so a survivor's re-init re-engages speculation in
            # O(1) rounds.  A faulted generation carries nothing — and
            # must also CLEAR any older hint, or a stale seed from two
            # generations back would leak past the instability that just
            # killed this one.
            if elastic_world:
                if abrupt:
                    _elastic_carry["spec_seed"] = 0
                else:
                    try:
                        _elastic_carry["spec_seed"] = \
                            st.controller.spec_carry_hint()
                    except Exception:  # noqa: BLE001 - telemetry only
                        _elastic_carry["spec_seed"] = 0
            st.controller.shutdown()
            st.controller = None
        if st.host_agent is not None:
            # After the controller: the agent must outlive this process's
            # own client socket so its teardown EOF is observed (and
            # reported upstream) rather than racing a dead agent thread.
            # Elastic worlds only END the generation (ISSUE 12): the agent
            # object — and its stable listen port — survives for the next
            # re-rendezvous generation's new_generation.
            if elastic_world:
                st.host_agent.end_generation()
            else:
                st.host_agent.stop()
                st.host_agent = None
        if st.timeline is not None:
            st.timeline.close()
            st.timeline = None
        # Elastic resets must fully tear down the JAX world so the next
        # init() can re-form it with a different size (mesh invalidation —
        # SURVEY.md §7 hard-part #3).
        if (st.config is not None and st.config.elastic
                and st.config.controller_addr != ""):
            from ..elastic.worker import (exit_guard_note_clean_shutdown,
                                          teardown_distributed)
            # A clean LEAVE — ours (leave_sent: the peers are NOT shutting
            # down, so the cooperative barrier would hang waiting for
            # them) or a peer's (peers_left: the departed rank will never
            # join it) — parks the world like the fault path; only a
            # full-world synchronized shutdown can take the graceful
            # barrier.
            teardown_distributed(abrupt=abrupt or leave_sent or peers_left)
            if not abrupt:
                # A non-abrupt explicit shutdown means the run completed:
                # any exit code latched by a caught-and-recovered
                # sys.exit() is stale.  Clean leaves count — the departure
                # was orderly.
                exit_guard_note_clean_shutdown()
        st.initialized = False
        st.topology = None


atexit.register(shutdown)
# a process that never initialised (the launcher) or never shut down
atexit.register(_trace.write_startup)


def is_initialized() -> bool:
    return _state.initialized


def _topo() -> Topology:
    if not _state.initialized or _state.topology is None:
        raise NotInitializedError()
    return _state.topology


def _cfg() -> Config:
    cfg = _state.config
    assert cfg is not None
    return cfg


def size() -> int:
    """Global number of ranks (devices), like ``hvd.size()``."""
    return _topo().size


def rank() -> int:
    """This process's rank.

    Launcher-provided HOROVOD_RANK wins (one-process-per-device launches);
    otherwise the global rank of this process's first local device.  In
    pod mode (HOROVOD_ONE_PROC_PER_HOST) the env value describes the
    PROCESS world for the control plane, not the device world — rank() is
    always topology-derived there so ``dataset.shard(size(), rank())``
    stays consistent with size() on multi-chip hosts.
    """
    t = _topo()
    cfg = _cfg()
    if cfg.rank_env >= 0 and not cfg.one_proc_per_host:
        return cfg.rank_env
    mine = t.ranks_of_process(t.my_process)
    return mine[0] if mine else 0


def local_size() -> int:
    cfg = _cfg()
    if cfg.local_size_env > 0 and not cfg.one_proc_per_host:
        return cfg.local_size_env
    return _topo().local_size


def local_rank() -> int:
    """Rank of this process's first device within its host.

    Launcher-provided HOROVOD_LOCAL_RANK wins (it knows host boundaries
    even when several single-device processes share one physical host);
    otherwise — and always in pod mode — derived from the device topology.
    """
    cfg = _cfg()
    if cfg.local_rank_env >= 0 and not cfg.one_proc_per_host:
        return cfg.local_rank_env
    t = _topo()
    mine = t.ranks_of_process(t.my_process)
    if not mine:
        return 0
    return t.local_rank_of[mine[0]]


def cross_size() -> int:
    """Number of hosts, like ``hvd.cross_size()``."""
    env = _cfg().cross_size_env
    return env if env > 0 else _topo().num_processes


def cross_rank() -> int:
    env = _cfg().cross_rank_env
    return env if env >= 0 else _topo().my_process


def mesh():
    """The global 1-D world mesh (axis name = ``hvd``)."""
    return _topo().mesh


def is_homogeneous() -> bool:
    t = _topo()
    return all(c == t.local_counts[0] for c in t.local_counts)


def add_process_set(ps_or_ranks) -> ProcessSet:
    st = _state
    if not st.initialized:
        raise NotInitializedError()
    ps = ps_or_ranks if isinstance(ps_or_ranks, ProcessSet) else ProcessSet(ps_or_ranks)
    assert st.topology is not None and st.config is not None
    return st.process_set_table.add(ps, st.topology.devices, st.config.mesh_axis_name)


def remove_process_set(ps: ProcessSet):
    if not _state.initialized:
        raise NotInitializedError()
    _state.process_set_table.remove(ps)


def process_set_included(ps: ProcessSet) -> bool:
    return ps.included(rank())


# Capability probes, for API parity with HorovodBasics (reference
# horovod/common/basics.py: nccl_built/mpi_enabled/...).  On TPU the data
# plane is always XLA collectives, so these report the analogous truths.
def xla_built() -> bool:
    return True


def nccl_built() -> bool:
    return False


def mpi_enabled() -> bool:
    return False


def gloo_enabled() -> bool:
    return False


def mpi_threads_supported() -> bool:
    return False


def cuda_built() -> bool:
    return False


def rocm_built() -> bool:
    return False


def tpu_available() -> bool:
    return any(d.platform == "tpu" for d in jax.devices())


def start_timeline(filename: str, mark_cycles: bool = False):
    """Begin writing a Chrome-trace timeline (reference: timeline.cc N10)."""
    st = _state
    if not st.initialized:
        raise NotInitializedError()
    from ..utils.timeline import Timeline
    if st.timeline is not None:
        st.timeline.close()
    st.timeline = Timeline(filename, mark_cycles=mark_cycles)


def stop_timeline():
    st = _state
    if not st.initialized:
        raise NotInitializedError()
    if st.timeline is not None:
        st.timeline.close()
    from ..utils.timeline import Timeline
    st.timeline = Timeline("", mark_cycles=False)


def start_profile(logdir: str):
    """Start a device-level profiler trace (XProf/TensorBoard format).

    The coordinator's own Chrome-trace timeline (``start_timeline``, the
    reference's N10) covers NEGOTIATE/XLA phases per tensor; this is the
    complementary device view SURVEY.md §5 calls for — XLA op timing, HBM
    traffic, ICI collectives — via ``jax.profiler``.  View with
    ``tensorboard --logdir`` or Perfetto.  One trace at a time.
    """
    jax.profiler.start_trace(logdir)


def stop_profile():
    """Stop the trace started by :func:`start_profile` and flush it."""
    jax.profiler.stop_trace()


@contextlib.contextmanager
def profile_step(logdir: str):
    """Context manager profiling one region (e.g. a train step)::

        with hvd.profile_step("/tmp/prof"):
            params, opt_state, loss = train_step(...)
    """
    start_profile(logdir)
    try:
        yield
    finally:
        stop_profile()
