"""One process of one run: set up, drive the first steps, warm up, measure,
run the reference; ``run.py`` compares and reports.  It calls ``measure``
in its own process for an ``inproc`` mix and in every launched worker for a
``torovodrun`` mix.

How a step is timed.  The batch is fixed and on the device.  After
enqueuing step k the loop blocks on the loss of step k-1 (one step of
run-ahead, as a loop that logs its loss has), and stamps the host clock: a
step's time is the interval between successive completions.  The window
opens at the completion of the last warm-up step, with the pipeline
running, and closes at the last completion inside ``--seconds``.
"""

from __future__ import annotations

import glob
import hashlib
import json
import math
import os
import statistics
import sys
import time

from . import cell as cells

FIRST_STEPS = 3


class Compiles:
    """Backend-compile seconds and persistent-cache hits and misses, from
    ``jax.monitoring`` (``chip_smoke.py``'s listeners)."""

    def __init__(self):
        import jax.monitoring as mon
        self.seconds, self.count, self.hits, self.misses = 0.0, 0, 0, 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.count += 1

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return {"compile_s": self.seconds, "compiles": self.count,
                "cache_hits": self.hits, "cache_misses": self.misses}


def seed_key(seed):
    """A key for any whole-number seed, past 32 signed bits too."""
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed % (1 << 31)),
                              seed >> 31)


def digest(params):
    """sha256 over the parameters' bytes, leaves in tree order."""
    import jax
    import numpy as np
    h = hashlib.sha256()
    for x in jax.tree_util.tree_leaves(params):
        h.update(np.asarray(x).tobytes())
    return h.hexdigest()[:16]


def start(cell, args):
    """jax, the device check, ``hvd.init()``.  Returns (jax, hvd, device,
    compiles, seconds since the epoch when the world had formed)."""
    launched = cell.mix["launch"] == "torovodrun"
    if args.trace and cell.mix["step_mode"] == "eager":
        os.environ["HOROVOD_TRACE"] = "1"       # in-memory engine spans
    import jax
    if args.rehearse:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_enable_compilation_cache", False)
    # every program of the run goes to the persistent cache, the ~160 small
    # ones of an eager step too: set-up is then the same from run to run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    import horovod_tpu as hvd
    compiles = Compiles()
    if launched:
        hvd.init()      # the process world forms before jax is asked
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if not args.rehearse and (dev.platform != "tpu"
                              or device["count"] < cell.chips):
        sys.exit(f"benchmark: {cell.name} needs {cell.chips} TPU chip(s); "
                 f"jax reports {device}")
    hvd.init()
    formed = time.time()
    if hvd.size() != cell.world:
        sys.exit(f"benchmark: {cell.name} is a world of {cell.world}, "
                 f"hvd.size() is {hvd.size()}")
    return jax, hvd, device, compiles, formed


def engine_counters():
    """The eager engine's own counts and recorder totals, where this
    process has an engine."""
    from horovod_tpu.ops import eager
    try:
        eng = eager._engine()
    except Exception:
        return None
    out = {"cycle_count": eng.cycle_count,
           "pipeline_dispatches": eng.pipeline_dispatches,
           "fast_lane_dispatches": eng.fast_lane_dispatches}
    if eng.tracer is not None:
        out["phase_us"] = {p: v[1] for p, v in
                           eng.tracer.phase_histograms().items()}
        out["spans"] = eng.tracer.spans_committed
    return out


def delta(after, before):
    if after is None or before is None:
        return None
    out = {}
    for k, v in after.items():
        out[k] = ({p: v[p] - before[k].get(p, 0.0) for p in v}
                  if isinstance(v, dict) else v - before[k])
    return out


class Loop:
    """The timed loop: one step of run-ahead, a stamp per completion."""

    def __init__(self, step, state, batch):
        self.step, self.state, self.batch = step, state, batch
        self.pending, self.enqueued = None, 0
        self.stamps, self.losses, self.failed = [], [], 0

    def advance(self):
        """Enqueue one step, then wait for the one before it."""
        self.state, loss = self.step(self.state, self.batch)
        self.enqueued += 1
        if self.pending is not None:
            self._complete()
        self.pending = loss

    def _complete(self):
        value = float(self.pending)
        self.stamps.append(time.perf_counter())
        self.losses.append(value)
        self.failed += not math.isfinite(value)
        self.pending = None

    def drain(self):
        if self.pending is not None:
            self._complete()


def quantile(values, q):
    """The q-quantile by linear interpolation between order statistics."""
    xs = sorted(values)
    pos = q * (len(xs) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def drive_first_steps(jax, built, loop):
    """The timed object's first steps from the seed, through the window's
    own call and batch: each step's loss, the norms of the first gradient
    as the optimizer got it, and of the parameters' change."""
    from .reference.common import leaf_norms
    losses = []
    for i in range(FIRST_STEPS):
        loop.advance()
        loop.drain()
        losses.append(loop.losses[-1])
        if i == 0:
            grad_norms = leaf_norms(built["first_gradient_of"](loop.state))
    seed_params = built["seed_params"]()
    delta_norms = leaf_norms(built["params_of"](loop.state),
                             minus=seed_params)
    return {"first_losses": losses, "grad_norms": grad_norms,
            "delta_norms": delta_norms, "seed_digest": digest(seed_params)}


def measure(cell, args, t_start):
    """Returns this rank's record; ``run.py`` compares and prints."""
    marks = {}                          # seconds since the start, by stage

    def mark(stage):
        marks[stage] = time.time() - t_start

    mark("process")
    jax, hvd, device, compiles, formed = start(cell, args)
    mark("world")
    family = cells.load_module("families", cell.family)
    key = seed_key(args.seed)
    annotate = jax.profiler.TraceAnnotation
    built = family.build(hvd, cell, key, annotate)
    rank = hvd.rank()

    mark("built")
    loop = Loop(built["step"], built.pop("state"), built["batch"])
    first = drive_first_steps(jax, built, loop)
    mark("first_steps")

    # ---- warm-up, then the window, without draining between them
    warm = cell.sizes["warmup_steps"]
    for _ in range(warm):
        loop.advance()
    loop.drain()
    # Ranks that wait for each other have to enqueue the same number of
    # steps, so they agree on one beforehand: enough to fill --seconds at
    # the pace of the warm-up's fastest step (its first ones are slow) and
    # some more.  The window still closes at the last completion inside
    # --seconds.  One rank alone goes by its clock.
    budget = None
    if cell.world > 1:
        recent = loop.stamps[-1 - warm:-1]   # the drain's stamp is no step
        pace = min(b - a for a, b in zip(recent, recent[1:]))
        budget = max(hvd.allgather_object(
            int(math.ceil(args.seconds * 1.15 / pace)) + 2))
    loop.advance()                      # refill the pipeline ...
    loop.advance()                      # ... and complete one more
    at_setup = compiles.snapshot()
    counters0 = engine_counters()
    n0, e0 = len(loop.stamps), loop.enqueued
    t0 = loop.stamps[-1]
    setup_s = time.time() - t_start - (time.perf_counter() - t0)
    failed_before = loop.failed

    def run_until(share):
        """Advance to ``share`` of the window: of --seconds, or of the
        agreed number of steps."""
        if budget is None:
            while loop.stamps[-1] - t0 < args.seconds * share:
                loop.advance()
        else:
            while loop.enqueued - e0 < budget * share:
                loop.advance()

    traced, error = None, None
    try:
        if args.trace:
            run_until(1 / 3)
            traced = trace_steps(jax, cell, loop, record_it=rank == 0)
        run_until(1)
    except Exception as e:              # a step that raised has failed
        error = f"{type(e).__name__}: {e}"
        loop.pending = None
    stamps = [t0] + [t for t in loop.stamps[n0:] if t - t0 <= args.seconds]
    counters = delta(engine_counters(), counters0)
    if error is None:
        loop.drain()
    in_window = compiles.snapshot()
    steps = len(stamps) - 1
    window_s = stamps[-1] - stamps[0]
    # every completion interval of the window, one step each.  A step is
    # shorter than the host clock reads to a per cent (47 ms against half
    # a millisecond), so these are printed and kept beside the run
    # (benchmark/out/<cell>/steps-rank<r>.json) and are no metric: the
    # rate over the whole window is, and every stall is in it.  (In a
    # traced run the profiler's start and stop are inside two of them.)
    step_ms = [(b - a) * 1e3 for a, b in zip(stamps, stamps[1:])]
    stats = jax.local_devices()[0].memory_stats() or {}
    record = {
        "rank": rank, "device": device, "world_formed_at": formed,
        "setup_s": setup_s, "setup_marks": marks, "steps": steps,
        "window_s": window_s,
        "counted_steps": loop.enqueued - e0,    # what the counters cover
        "failed": loop.failed - failed_before + (error is not None),
        "error": error,
        "items_per_s_per_chip": (steps * built["items_per_step_per_chip"]
                                 / window_s) if steps and window_s else None,
        "step_ms": {"median": statistics.median(step_ms),
                    "p90": quantile(step_ms, 0.9),
                    "p99": quantile(step_ms, 0.99),
                    "max": max(step_ms)} if step_ms else None,
        "setup": at_setup,
        "compiles_in_window": in_window["compiles"] - at_setup["compiles"],
        "counters": counters,
        "memory_stats": {k: stats.get(k) for k in (
            "bytes_in_use", "peak_bytes_in_use", "bytes_limit")},
        "temp_bytes": built["temp_bytes"],
        "flops_per_step_per_chip": built["flops_per_item"]
        * built["items_per_step_per_chip"],
        "kernel": built.get("kernel"),
        **{k: first[k] for k in ("first_losses", "grad_norms",
                                  "delta_norms")},
        "last_loss": loop.losses[-1],
        "traced": traced,
    }
    # The peak on the chip.  The allocator's own high-water mark where it
    # counts the step program's temporaries; where it does not (ResNet-50
    # reads 0.48 GB beside 4.55 GB of temporaries), what was live in the
    # window plus ``memory_analysis()``'s temporaries of the step: a
    # derived number, and ``memory_peak_source`` says which it is.
    live = stats.get("bytes_in_use") or 0
    allocator = stats.get("peak_bytes_in_use") or 0
    derived = live + built["temp_bytes"]
    record["memory_peak_bytes"] = max(allocator, derived)
    record["memory_peak_source"] = (
        "allocator_peak_bytes_in_use" if allocator >= derived
        else "bytes_in_use_plus_program_temp_bytes")
    os.makedirs(os.path.join(cells.OUT, cell.name), exist_ok=True)
    with open(os.path.join(cells.OUT, cell.name,
                           f"steps-rank{rank}.json"), "w") as fh:
        json.dump({"seed": args.seed, "seconds": args.seconds,
                   "step_ms": step_ms}, fh)
    final = built["params_of"](loop.state)
    record["digest"] = digest(final)
    record["params_changed"] = record["digest"] != first["seed_digest"]
    hvd.shutdown()

    # ---- the reference, once the program's state is freed; where ranks
    # were launched, the one whose compiles JAX's cache keeps runs it
    del loop, final, built
    record["reference"] = None
    if jax.process_index() == 0:
        t_ref = time.perf_counter()
        ref = cells.load_module("reference", cell.family)
        record["reference"] = ref.follow(cell.sizes, key, cell.world,
                                         FIRST_STEPS)
        record["reference_s"] = time.perf_counter() - t_ref
    return record


def trace_steps(jax, cell, loop, record_it):
    """``trace_steps`` steps under the profiler (on the rank that records;
    the others just take the steps), then the reduction.  Returns what
    ``trace_reduce`` found."""
    from . import trace_reduce
    steps = cell.sizes["trace_steps"]
    if not record_it:
        for _ in range(steps):
            loop.advance()
        return None
    loop.drain()
    out = os.path.join(cells.OUT, cell.name, "trace")
    for old in glob.glob(os.path.join(out, "plugins", "profile", "*", "*")):
        os.unlink(old)
    # host spans yes, python's own call tracer no: in an eager step it
    # would be most of what the host does
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("bench/traced_window"):
            for i in range(steps):
                with jax.profiler.TraceAnnotation("bench/step", step=i):
                    loop.advance()
            with jax.profiler.TraceAnnotation("bench/drain"):
                loop.drain()
    finally:
        jax.profiler.stop_trace()
    found = sorted(glob.glob(os.path.join(out, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not found:
        raise RuntimeError(f"the profiler wrote no trace under {out}")
    reduced = trace_reduce.reduce_file(found[-1])
    reduced["steps"] = steps
    reduced["path"] = found[-1]
    return reduced
