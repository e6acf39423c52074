"""Where JAX's persistent compilation cache lives, and what each program
cost to compile (no jax at import).

One rule for every entry point (``hvd.init()`` on an accelerator, hence
``chip_smoke.py``, ``benchmark/`` and the workers ``torovodrun`` starts):
where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and the
program sets nothing; where it is not, the cache goes to ``.jax_cache/``
beside the package (git-ignored).  The path is part of no key but a
directory that moves never hits, so it is never built from a temporary
name, a pid or the time.

**The compile ledger** (:func:`register_ledger`, once a process from
``hvd.init()`` on every platform) listens to ``jax.monitoring`` and keeps,
by program name, what jax publishes of a compile: the jaxpr trace, the
lowering, the backend compile, whether the persistent cache was asked and
hit, and the seconds spent reading a hit back.  It rides the start-up
record (``trace.startup()``) and ``/metrics``.  A dictionary update a
compile event; nothing on a step's path.
"""

from __future__ import annotations

import os
import threading

from ..trace import core as _trace

ENV = "JAX_COMPILATION_CACHE_DIR"
_FIXED = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), ".jax_cache")


def cache_dir() -> str:
    """The directory every process of this program resolves to."""
    return os.environ.get(ENV) or _FIXED


def enable() -> str:
    """Point JAX at :func:`cache_dir` and return it.

    A no-op where the environment already placed the cache.  Otherwise the
    cache's lazily-latched "is it used" verdict is reset, so a program
    that compiled something before ``hvd.init()`` still gets the cache for
    everything after.
    """
    import jax
    path = cache_dir()
    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
        from jax.experimental.compilation_cache import compilation_cache
        compilation_cache.reset_cache()
    return path


def place_process_file(accelerator: bool) -> None:
    """Tell the start-up record where this process's line goes: beside the
    cache where there is one — on an accelerator, or where the environment
    placed it — and nowhere on a CPU run by itself.  Nowhere either where
    the cache is no local directory (``gs://bucket/...``, which jax reads
    through its own file system layer): the line is written with ``os``."""
    path = cache_dir()
    if (accelerator or os.environ.get(ENV)) and "://" not in path:
        _trace.startup_attach(directory=path)


# jax's events (jax 0.9.0: ``jax/_src/dispatch.py``, ``compiler.py``,
# ``compilation_cache.py``).  The three stages carry the program's name
# (``fun_name``) and, through the scalar listener, their start on
# ``time.time()``.  ``cache_misses`` is recorded where an entry is
# *written*, which only process 0 does: it is kept as ``cache_writes``, and
# a miss is a request that did not hit.
_STAGES = {"/jax/core/compile/jaxpr_trace_duration": "trace_s",
           "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
           "/jax/core/compile/backend_compile_duration": "backend_s"}
_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_EVENTS = {"/jax/compilation_cache/compile_requests_use_cache": "asked_cache",
           "/jax/compilation_cache/cache_hits": "hits",
           "/jax/compilation_cache/cache_misses": "cache_writes"}
_PROGRAM_KEYS = ("count", "trace_s", "lower_s", "backend_s", "asked_cache",
                 "hits", "retrieval_s")
# A job that retraces under ever new names must not grow the table without
# bound: past this many names the rest add up under one.
MAX_PROGRAMS = 512
_OTHER = "(other programs)"


def _program(fun_name) -> str:
    """``jit(step)`` (lowering, backend) and ``step`` (trace) are one
    program."""
    name = str(fun_name)
    if name.startswith("jit(") and name.endswith(")"):
        name = name[4:-1]
    return name


class CompileLedger:
    """What this process compiled, by program name, and the totals.

    A request and a hit carry no name: they belong to the program whose
    backend-compile interval is open on their thread.  Seconds are a
    stage's own: a compile that runs inside another program's trace (an
    eager operation on a constant) is taken out of that trace, and a
    retrieval out of its backend interval, so the four stages add up to
    wall time with nothing counted twice; a function traced inside another
    one's trace is part of the outer."""

    def __init__(self):
        self._lock = threading.Lock()
        self._open = threading.local()      # .stack: the open intervals
        self.programs: dict = {}
        self.totals = dict.fromkeys(_PROGRAM_KEYS + ("cache_writes",), 0)

    # ------------------------------------------------------------ listeners
    def _stack(self) -> list:
        stack = getattr(self._open, "stack", None)
        if stack is None:
            stack = self._open.stack = []
        return stack

    def on_scalar(self, event, value, **kw):
        """A stage begins: jax publishes its ``time.time()`` start."""
        key = _STAGES.get(event)
        if key is not None:
            # [stage, program, start, seconds of what ran inside it]
            self._stack().append(
                [key, _program(kw.get("fun_name", "")), float(value), 0.0])

    def on_duration(self, event, seconds, **kw):
        if event == _RETRIEVAL:
            stack = self._stack()
            if stack and stack[-1][0] == "backend_s":
                stack[-1][3] += seconds
                self._add(stack[-1][1], stack[-1][2], retrieval_s=seconds)
            else:
                self._add("", 0.0, retrieval_s=seconds)
            return
        key = _STAGES.get(event)
        if key is None:
            return
        stack = self._stack()
        name = _program(kw.get("fun_name", ""))
        while stack:
            frame = stack.pop()
            if frame[0] == key and frame[1] == name:
                break
        else:       # registered while this stage was open: no start seen
            self._add(name, 0.0, **{key: seconds},
                      count=int(key == "backend_s"))
            return
        outer = stack[-1] if stack else None
        if key == "trace_s" and any(f[0] == "trace_s" for f in stack):
            outer[3] += frame[3]    # the outer trace keeps this one's time
            return
        if outer is not None:
            outer[3] += seconds
        self._add(name, frame[2], **{key: max(0.0, seconds - frame[3])},
                  count=int(key == "backend_s"))

    def on_event(self, event, **kw):
        key = _EVENTS.get(event)
        if key is None:
            return
        if key in ("asked_cache", "hits"):
            stack = self._stack()
            if stack and stack[-1][0] == "backend_s":
                self._add(stack[-1][1], stack[-1][2], **{key: 1})
            else:
                self._add("", 0.0, **{key: 1})
        else:
            with self._lock:
                self.totals[key] += 1

    def _add(self, name, started, **amounts):
        with self._lock:
            entry = self.programs.get(name)
            if entry is None:
                if len(self.programs) >= MAX_PROGRAMS:
                    name = _OTHER
                entry = self.programs.setdefault(
                    name, dict.fromkeys(_PROGRAM_KEYS, 0) | {"first_at": 0.0})
            if started and not entry["first_at"]:
                entry["first_at"] = started
            for key, amount in amounts.items():
                entry[key] += amount
                self.totals[key] += amount

    # -------------------------------------------------------------- reading
    def snapshot(self) -> dict:
        """``totals`` and ``programs`` by name: ``count`` backend compiles,
        ``trace_s``, ``lower_s``, ``backend_s`` (a hit's retrieval is not
        in it), ``asked_cache``, ``hits``, ``retrieval_s`` and ``first_at``,
        the ``time.time()`` its first stage began.  A program missed the
        cache ``asked_cache - hits`` times and never asked ``count -
        asked_cache`` times."""
        with self._lock:
            return {"totals": dict(self.totals),
                    "programs": {n: dict(e)
                                 for n, e in self.programs.items()}}


_ledger: "CompileLedger | None" = None


def register_ledger() -> CompileLedger:
    """Register the ledger's listeners, once a process.  Each of jax's
    register functions is looked up by name: one that is gone leaves its
    columns empty, not an error."""
    global _ledger
    if _ledger is not None:
        return _ledger
    import jax.monitoring as mon
    led = _ledger = CompileLedger()
    for register, listener in (
            ("register_scalar_listener", led.on_scalar),
            ("register_event_duration_secs_listener", led.on_duration),
            ("register_event_listener", led.on_event)):
        if hasattr(mon, register):
            getattr(mon, register)(listener)
    _trace.startup_attach(ledger=led.snapshot)
    totals = led.totals
    _trace.register_series(
        "hvd_compiles_total", "counter",
        "backend compiles (rising in a steady job: a recompile)",
        lambda: totals["count"])
    _trace.register_series(
        "hvd_compile_seconds_total", "counter",
        "seconds compiling, by stage",
        lambda: {"trace": totals["trace_s"], "lower": totals["lower_s"],
                 "backend": totals["backend_s"],
                 "retrieval": totals["retrieval_s"]}, label="stage")
    _trace.register_series(
        "hvd_compile_cache_requests_total", "counter",
        "compiles that asked the persistent cache",
        lambda: totals["asked_cache"])
    _trace.register_series(
        "hvd_compile_cache_hits_total", "counter",
        "compiles the persistent cache served", lambda: totals["hits"])
    return led
