"""Distributed collective tracing (no jax imports — tier-1 purity guarded).

Follows every tensor through its five host-side lifecycle phases (queue →
negotiation → copy_in → reduce → drain), correlates ranks on the
negotiation cycle id, and merges the fleet into one perfetto view:

- :mod:`.core`    — span ring + per-phase accumulators (the engine stamps),
  and the program spans (:func:`span`: one interval of one thread between
  two of the program's layer boundaries, a TraceMe on the profiler's clock),
  and the start-up record (:func:`startup`: the phases of a process's
  start and its compile ledger, kept whether tracing is armed or not);
- :mod:`.writer`  — per-rank JSONL trace files (``HOROVOD_TRACE``);
- :mod:`.merge`   — cross-rank merge into a chrome/perfetto trace with
  per-rank lanes and cycle flow arrows (``python -m horovod_tpu.trace``);
- :mod:`.analyze` — critical-path attribution (which phase eats the cycle).

See ``docs/timeline.md`` for knobs and reading recipes.
"""

from __future__ import annotations

import sys

from . import core
from .core import (DIGEST_MAX_CYCLES, DIGEST_MAX_OPEN, OFF, PHASE_BUCKETS_US,
                   PHASES, REDUCE_LEGS, CycleRecord, ProgramSpan, TensorSpan,
                   TraceRecorder, attention, causal_conv, delta_rule,
                   expert_blocks, flash_blocks, inner_update, installed, selective_scan,
                   span, stage_group, startup, startup_span, write_startup)
from .writer import TraceWriter

__all__ = [
    "PHASES", "REDUCE_LEGS", "PHASE_BUCKETS_US", "DIGEST_MAX_CYCLES",
    "DIGEST_MAX_OPEN", "CycleRecord", "TensorSpan", "TraceRecorder",
    "TraceWriter", "maybe_install", "span", "installed", "OFF",
    "ProgramSpan", "inner_update", "stage_group", "causal_conv",
    "selective_scan", "delta_rule", "expert_blocks", "flash_blocks",
    "attention", "startup", "startup_span", "write_startup",
]


def maybe_install(cfg, rank: int = 0):
    """Build a :class:`TraceRecorder` when the config arms tracing
    (``HOROVOD_TRACE``), else None — the engine's ``tracer`` attribute.
    Called from the engine constructor; a None return keeps every stamp
    site a single attribute check (the strictly-zero-cost disarmed
    contract).  The recorder built here is
    also the one :func:`span` reaches from the calling thread, until it
    closes."""
    if not getattr(cfg, "trace", False):
        return None
    filename = getattr(cfg, "trace_filename", "") or ""
    writer = TraceWriter(filename, rank=rank) if filename else None
    # Program spans open a TraceMe where the process has jax (the
    # engine's always has); this package itself never imports it.
    jax = sys.modules.get("jax")
    rec = TraceRecorder(
        capacity=getattr(cfg, "trace_ring", 4096), writer=writer, rank=rank,
        annotation=jax.profiler.TraceAnnotation if jax is not None else None)
    core._installed = rec
    return rec
