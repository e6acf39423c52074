"""Kernels: the sliding-window layers' attention kernels' share of their
roofline as the ``laguna`` family counts it — 72 query heads on 8 key-value
heads of 128 in three layers, the band's own pairs (``sum_t min(t + 1,
512)`` a head) over 16384 positions (``families/laguna.py``:
``kernel["window_flash"]``), the larger of operations over the bf16 peak
and bytes over the HBM peak — over the device time of the flash kernels
that the family's ``scopes`` table gives to ``attn/window``.  The cell's
full layers run the same kernels under ``attn/full``
(``full_flash_roofline``), so the kernels of a layer kind are found by the
``flash_`` in their instructions' names, as ``nemotron_flash_roofline``
finds them, **and** by their scope.  ``window_flash_bound`` in the notes
says which peak bounds them, and ``attention_paths`` which path the step's
call sites took by layer kind (the family's copy of ``trace.attention``),
so that a silent fall to the plain path shows in the run's own notes.  A
program without the record, or a trace without such events, yields
nothing."""

from .. import trace_reduce, trace_scopes
from .gdn_scan_roofline import least_s


def scoped_flash_roofline(ctx, scope, record):
    """The share for the flash kernels under ``scope`` against
    ``kernel[record]``, or ``None``."""
    t, kernel = ctx["trace"], ctx["record"].get("kernel") or {}
    table, counted = kernel.get("scopes") or {}, kernel.get(record)
    paths = (kernel.get("counters") or {}).get("attention")
    if paths:
        ctx.setdefault("notes", {})["attention_paths"] = paths
    if not t or not t.get("path") or not counted or not counted.get(
            "flops_per_step"):
        return None
    totals = [trace_reduce.length(trace_reduce.union(
        [(a, b) for name, a, b in events
         if "flash_" in name and table.get(name) == scope]))
        for events in trace_scopes.device_events(t["path"]).values()]
    if not totals or not any(totals):
        return None
    seconds = sum(totals) / len(totals) / t["steps"]
    return 100.0 * least_s(ctx, counted, f"{record}_bound") / seconds


def read(ctx):
    return scoped_flash_roofline(ctx, "attn/window", "window_flash")
