"""Kernels: the least time the chip could take for the selective scan of a
step — its bytes over the HBM peak, from the shapes alone
(``families/jamba.py``: ``kernel["selective_scan"]``; the recurrence has no
matrix operation, so the bound is the memory's whatever implements it) —
over the device time under ``ssm/scan``, where the scan's kernels
(``ops/selective_scan.py``) or XLA's loop over the tokens run.
``selective_scan_bound`` in the notes says which peak bounds it, and
``selective_scan_paths`` which path the step's call sites took (the
family's copy of ``trace.selective_scan``: ``kernel`` or ``plain``), so
that a silent fall to the plain path shows in the run's own notes.  A
program without the record or the scope yields nothing."""

from .. import trace_scopes
from .gdn_scan_roofline import least_s
from .ssm_scan_ms import SCOPES


def read(ctx):
    record = ctx["record"].get("kernel") or {}
    kernel = record.get("selective_scan")
    paths = (record.get("counters") or {}).get("selective_scan")
    if paths:
        ctx.setdefault("notes", {})["selective_scan_paths"] = paths
    s = trace_scopes.per_step(ctx, SCOPES)
    if not kernel or not s:
        return None
    return 100.0 * least_s(ctx, kernel, "selective_scan_bound") / s
