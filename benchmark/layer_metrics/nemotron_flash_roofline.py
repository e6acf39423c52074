"""Kernels: the attention kernels' share of their roofline as the
``nemotron_h`` family counts it — 32 query heads on 2 key-value heads of
128 over 8192 causal positions (``families/nemotron_h.py``:
``kernel["flops_per_step"]``, ``kernel["bytes_per_step"]``), the larger of
operations over the bf16 peak and bytes over the HBM peak — over the device
time of the flash kernels alone.  ``flash_roofline`` divides by every
``tpu_custom_call`` event of the step, and in this cell the TPU's grouped
matrix products (``ragged-dot-...``) are such events too: here the kernels
are found by the ``flash_`` in their instructions' names
(``flash_fwd.N``, ``jvp_flash_bwd_dq_.N``).  ``flash_bound`` in the notes
says which peak bounds them.  A trace without such events yields
nothing."""

from .. import trace_reduce, trace_scopes
from .gdn_scan_roofline import least_s


def read(ctx):
    t, kernel = ctx["trace"], ctx["record"].get("kernel")
    if not t or not t.get("path") or not kernel or not kernel.get(
            "flops_per_step"):
        return None
    totals = [trace_reduce.length(trace_reduce.union(
        [(a, b) for name, a, b in events if "flash_" in name]))
        for events in trace_scopes.device_events(t["path"]).values()]
    if not totals or not any(totals):
        return None
    seconds = sum(totals) / len(totals) / t["steps"]
    return 100.0 * least_s(ctx, kernel, "flash_bound") / seconds
