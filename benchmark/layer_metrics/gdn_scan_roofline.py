"""Kernels: the least time the chip could take for the chunked delta rule
of a step — the larger of its products over the bf16 peak and its bytes
over the HBM peak, both from the shapes (``families/qwen3_next.py``) — over
the device time under ``gdn/scan``.  ``gdn_scan_bound`` in the notes says
which."""

from .. import trace_scopes
from .gdn_scan_ms import SCOPES


def least_s(ctx, kernel, note):
    by_ops = kernel["flops_per_step"] / ctx["peaks"]["bf16_flops_per_s"]
    by_bytes = kernel["bytes_per_step"] / ctx["peaks"]["hbm_bytes_per_s"]
    ctx.setdefault("notes", {})[note] = (
        "compute" if by_ops >= by_bytes else "memory")
    return max(by_ops, by_bytes)


def read(ctx):
    kernel = (ctx["record"].get("kernel") or {}).get("gdn_scan")
    s = trace_scopes.per_step(ctx, SCOPES)
    if not kernel or not s:
        return None
    return 100.0 * least_s(ctx, kernel, "gdn_scan_bound") / s
