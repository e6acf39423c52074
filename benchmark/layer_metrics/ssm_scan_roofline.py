"""Kernels: the least time the chip could take for the chunked state-space
recurrence of a step — the larger of its products over the bf16 peak and
its bytes over the HBM peak, both from the shapes
(``families/nemotron_h.py``: ``kernel["ssm_scan"]``) — over the device time
under ``ssm/scan``.  ``ssm_scan_bound`` in the notes says which."""

from .. import trace_scopes
from .gdn_scan_roofline import least_s
from .ssm_scan_ms import SCOPES


def read(ctx):
    kernel = (ctx["record"].get("kernel") or {}).get("ssm_scan")
    s = trace_scopes.per_step(ctx, SCOPES)
    if not kernel or not s:
        return None
    return 100.0 * least_s(ctx, kernel, "ssm_scan_bound") / s
