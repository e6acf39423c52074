"""Kernels: the least time the chip could take for the grouped products of
the experts held here over the assignments that the fixed batch sends them
— the larger of their operations over the bf16 peak and the held weights
read forward and backward plus their gradient written over the HBM peak
(``families/qwen3_next.py``) — over the device time under ``moe/experts``.
``expert_matmul_bound`` in the notes says which."""

from .. import trace_scopes
from .gdn_scan_roofline import least_s
from .moe_ms import KERNELS

SCOPES = ("moe/experts",)


def read(ctx):
    kernel = (ctx["record"].get("kernel") or {}).get("experts")
    s = trace_scopes.per_step(ctx, SCOPES, KERNELS)
    if not kernel or not s:
        return None
    return 100.0 * least_s(ctx, kernel, "expert_matmul_bound") / s
