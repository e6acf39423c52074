"""Kernels: the least time the chip could take for the step's attention
kernels — the larger of their operations over the bf16 peak and their
bytes over the HBM peak, counted from the shapes over the causal in-window
positions (``families/llama.py``) — over the summed device time of the
step's custom-call events.  ``bound`` in the context line says which."""


def read(ctx):
    t, kernel = ctx["trace"], ctx["record"].get("kernel")
    if not t or not kernel or not t.get("custom_call_s"):
        return None
    by_ops = kernel["flops_per_step"] / ctx["peaks"]["bf16_flops_per_s"]
    by_bytes = kernel["bytes_per_step"] / ctx["peaks"]["hbm_bytes_per_s"]
    ctx.setdefault("notes", {})["flash_bound"] = (
        "compute" if by_ops >= by_bytes else "memory")
    return 100.0 * max(by_ops, by_bytes) / (t["custom_call_s"] / t["steps"])
