"""Step programs: model FLOPs of a step (forward and backward from the
shapes, nothing recomputed: ``families/<family>.py``) over the traced
steps' wall time and the chip's bf16 peak (``peaks.json``)."""


def read(ctx):
    t = ctx["trace"]
    if not t:
        return None
    step_s = t["window_s"] / t["steps"]
    return 100.0 * ctx["record"]["flops_per_step_per_chip"] / (
        step_s * ctx["peaks"]["bf16_flops_per_s"])
