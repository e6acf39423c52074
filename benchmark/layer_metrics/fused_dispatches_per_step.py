"""Eager engine: fused programs dispatched per step, the engine's own
``pipeline_dispatches`` over the steps enqueued in the window."""


def read(ctx):
    counters = ctx["record"]["counters"]
    steps = ctx["record"]["counted_steps"]
    if not counters or not steps or ctx["mix"]["step_mode"] != "eager":
        return None
    return counters["pipeline_dispatches"] / steps
