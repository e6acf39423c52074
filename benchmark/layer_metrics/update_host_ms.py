"""Eager engine: median host time inside ``DistributedOptimizer.update``
per step, from the benchmark's ``bench/update`` span in the trace."""

import statistics


def read(ctx):
    spans = (ctx["trace"] or {}).get("host_spans", {}).get("bench/update")
    return statistics.median(spans) * 1e3 if spans else None
