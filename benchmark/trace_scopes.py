"""Device time by the program's named scopes, from the profiler trace of a
``--trace 1`` run (``ctx["trace"]["path"]``).

``jax.named_scope`` names are metadata: they end up in an operation's
``op_name`` in the compiled program (``.../forward/jvp(gdn/scan)/...``, in
the backward pass ``.../transpose(jvp(gdn/scan))/...``, recomputed
``.../rematted_computation/gdn/scan/...``).  A device event of the trace
names its instruction and carries no ``op_name`` (the TPU's ``XLA Ops``
events have three timing stats, the CPU's an ``hlo_op``), so the family's
``kernel["scopes"]`` table, made from the compiled step's text in set-up
with :func:`within`, says which scope an instruction belongs to.  A kernel
that the compiler names itself (``ragged-dot-...``, the TPU's grouped
matrix product, whose ``op_name`` the compiler rewrites) is found by the
prefix of its instruction's name.

A scope's time is the union of its events' intervals on a device (a
``while`` and the operations of its body overlap), averaged over the
devices.  A program without such scopes yields ``None``, never an error.
"""

from __future__ import annotations

import functools
import re

from . import trace_reduce


@functools.lru_cache(maxsize=2)
def device_events(path):
    """{device: [(instruction name, start_s, end_s)]}: ``trace_reduce``'s
    device operations (the CPU's stand-ins in a rehearsal), each under the
    name of its instruction."""
    device_ops, _ = trace_reduce.read_planes(path)
    return {device: [(trace_reduce.describe(text)[0].split(" ")[0], a, b)
                     for text, a, b in ops]
            for device, ops in device_ops.items()}


def within(scopes, hlo_text):
    """{instruction name: scope} for the operations of a compiled program
    whose ``op_name`` has one of ``scopes`` as whole path elements:
    ``moe/experts`` in ``transpose(jvp(moe/experts))/mul``."""
    found = {}
    for m in re.finditer(r'%([\w.\-]+) = [^\n]*?op_name="([^"]*)"',
                         hlo_text):
        scope = next((s for s in scopes if re.search(
            rf"(?<![\w]){re.escape(s)}(?![\w])", m.group(2))), None)
        if scope:
            found[m.group(1)] = scope
    return found


def seconds(path, scopes, kernels=(), table=None):
    """Device seconds in the instructions that ``table`` gives to one of
    ``scopes`` (and in those whose name starts with one of ``kernels``) in
    the whole trace, or ``None`` where nothing matches."""
    table = table or {}
    totals = []
    for events in device_events(path).values():
        hit = [(a, b) for name, a, b in events
               if table.get(name) in scopes or name.startswith(kernels)]
        totals.append(trace_reduce.length(trace_reduce.union(hit)))
    if not totals or not any(totals):
        return None
    return sum(totals) / len(totals)


def per_step(ctx, scopes, kernels=()):
    """Seconds a traced step under ``scopes``, or ``None``."""
    t = ctx["trace"]
    if not t or not t.get("path"):
        return None
    kernel = ctx["record"].get("kernel") or {}
    total = seconds(t["path"], tuple(scopes), tuple(kernels),
                    kernel.get("scopes"))
    return None if total is None else total / t["steps"]
