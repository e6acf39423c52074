"""The comparison that decides ``correct`` (no jax: the parent of a
launched cell runs it too).

A training cell's timed object — the compiled step with its state — is
driven from the seed through its first three steps by set-up, through the
window's own call and batch, and then handed to the window.  The plain
float32 reference (``reference/<family>.py``) follows the same three steps
from the same seed.  Compared, each against a limit of its own from the
configuration file (``limits``; ``PERF.md`` gives the readings each was set
from):

``loss_rel``        each step's loss, |program - reference| / |reference|.
                    Hardly moved by precision; held against a part of the
                    batch left out or a wrong shard.
``grad_norm_gap``   the first gradient as the optimizer got it (worked out
                    from its state after one step), by the worst leaf of
                    two dimensions or more: the gap between the program's
                    norm and the reference's, against the reference's norm
                    of that leaf or of the median leaf, whichever is
                    larger.
``delta_norm_gap``  the parameters' change over the three steps, the same
                    way.  Held against a step that returns its state
                    unchanged (gap 1).
``vector_grad_norm_gap``, ``vector_delta_norm_gap``
                    the same two for the vectors and scalars (norm scales,
                    biases), which are not held leaf by leaf: the leaves
                    of one kind (the last key of the path: every
                    batch-norm ``scale``, every ``bias``) taken together,
                    the gap between the program's and the reference's norm
                    over all of them against the reference's, by the worst
                    kind.  Held against a kind's gradient left out, or
                    scaled wrongly (gap 1).  Compared where the
                    configuration's ``limits`` name them, printed
                    everywhere.

After the window: the last loss is finite and the parameters differ from
the seed's; with several ranks every rank holds the same parameter digest.
"""

from __future__ import annotations

import math
import re
import statistics


def held(leaf):
    """Matrices and kernels are held leaf by leaf; vectors and scalars
    (``...:1d``, ``...:0d``: norm scales, biases) are not.  A batch norm's
    scale and bias gradients are sums that all but cancel, and a sound
    bfloat16 ResNet-50 reads 13 to 46 % off on them, as far as its float8
    control (PERF.md section 2); they are held kind by kind
    (``vector_gaps``)."""
    return not leaf.endswith((":1d", ":0d"))


def norm_gap(program, reference):
    """(worst gap, its leaf) between two {leaf: norm} tables, over the
    leaves that are held; the floor is the median over all leaves."""
    if set(program) != set(reference):
        missing = sorted(set(program) ^ set(reference))[:4]
        raise ValueError(f"the program's leaves are not the reference's: "
                         f"{missing}")
    floor = statistics.median(reference.values())
    worst, where = 0.0, ""
    for leaf, ref in reference.items():
        if not held(leaf):
            continue
        gap = abs(program[leaf] - ref) / max(ref, floor, 1e-30)
        if math.isnan(gap):             # a nan is the worst there is
            return gap, leaf
        if gap > worst:
            worst, where = gap, leaf
    return worst, where


def kind(leaf):
    """The last key of a leaf's path: ``['stem']['bn']['scale']:1d`` is a
    ``scale``."""
    keys = re.findall(r"\[\'?(\w+)\'?\]", leaf)
    return keys[-1] if keys else leaf


def vector_gaps(program, reference):
    """{kind: gap} over the kinds of leaves that are not held one by one."""
    kinds = {}
    for leaf in reference:
        if not held(leaf):
            kinds.setdefault(kind(leaf), []).append(leaf)
    gaps = {}
    for name, leaves in sorted(kinds.items()):
        ref = math.sqrt(sum(reference[l] ** 2 for l in leaves))
        got = math.sqrt(sum(program[l] ** 2 for l in leaves))
        gaps[name] = abs(got - ref) / max(ref, 1e-30)
    return gaps


def vector_gap(program, reference):
    """(worst gap, its kind); ``(None, "")`` where every leaf is held.  A
    nan is the worst there is."""
    gaps = vector_gaps(program, reference)
    if not gaps:
        return None, ""
    where = max(gaps, key=lambda k: (math.isnan(gaps[k]), gaps[k]))
    return gaps[where], where


def decide(ranks, reference, limits):
    """``ranks``: one program record per rank (``rank``, ``first_losses``,
    ``grad_norms``, ``delta_norms``, ``digest``, ``last_loss``,
    ``params_changed``); ``reference``: what ``follow`` returned.  Returns
    ``(correct, rows)`` with a row ``(name, value, limit, ok)`` for every
    number compared."""
    rows = []

    def row(name, value, limit, ok=None):
        ok = (value <= limit) if ok is None else ok
        rows.append((name, value, limit, bool(ok)))

    for rec in ranks:
        tag = f"rank{rec['rank']}." if len(ranks) > 1 else ""
        want = reference["losses"][rec["rank"]]
        for i, (got, ref) in enumerate(zip(rec["first_losses"], want), 1):
            row(f"{tag}loss_rel.step{i}", abs(got - ref) / abs(ref),
                limits["loss_rel"])
        gap, leaf = norm_gap(rec["grad_norms"], reference["grad_norms"])
        row(f"{tag}grad_norm_gap{leaf}", gap, limits["grad_norm_gap"])
        gap, leaf = norm_gap(rec["delta_norms"], reference["delta_norms"])
        row(f"{tag}delta_norm_gap{leaf}", gap, limits["delta_norm_gap"])
        for what in ("grad", "delta"):
            gap, where = vector_gap(rec[f"{what}_norms"],
                                    reference[f"{what}_norms"])
            name = f"vector_{what}_norm_gap"
            if gap is None:
                continue
            if name in limits:
                row(f"{tag}{name}.{where}", gap, limits[name])
            else:                       # read, and held to nothing
                row(f"{tag}{name}.{where}", gap, "none", True)
        row(f"{tag}last_loss_finite", rec["last_loss"], "finite",
            math.isfinite(rec["last_loss"]))
        row(f"{tag}params_changed", rec["params_changed"], True,
            rec["params_changed"])
    digests = sorted({rec["digest"] for rec in ranks})
    if len(ranks) > 1:
        row("ranks_hold_one_digest", digests, 1, len(digests) == 1)
    return all(r[3] for r in rows), rows


def show(rows, out):
    for name, value, limit, ok in rows:
        value = f"{value:.6g}" if isinstance(value, float) else value
        print(f"compare  {name}  {value}  limit {limit}  "
              f"{'ok' if ok else 'FAILED'}", file=out, flush=True)
