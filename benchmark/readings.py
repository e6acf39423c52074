#!/usr/bin/env python3
"""The readings a limit of ``correct`` is set from (PERF.md section 2).

    python benchmark/readings.py --workload <name> --seeds 12 --control 3

In one process, seed after seed at the cell's own sizes: the timed object's
first three steps, the plain float32 reference's, and for the first
``--control`` seeds the control's — the reference computed in the nearest
precision below the configuration's (``float8`` under bfloat16,
``bfloat16`` under float32).  Prints every number compared, then the
largest the sound program gave and the smallest the control gave.  A limit
belongs above the first and below the second.  Needs no measured window.

A launched mix is read under its launcher:
``python -m horovod_tpu.runner.launch -np 1 python benchmark/readings.py ...``
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import cell as cells          # noqa: E402
from benchmark import compare                # noqa: E402
from benchmark import run as runner          # noqa: E402
from benchmark import worker                 # noqa: E402

BELOW = {"bfloat16": "float8", "float16": "float8", "float32": "bfloat16"}
KINDS = ("loss_rel", "grad_norm_gap", "delta_norm_gap",
         "vector_grad_norm_gap", "vector_delta_norm_gap")
NO_LIMIT = dict.fromkeys(KINDS, float("inf"))


def by_kind(program, reference):
    """Worst gap among the leaves of each kind (the last key of the path):
    to see which leaves a number hangs on."""
    import statistics
    floor = statistics.median(reference.values())
    out = {}
    for leaf, ref in reference.items():
        kind = compare.kind(leaf) + leaf[-3:]
        gap = abs(program[leaf] - ref) / max(ref, floor, 1e-30)
        out[kind] = max(out.get(kind, 0.0), gap)
    return {k: round(v, 5) for k, v in out.items()}


def numbers(record, reference):
    rows = compare.decide([record], reference, NO_LIMIT)[1]
    out = {"loss_rel": max(v for n, v, _, _ in rows
                           if n.startswith("loss_rel"))}
    for name, value, _, _ in rows:
        for kind in KINDS[1:]:
            if name.startswith(kind):
                out[kind], out[kind + "_leaf"] = value, name[len(kind):]
    for what in ("grad", "delta"):
        out[f"vector_{what}_by_kind"] = {
            k: round(v, 5) for k, v in compare.vector_gaps(
                record[f"{what}_norms"], reference[f"{what}_norms"]).items()}
    return out


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, default=12)
    p.add_argument("--first-seed", type=int, default=1000)
    p.add_argument("--control", type=int, default=3)
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args()
    args.trace, args.seed = 0, args.first_seed
    cell = cells.load_cell(args.workload, args.rehearse)
    runner.adopt_environment()
    jax, hvd, device, _, _ = worker.start(cell, args)
    family = cells.load_module("families", cell.family)
    ref = cells.load_module("reference", cell.family)
    lower = BELOW[cell.sizes["dtype"]]
    sound, control = [], []
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        key = worker.seed_key(seed)
        built = family.build(hvd, cell, key, jax.profiler.TraceAnnotation)
        loop = worker.Loop(built["step"], built.pop("state"), built["batch"])
        record = worker.drive_first_steps(jax, built, loop)
        record.update(rank=hvd.rank(), last_loss=loop.losses[-1],
                      params_changed=True, digest="")
        del built, loop
        reference = ref.follow(cell.sizes, key, cell.world,
                               worker.FIRST_STEPS)
        sound.append(numbers(record, reference))
        print(json.dumps({"seed": seed, "program": sound[-1],
                          "losses": record["first_losses"],
                          "reference_losses":
                              reference["losses"][record["rank"]]}),
              flush=True)
        if i < args.control:
            low = ref.follow(cell.sizes, key, cell.world,
                             worker.FIRST_STEPS, lower)
            stand_in = dict(record, first_losses=low["losses"][hvd.rank()],
                            grad_norms=low["grad_norms"],
                            delta_norms=low["delta_norms"])
            control.append(numbers(stand_in, reference))
            print(json.dumps({"seed": seed, "control": lower,
                              "losses": stand_in["first_losses"],
                              **control[-1]}), flush=True)
    hvd.shutdown()
    kinds = [k for k in KINDS if k in sound[0]]
    print(json.dumps({
        "workload": cell.name, "device": device, "seeds": args.seeds,
        "control_precision": lower, "control_seeds": len(control),
        "sound_largest": {k: max(s[k] for s in sound) for k in kinds},
        "control_smallest": {k: min(c[k] for c in control) for k in kinds}
        if control else None}), flush=True)


if __name__ == "__main__":
    main()
