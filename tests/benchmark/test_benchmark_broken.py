"""A run whose timed path is broken underneath has to come out as not
correct: the harness's look for a chip is skipped (``--rehearse``), the
rest of the run is driven as it stands."""

import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import rehearsal  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.mark.parametrize("fault, failing", [
    ("state_unchanged", "delta_norm_gap"),
    ("half_batch", "grad_norm_gap"),
    ("loss_altered", "loss_rel"),
    # a vector leaf's gradient gone wrong: no matrix or kernel sees it
    ("bn_scale_gradient", "vector_grad_norm_gap.scale"),
])
def test_a_broken_step_is_not_correct(fault, failing):
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "broken_run.py"), fault,
         "--workload", "resnet50-spmd-1c", "--seed", "9", "--seconds", "0.5",
         "--trace", "0", "--rehearse"],
        cwd=rehearsal.ROOT, env=rehearsal.child_env(), timeout=240,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is False
    failed = [r.split()[1] for r in proc.stdout.splitlines()
              if r.startswith("compare") and r.endswith("FAILED")]
    assert any(name.startswith(failing) for name in failed), failed
