"""An ``olmo_hybrid`` run whose step is broken underneath has to come out
as not correct: each fault with the number that catches it."""

import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import rehearsal  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.mark.parametrize("fault, failing", [
    # beta left in (0, 1): the loss of random weights moves by a per cent,
    # the gate projection's gradient by half
    ("beta_not_doubled", "grad_norm_gap"),
    # the norm before the mixer instead of after it: the same loss again,
    # every mixer's gradients far off
    ("norm_first", "grad_norm_gap"),
    # the loss over half of each sequence's targets: a mean over fewer
    # tokens is the same loss and a larger gradient
    ("half_the_batch", "grad_norm_gap"),
])
def test_a_broken_step_is_not_correct(fault, failing):
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "broken_run_olmo_hybrid.py"),
         fault, "--workload", "olmohybrid-4l-spmd-1c", "--seed", "9",
         "--seconds", "2", "--trace", "0", "--rehearse"],
        cwd=rehearsal.ROOT, env=rehearsal.child_env(), timeout=240,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is False
    failed = [r.split()[1] for r in proc.stdout.splitlines()
              if r.startswith("compare") and r.endswith("FAILED")]
    assert any(name.startswith(failing) for name in failed), failed
    # every fault leaves the loss inside its limit: the norms catch it
    assert not any(name.startswith("loss_rel") for name in failed), failed
