"""A ``jamba`` run whose step is broken underneath has to come out as not
correct: each fault with the number that catches it.  Here at the files'
tiny sizes; at the cell's own sizes on the chip the same six read, in the
order below, ``grad_norm_gap`` 1.71, 0.873, 23.0, 1.79, ``delta_norm_gap``
0.754 on ``embed`` and ``grad_norm_gap`` 0.795 beside limits of 0.06 and 0.1
(PERF.md section 2)."""

import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import rehearsal  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.mark.parametrize("fault, failing", [
    # y = h C without + D x: the loss of random weights moves by two per
    # cent, the step's projection upstream of the scan by its own size
    ("skip_left_out", "grad_norm_gap"),
    # dt_r, B and C un-normed: W_x's gradient is four fifths off
    ("inner_norms_left_out", "grad_norm_gap"),
    # delta = softplus(dt_r W_dt) without b_dt: steps near 0.7 where the
    # draw has 0.001..0.1, every state forgets; the loss moves by 2-3 %,
    # W_x's gradient by two and a half times its size
    ("step_bias_left_out", "grad_norm_gap"),
    # A[c, s] = A[c, 0]: every state of a channel decays alike; the loss
    # moves by half a per cent, W_x's gradient by half its size
    ("one_decay_a_channel", "grad_norm_gap"),
    # the head reads the tied matrix as a constant: the loss is the same,
    # the lookup's rows still get their gradient, and Adam, which steps
    # every element by about lr, shows the rows that got none
    ("head_part_dropped", "delta_norm_gap['embed']"),
    # the loss over the first half of each sequence
    ("half_the_batch", "grad_norm_gap"),
])
def test_a_broken_step_is_not_correct(fault, failing):
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "broken_run_jamba.py"),
         fault, "--workload", "jamba2_3b-14l-spmd-1c", "--seed", "9",
         "--seconds", "2", "--trace", "0", "--rehearse"],
        cwd=rehearsal.ROOT, env=rehearsal.child_env(), timeout=300,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is False
    failed = [r.split()[1] for r in proc.stderr.splitlines()
              if r.startswith("compare") and r.endswith("FAILED")]
    assert any(name.startswith(failing) for name in failed), failed
    # a dropped part of the tied gradient leaves the loss where it was:
    # the norms are what catches it
    if fault == "head_part_dropped":
        assert not any(name.startswith("loss_rel") for name in failed), failed


def test_an_unknown_fault_is_refused():
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "broken_run_jamba.py"),
         "no_such_fault", "--workload", "jamba2_3b-14l-spmd-1c"],
        cwd=rehearsal.ROOT, env=rehearsal.child_env(), timeout=120,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    assert proc.returncode != 0 and "unknown fault" in proc.stderr
