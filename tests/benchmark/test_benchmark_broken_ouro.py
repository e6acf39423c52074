"""An ``ouro`` run whose step is broken underneath has to come out as not
correct: each fault with the number that catches it."""

import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import rehearsal  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.mark.parametrize("fault, failing", [
    # three passes for four: the loss of random weights moves by a third of
    # a per cent (the passes' losses are close), but a shared weight's
    # gradient lacks a pass's contribution
    ("three_passes", "grad_norm_gap"),
    # the final norm left out of the loop: later passes start from a stream
    # that was never normed; the first pass, and so most of the loss, is the
    # same
    ("norm_outside_the_loop", "grad_norm_gap"),
    # the last pass gated like the others: the exit distribution no longer
    # sums to one and the loss itself is short by the missing share
    ("last_pass_gated", "loss_rel"),
    # the MLP's output added without its norm: the loss of random weights
    # hardly moves, the MLP's gradients do
    ("no_output_norm", "grad_norm_gap"),
])
def test_a_broken_step_is_not_correct(fault, failing):
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "broken_run_ouro.py"),
         fault, "--workload", "ouro2_6b-16l-spmd-1c", "--seed", "9",
         "--seconds", "2", "--trace", "0", "--rehearse"],
        cwd=rehearsal.ROOT, env=rehearsal.child_env(), timeout=240,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is False
    failed = [r.split()[1] for r in proc.stdout.splitlines()
              if r.startswith("compare") and r.endswith("FAILED")]
    assert any(name.startswith(failing) for name in failed), failed
