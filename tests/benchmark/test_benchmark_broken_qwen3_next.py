"""A ``qwen3_next`` run whose own mechanisms are broken underneath has to
come out as not correct: each fault with the number that catches it."""

import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import rehearsal  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.mark.parametrize("fault, failing", [
    # every chunk of the delta rule starts from a zero state: the loss
    # moves little, the recurrent layers' own weights' gradients do
    ("chunk_state_not_carried", "grad_norm_gap"),
    # assignments over a capacity dropped, as the capacity router does
    ("capacity_dropped", "grad_norm_gap"),
    # the shared expert added without its sigmoid gate: its output about
    # doubles, and so do the gradients of its own matrices (the gates'
    # gradient, a vector a layer, is gone: gap 1 within its kind)
    ("shared_gate_left_out", "grad_norm_gap"),
])
def test_a_broken_mechanism_is_not_correct(fault, failing):
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "broken_run_qwen3_next.py"),
         fault, "--workload", "qwen3next-4l-spmd-1c", "--seed", "9",
         "--seconds", "2", "--trace", "0", "--rehearse"],
        cwd=rehearsal.ROOT, env=rehearsal.child_env(), timeout=240,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is False
    failed = [r.split()[1] for r in proc.stdout.splitlines()
              if r.startswith("compare") and r.endswith("FAILED")]
    assert any(name.startswith(failing) for name in failed), failed
