"""A ``nemotron_h`` run whose step is broken underneath has to come out as
not correct: each fault with the number that catches it."""

import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import rehearsal  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.mark.parametrize("fault, failing", [
    # y = S C without + D x: the loss of random weights moves by a per
    # cent or two, the convolution's gradient by more than itself
    ("skip_left_out", "grad_norm_gap"),
    # the gated norm over all 64 channels instead of groups of 32: the
    # same loss again, the matrices downstream a fifth off
    ("norm_over_all_channels", "grad_norm_gap"),
    # the chosen experts weighed by s + b: the selection bias, a buffer
    # that takes no gradient, now takes one (the reference's is zero)
    ("weights_from_score_plus_bias", "vector_grad_norm_gap.router_bias"),
])
def test_a_broken_step_is_not_correct(fault, failing):
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "broken_run_nemotron_h.py"),
         fault, "--workload", "nemotron3super-11l-spmd-1c", "--seed", "9",
         "--seconds", "2", "--trace", "0", "--rehearse"],
        cwd=rehearsal.ROOT, env=rehearsal.child_env(), timeout=300,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is False
    failed = [r.split()[1] for r in proc.stdout.splitlines()
              if r.startswith("compare") and r.endswith("FAILED")]
    assert any(name.startswith(failing) for name in failed), failed
    # the loss of random weights moves by a per cent or so under the first
    # two faults (about its limit, which the float8 control sets) and by a
    # tenth of that under the third: the norms are what catches each
    if fault == "weights_from_score_plus_bias":
        assert not any(name.startswith("loss_rel") for name in failed), failed
