"""``mistral7b-4l-spmd-1c`` end to end with ``--rehearse``: the cell's own control flow
at the files' tiny sizes on the CPU, as a child process."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import rehearsal  # noqa: E402

CELL, CHIPS = "mistral7b-4l-spmd-1c", 1


@pytest.fixture(scope="module")
def runs():
    """One run of each kind, same seed (past 32 signed bits)."""
    return [rehearsal.run(["--workload", CELL, "--seed", "4294967301",
                           "--seconds", "1", "--trace", str(trace),
                           "--rehearse"]) for trace in (0, 1)]


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_the_contracts_line(runs, trace):
    line = rehearsal.last_line(runs[trace])
    rehearsal.check_line(line, CELL, trace, CHIPS)


def test_the_same_seed_gives_the_same_first_steps(runs):
    rows = [[r for r in p.stdout.splitlines() if r.startswith("compare")
             and "last_loss" not in r and "digest" not in r] for p in runs]
    assert rows[0] and rows[0] == rows[1], [
        (p.returncode, p.stdout[-1500:], p.stderr[-1500:]) for p in runs]
