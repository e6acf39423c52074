"""``qwen3next-4l-spmd-1c`` end to end with ``--rehearse``: the cell's own control flow
at the files' tiny sizes on the CPU, as a child process."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import rehearsal  # noqa: E402

CELL, CHIPS = "qwen3next-4l-spmd-1c", 1


@pytest.fixture(scope="module")
def runs():
    """One run of each kind, same seed (past 32 signed bits).  A step of
    this cell is some 60 ms of small operations on an idle CPU: three
    seconds, so that a loaded machine still completes steps in the window."""
    return [rehearsal.run(["--workload", CELL, "--seed", "4294967301",
                           "--seconds", "3", "--trace", str(trace),
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


def test_the_scopes_are_read_and_are_parts_of_the_step(runs):
    """The four metrics this cell brings come from the program's named
    scopes; the recurrence and the expert layer are parts of one step."""
    metrics = rehearsal.last_line(runs[1])["metrics"]
    value = lambda name: metrics[name]["value"]
    assert value("gdn_scan_ms") > 0 and value("moe_ms") > 0
    assert value("gdn_scan_ms") + value("moe_ms") < value(
        "device_step_ms.spmd")
    for share in ("gdn_scan_roofline", "expert_matmul_roofline"):
        assert 0 < value(share) < 100
