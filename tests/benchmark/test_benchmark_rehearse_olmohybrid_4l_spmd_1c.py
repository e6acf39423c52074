"""``olmohybrid-4l-spmd-1c`` end to end with ``--rehearse``: the cell's own control flow
at the files' tiny sizes on the CPU, as a child process."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import rehearsal  # noqa: E402

CELL, CHIPS = "olmohybrid-4l-spmd-1c", 1
# the attention kernels' share is the chip's alone: interpreted on the CPU
# a Pallas kernel leaves no kernel event (``rehearsal.CHIP_ONLY``'s reason)
NEW = ("gdn_mixer_ms", "gdn_scan_ms.olmo", "olmo_gdn_scan_roofline",
       "olmo_flash_roofline", "mlp_ms")


@pytest.fixture(scope="module")
def runs():
    """One run of each kind, same seed (past 32 signed bits).  Four
    seconds, so that a loaded machine still completes steps in the
    window."""
    return [rehearsal.run(["--workload", CELL, "--seed", "4294967301",
                           "--seconds", "4", "--trace", str(trace),
                           "--rehearse"]) for trace in (0, 1)]


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_the_contracts_line(runs, trace, monkeypatch):
    monkeypatch.setattr(rehearsal, "CHIP_ONLY",
                        rehearsal.CHIP_ONLY | {"olmo_flash_roofline"})
    line = rehearsal.last_line(runs[trace])
    rehearsal.check_line(line, CELL, trace, CHIPS)


def test_the_same_seed_gives_the_same_first_steps(runs):
    rows = [[r for r in p.stdout.splitlines() if r.startswith("compare")
             and "last_loss" not in r and "digest" not in r] for p in runs]
    assert rows[0] and rows[0] == rows[1], [
        (p.returncode, p.stdout[-1500:], p.stderr[-1500:]) for p in runs]


def test_the_five_new_metrics_are_this_cells_and_are_read(runs):
    """The metrics this cell brings come from the program's named scopes:
    the recurrence is a part of the mixer, the mixer and the MLP are parts
    of one step."""
    assert set(NEW) <= set(rehearsal.metrics_of(CELL, "per_layer"))
    assert not set(NEW) & set(rehearsal.metrics_of("qwen3next-4l-spmd-1c",
                                                   "per_layer"))
    metrics = rehearsal.last_line(runs[1])["metrics"]
    value = lambda name: metrics[name]["value"]
    assert 0 < value("gdn_scan_ms.olmo") < value("gdn_mixer_ms")
    assert value("mlp_ms") > 0
    assert value("gdn_mixer_ms") + value("mlp_ms") < value(
        "device_step_ms.spmd")
    assert 0 < value("olmo_gdn_scan_roofline") < 100
    assert 0 < value("mfu_pct.spmd") < 100


def test_the_counters_say_the_negative_eigenvalues_are_in_play(runs):
    notes = next(json.loads(r)["notes"] for r in runs[1].stdout.splitlines()
                 if r.startswith('{"notes"'))
    stats = notes["beta_stats"]
    assert len(stats["beta_over_one_share"]) == 3       # the three layers
    assert stats["least_share_over_one"] >= 0.25
    assert all(1.5 < b < 2 for b in stats["beta_largest"])
    assert stats["chunks_per_sequence"] == 4            # 200 tokens of 64
