"""``nemotron3super-11l-spmd-1c`` end to end with ``--rehearse``: the cell's own control
flow at the files' tiny sizes on the CPU, as a child process."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import rehearsal  # noqa: E402

CELL, CHIPS = "nemotron3super-11l-spmd-1c", 1
# the attention kernels' share is the chip's alone: interpreted on the CPU
# a Pallas kernel leaves no kernel event (``rehearsal.CHIP_ONLY``'s reason)
NEW = ("ssm_mixer_ms", "ssm_scan_ms", "ssm_scan_roofline", "nemotron_moe_ms",
       "nemotron_expert_matmul_roofline", "nemotron_flash_roofline")


@pytest.fixture(scope="module")
def runs():
    """One run of each kind, same seed (past 32 signed bits).  Four
    seconds, so that a loaded machine still completes steps in the
    window."""
    return [rehearsal.run(["--workload", CELL, "--seed", "4294967301",
                           "--seconds", "4", "--trace", str(trace),
                           "--rehearse"], timeout=420) for trace in (0, 1)]


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_the_contracts_line(runs, trace, monkeypatch):
    monkeypatch.setattr(rehearsal, "CHIP_ONLY",
                        rehearsal.CHIP_ONLY | {"nemotron_flash_roofline"})
    line = rehearsal.last_line(runs[trace])
    rehearsal.check_line(line, CELL, trace, CHIPS)


def test_the_same_seed_gives_the_same_first_steps(runs):
    rows = [[r for r in p.stdout.splitlines() if r.startswith("compare")
             and "last_loss" not in r and "digest" not in r] for p in runs]
    assert rows[0] and rows[0] == rows[1], [
        (p.returncode, p.stdout[-1500:], p.stderr[-1500:]) for p in runs]


def test_the_six_new_metrics_are_this_cells_and_are_read(runs):
    """The metrics this cell brings come from the program's named scopes:
    the recurrence is a part of the mixer, the mixer and the expert layer
    are parts of one step."""
    assert set(NEW) <= set(rehearsal.metrics_of(CELL, "per_layer"))
    for other in ("qwen3next-4l-spmd-1c", "olmohybrid-4l-spmd-1c"):
        assert not set(NEW) & set(rehearsal.metrics_of(other, "per_layer"))
    metrics = rehearsal.last_line(runs[1])["metrics"]
    value = lambda name: metrics[name]["value"]
    assert 0 < value("ssm_scan_ms") < value("ssm_mixer_ms")
    assert value("nemotron_moe_ms") > 0
    assert value("ssm_mixer_ms") + value("nemotron_moe_ms") < value(
        "device_step_ms.spmd")
    assert 0 < value("ssm_scan_roofline") < 100
    assert 0 < value("nemotron_expert_matmul_roofline") < 100
    assert 0 < value("mfu_pct.spmd") < 100


def test_the_counters_say_what_the_batch_exercises(runs):
    notes = next(json.loads(r)["notes"] for r in runs[1].stdout.splitlines()
                 if r.startswith('{"notes"'))
    load = notes["expert_load"]
    assert load["assignments"] == 5 * 200 * 3       # five expert layers
    assert load["assignments_dropped"] == 0
    assert 0.15 < load["held_share"] < 0.35         # 4 of 16 held
    held = load["tokens_per_held_expert"]
    assert 0 < held["least"] <= held["mean"] <= held["most"] <= 200
    stats = notes["decay_stats"]
    assert len(stats["chunk_decay_over_0.01_share"]) == 5   # Mamba layers
    assert 0.1 <= stats["least_share_carried"] < 1
    assert all(0 < a < 0.5 for a in stats["decay_least"])
    assert all(0.99 < a < 1 for a in stats["decay_most"])
    assert stats["chunks_per_sequence"] == 7        # 200 tokens of 32
