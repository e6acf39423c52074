"""``jamba2_3b-14l-spmd-1c`` end to end with ``--rehearse``: the cell's own control
flow at the files' tiny sizes on the CPU, as a child process."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import rehearsal  # noqa: E402

CELL, CHIPS = "jamba2_3b-14l-spmd-1c", 1
# the attention kernels' share is the chip's alone: interpreted on the CPU
# a Pallas kernel leaves no kernel event (``rehearsal.CHIP_ONLY``'s reason)
NEW = ("ssm_mixer_ms.jamba", "ssm_scan_ms.jamba", "conv_ms.jamba",
       "mlp_ms.jamba", "attn_ms.jamba", "head_ms.jamba",
       "selective_scan_roofline", "jamba_flash_roofline")


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
                        rehearsal.CHIP_ONLY | {"jamba_flash_roofline"})
    line = rehearsal.last_line(runs[trace])
    rehearsal.check_line(line, CELL, trace, CHIPS)


def test_the_same_seed_gives_the_same_first_steps(runs):
    rows = [[r for r in p.stderr.splitlines() if r.startswith("compare")
             and "last_loss" not in r and "digest" not in r] for p in runs]
    assert rows[0] and rows[0] == rows[1], [
        (p.returncode, p.stdout[-1500:], p.stderr[-1500:]) for p in runs]


def test_the_compared_numbers_are_the_lines_last_key_and_stderrs_last_lines(
        runs):
    for proc in runs:
        line = rehearsal.last_line(proc)
        assert list(line)[-1] == "compared"
        rows = [r for r in proc.stderr.strip().splitlines()][-len(
            line["compared"]):]
        assert all(r.startswith("compare") for r in rows)
        assert [r.split()[1] for r in rows] == list(line["compared"])
        # the tied matrix is one leaf and is held as one
        assert not any("lm_head" in name for name in line["compared"])


def test_the_eight_new_metrics_are_this_cells_and_are_read(runs):
    """The metrics this cell brings come from the program's named scopes
    through readers that were there, by their suffix: the scan and the
    convolution are parts of the mixer; the mixer, the MLP, the attention
    layer and the head are parts of one step."""
    assert set(NEW) <= set(rehearsal.metrics_of(CELL, "per_layer"))
    for other in ("nemotron3super-11l-spmd-1c", "olmohybrid-4l-spmd-1c",
                  "ouro2_6b-16l-spmd-1c"):
        assert not set(NEW) & set(rehearsal.metrics_of(other, "per_layer"))
    metrics = rehearsal.last_line(runs[1])["metrics"]
    value = lambda name: metrics[name]["value"]
    assert 0 < value("ssm_scan_ms.jamba") < value("ssm_mixer_ms.jamba")
    assert 0 < value("conv_ms.jamba") < value("ssm_mixer_ms.jamba")
    parts = sum(value(n) for n in ("ssm_mixer_ms.jamba", "mlp_ms.jamba",
                                   "attn_ms.jamba", "head_ms.jamba"))
    assert min(value("mlp_ms.jamba"), value("attn_ms.jamba"),
               value("head_ms.jamba")) > 0
    assert parts < value("device_step_ms.spmd")
    assert 0 < value("selective_scan_roofline") < 100
    assert 0 < value("mfu_pct.spmd") < 100


def test_the_counter_says_what_the_batch_exercises(runs):
    notes = next(json.loads(r)["notes"] for r in runs[1].stdout.splitlines()
                 if r.startswith('{"notes"'))
    stats = notes["decay_stats"]
    assert len(stats["token_decay_under_0.5_share"]) == 3   # Mamba layers
    assert 0.05 <= stats["least_share"] <= stats["most_share"] <= 0.95
    assert all(0 < a < 1e-3 for a in stats["delta_least"])
    assert all(0.1 < a < 10 for a in stats["delta_most"])
    assert notes["selective_scan_bound"] == "memory"
    # no TPU here: the call sites traced for the step kept the plain path
    paths = notes["selective_scan_paths"]
    assert paths["kernel"] == 0 and paths["plain"] >= 1
