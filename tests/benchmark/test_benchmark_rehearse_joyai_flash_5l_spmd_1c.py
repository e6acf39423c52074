"""``joyai_flash-5l-spmd-1c`` end to end with ``--rehearse``: the cell's own
control flow at the files' tiny sizes on the CPU, as a child process.  What
is held is what a CPU run can say: the contract's keys, the steps counted,
the counters, which metrics are read.  No clock time is held against
another (PERF.md section 7 (aa), hazard (f))."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import rehearsal  # noqa: E402

CELL, CHIPS = "joyai_flash-5l-spmd-1c", 1
# the attention kernels' share is the chip's alone: interpreted on the CPU
# a Pallas kernel leaves no kernel event (``rehearsal.CHIP_ONLY``'s reason)
KERNELS_ALONE = {"latent_flash_roofline"}
NEW = ("attn_latent_ms", "latent_proj_ms", "mtp_ms", "moe_ms.joyai",
       "mlp_ms.joyai", "head_ms.joyai", "latent_flash_roofline",
       "joyai_expert_matmul_roofline")


@pytest.fixture(scope="module")
def runs():
    """One run of each kind, same seed (past 32 signed bits).  Four
    seconds, so that a loaded machine still completes steps in the
    window."""
    return [rehearsal.run(["--workload", CELL, "--seed", "4294967350",
                           "--seconds", "4", "--trace", str(trace),
                           "--rehearse"], timeout=420) for trace in (0, 1)]


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_the_contracts_line(runs, trace, monkeypatch):
    monkeypatch.setattr(rehearsal, "CHIP_ONLY",
                        rehearsal.CHIP_ONLY | KERNELS_ALONE)
    line = rehearsal.last_line(runs[trace])
    rehearsal.check_line(line, CELL, trace, CHIPS)


def test_the_window_counts_whole_steps_of_one_sequence(runs):
    """Every step is one sequence of the tiny mix's 200 tokens: the rate
    times the window is a whole number of them, and what was attempted is
    what was counted."""
    for proc in runs:
        line = rehearsal.last_line(proc)
        rank = next(json.loads(r) for r in proc.stdout.splitlines()
                    if r.startswith('{"rank"'))
        assert line["attempted"] == rank["steps"] >= 1
        assert rank["compiles_in_window"] == 0 and line["failed"] == 0
    rank = next(json.loads(r) for r in runs[0].stdout.splitlines()
                if r.startswith('{"rank"'))
    rate = rehearsal.last_line(runs[0])["metrics"][
        "items_per_s_per_chip.spmd"]["value"]
    assert abs(rate * rank["window_s"] - 200 * rank["steps"]) < 1e-3 * 200


def test_the_same_seed_gives_the_same_first_steps(runs):
    rows = [[r for r in p.stderr.splitlines() if r.startswith("compare")
             and "last_loss" not in r and "digest" not in r] for p in runs]
    assert rows[0] and rows[0] == rows[1], [
        (p.returncode, p.stdout[-1500:], p.stderr[-1500:]) for p in runs]
    # the vectors' change is held here, the selection biases' in it
    assert any(r.split()[1].startswith("vector_delta_norm_gap")
               and r.split()[-2] != "none" for r in rows[0])


def test_the_eight_new_metrics_are_this_cells_and_are_read(runs):
    """The metrics this cell brings come from the program's named scopes:
    three through readers that were there, by their suffix, one through
    ``expert_matmul_roofline``'s under the cell's own name, three through
    readers of ``attn/latent``, ``attn/latent/proj`` and ``mtp``; the
    kernels' share is the chip's."""
    assert set(NEW) <= set(rehearsal.metrics_of(CELL, "per_layer"))
    for other in ("qwen3next-4l-spmd-1c", "laguna_s2_1-5l-spmd-1c",
                  "mistral7b-4l-spmd-1c"):
        assert not set(NEW) & set(rehearsal.metrics_of(other, "per_layer"))
    metrics = rehearsal.last_line(runs[1])["metrics"]
    for name in set(NEW) - KERNELS_ALONE:
        assert metrics[name]["value"] > 0, name
    assert not KERNELS_ALONE & set(metrics)
    assert (metrics["latent_proj_ms"]["value"]
            < metrics["attn_latent_ms"]["value"])
    assert metrics["joyai_expert_matmul_roofline"]["value"] < 100
    assert 0 < metrics["mfu_pct.spmd"]["value"] < 100


def test_the_counters_say_what_the_batch_exercises(runs):
    notes = next(json.loads(r)["notes"] for r in runs[1].stdout.splitlines()
                 if r.startswith('{"notes"'))
    load = notes["expert_load"]
    # 200 tokens, top-3, one expert layer and the module's; 8 of 16 held
    assert load["assignments"] == 200 * 3 * 2
    assert 0 < load["assignments_held"] < load["assignments"]
    assert load["assignments_dropped"] == 0
    assert notes["expert_matmul_bound"] in ("compute", "memory")
    # the step's attention call sites: the tiny preset turns the kernels
    # on (interpreted here), two layers and the module, none fell to the
    # plain path
    paths = notes["attention_paths"]
    assert paths == load["attention"]
    assert paths["latent_flash"] == 3 and paths["latent_plain"] == 0
    # what the three followed steps made of the two layers' 32 biases
    bias = load["router_bias"]
    assert 0 < bias["raised"] + bias["lowered"] <= 32
    assert 0.0009 < bias["largest_move"] < 0.0031
