"""``ouro2_6b-16l-spmd-1c`` end to end with ``--rehearse``: the cell's own control flow
at the files' tiny sizes on the CPU, as a child process."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import rehearsal  # noqa: E402

CELL, CHIPS = "ouro2_6b-16l-spmd-1c", 1


@pytest.fixture(scope="module")
def runs():
    """One run of each kind, same seed (past 32 signed bits).  Four
    seconds, so that a loaded machine still completes steps in the
    window."""
    return [rehearsal.run(["--workload", CELL, "--seed", "4294967301",
                           "--seconds", "4", "--trace", str(trace),
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


def test_the_cell_reports_what_reaches_every_spmd_cell(runs):
    """The cell brings no per-layer entry of its own (PERF.md section 7):
    it reports the metrics that reach it by what they move, the whole
    step's time and its share of the peak among them."""
    mine = set(rehearsal.metrics_of(CELL, "per_layer"))
    assert {"device_step_ms.spmd", "mfu_pct.spmd",
            "device_idle_pct.spmd"} <= mine
    with open(os.path.join(rehearsal.ROOT, "BENCHMARK.json")) as fh:
        listed = [m["name"] for m in json.load(fh)["per_layer"]
                  if CELL in m.get("workloads", ())]
    assert listed == []
    metrics = rehearsal.last_line(runs[1])["metrics"]
    assert metrics["device_step_ms.spmd"]["value"] > 0
    assert 0 < metrics["mfu_pct.spmd"]["value"] < 100


def test_the_counters_say_every_pass_keeps_a_share(runs):
    for run in runs:                    # printed at set-up, traced or not
        stats = next(json.loads(r)["exit_stats"]
                     for r in run.stdout.splitlines()
                     if r.startswith('{"exit_stats"'))
        assert len(stats["exit_p_mean"]) == len(stats["nll_mean"]) == 4
        assert abs(sum(stats["exit_p_mean"]) - 1.0) < 1e-4
        assert stats["least_exit_p_mean"] >= 0.05
        assert 0 < stats["exit_entropy_mean"] < 1.3863      # log 4
        assert stats["layer_applications_per_step"] == 2 * 4
