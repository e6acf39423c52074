"""BENCHMARK.json against the contract's rules that need no run, the
peaks table, and the jax-free comparison that decides ``correct``."""

import json
import math
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import cell as cells      # noqa: E402
from benchmark import compare            # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
    BENCH = json.load(fh)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
LINE = re.compile(r"^[^\t\n]{1,200}$")
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_and_sizes():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    assert 1 <= len(BENCH["configs"]) <= 24
    assert 1 <= len(BENCH["workloads"]) <= 24
    assert 1 <= len(BENCH["end_to_end"]) <= 16
    assert 1 <= len(BENCH["per_layer"]) <= 128
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10
    assert BENCH["command"][-1].startswith(tuple(BENCH["paths"]))
    assert all(LINE.match(word) for word in BENCH["command"])


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_config_entry(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"])
    assert LINE.match(entry["source"]) and LINE.match(entry["why"])
    assert entry["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
    assert all(NAME.match(k) for k in entry["reduced"])
    assert len(entry["reduced"]) <= 16
    config = cells.load_json(os.path.join(ROOT, entry["file"]))
    assert config["source"] == entry["source"]
    assert config["reduced"] == entry["reduced"]
    # never a width: sizes, head counts per token and the like stay published
    widths = re.compile(r"(hidden|intermediate|latent|state|proj\w*)_size"
                        r"|_dim$|_rank$|expan|per_tok|^width$")
    assert not [k for k in entry["reduced"] if widths.search(k)]
    assert any(w["config"] == entry["name"] for w in BENCH["workloads"])
    for key in ("family", "limits", "tiny", "dtype", "item", "deployment"):
        assert key in config, key


@pytest.mark.parametrize("entry", BENCH["workloads"],
                         ids=lambda w: w["name"])
def test_workload_entry(entry):
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    assert all(NAME.match(entry[k]) for k in ("name", "config", "traffic"))
    assert entry["chips"] in (1, 4) and LINE.match(entry["why"])
    cell = cells.load_cell(entry["name"])
    assert cell.mix["chips"] == entry["chips"]
    assert cell.mix["step_mode"] in ("spmd", "eager")
    assert cell.mix["launch"] in ("inproc", "torovodrun")
    for key in ("batch_per_chip", "warmup_steps", "trace_steps"):
        assert cell.sizes[key] >= 1
    assert "steps_per_sample" not in cell.sizes     # no knob smooths a tail
    # every cell: set-up, one more end-to-end metric, a per-layer metric
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in names, (m["name"], m["moves"])


def test_pairs_and_names_are_unique_and_four_chip_share():
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for group in (BENCH["configs"], BENCH["workloads"], METRICS):
        names = [e["name"] for e in group]
        assert len(set(names)) == len(names)
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 4)


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    end_to_end = metric in BENCH["end_to_end"]
    keys = {"name", "unit", "better", "source"} | (
        {"bound"} if end_to_end else {"layer", "moves"})
    assert set(metric) - {"workloads"} == keys
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in (("host_clock", "device_trace") if end_to_end
                                else ("device_trace", "program_span",
                                      "program_counter", "host_clock"))
    for cell in metric.get("workloads", []):
        assert cell in CELLS
    if end_to_end:
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        assert LINE.match(metric["layer"])
        assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}
        reader = cells.load_module("layer_metrics",
                                   cells.base(metric["name"]))
        assert callable(reader.read)
        if "roofline" in metric["name"]:
            assert metric["name"].endswith("_roofline")
            assert metric["unit"] == "%"


def test_files_under_paths_have_plain_names():
    plain = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for path in BENCH["paths"]:
        assert re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", path)
        for base, dirs, files in os.walk(os.path.join(ROOT, path)):
            dirs[:] = [d for d in dirs if d not in ("out", "__pycache__")]
            for name in files:
                rel = os.path.relpath(os.path.join(base, name), ROOT)
                assert plain.match(rel), rel


def test_setup_s_is_an_end_to_end_metric_of_every_cell():
    setup = next(m for m in BENCH["end_to_end"] if m["name"] == "setup_s")
    assert "workloads" not in setup and setup["bound"] <= 0.1


# ---------------------------------------------------------------- the peaks
def test_a_device_kind_missing_from_the_table_raises():
    with pytest.raises(KeyError, match="not in benchmark/peaks.json"):
        cells.peaks_for("TPU v9 imaginary")


def test_the_v5e_row_and_the_rehearsal_row():
    row = cells.peaks_for("TPU v5 lite")
    assert row["bf16_flops_per_s"] == 197e12
    assert row["hbm_bytes_per_s"] == 819e9 and "Google Cloud" in row["source"]
    with pytest.raises(KeyError):           # a placeholder never measures
        cells.peaks_for("cpu")
    assert cells.peaks_for("cpu", rehearse=True)["rehearsal"] is True


def test_an_unknown_workload_is_refused():
    with pytest.raises(SystemExit, match="no workload"):
        cells.load_cell("no-such-cell")


# ----------------------------------------------------- compare.py (no jax)
LIMITS = {"loss_rel": 0.01, "grad_norm_gap": 0.05, "delta_norm_gap": 0.3}
REFERENCE = {"losses": [[2.0, 1.9, 1.8], [2.1, 2.0, 1.9]],
             "grad_norms": {"a:2d": 1.0, "b:2d": 2.0, "c:4d": 1e-9, "v:1d": 1.0},
             "delta_norms": {"a:2d": 0.1, "b:2d": 0.2, "c:4d": 0.3, "v:1d": 1.0}}


def rank_record(rank, **changes):
    record = {"rank": rank, "first_losses": list(REFERENCE["losses"][rank]),
              "grad_norms": dict(REFERENCE["grad_norms"]),
              "delta_norms": dict(REFERENCE["delta_norms"]),
              "digest": "d0", "last_loss": 1.0, "params_changed": True}
    record.update(changes)
    return record


def failed_rows(records):
    correct, rows = compare.decide(records, REFERENCE, LIMITS)
    return correct, [name for name, _, _, ok in rows if not ok]


def test_compare_passes_what_equals_the_reference():
    assert failed_rows([rank_record(0), rank_record(1)]) == (True, [])


def test_norm_gap_takes_the_worst_leaf_against_the_median_floor():
    # leaf c is all but zero: its gap is measured against the median (1.0)
    norms = {"a:2d": 1.0, "b:2d": 2.1, "c:4d": 0.02, "v:1d": 1.0}
    gap, leaf = compare.norm_gap(norms, REFERENCE["grad_norms"])
    assert leaf == "b:2d" and gap == pytest.approx(0.05)
    gap, leaf = compare.norm_gap(dict(norms, **{"b:2d": 2.0, "c:4d": 0.2}),
                                 REFERENCE["grad_norms"])
    assert leaf == "c:4d" and gap == pytest.approx(0.2)


def test_vectors_are_not_held_leaf_by_leaf():
    norms = dict(REFERENCE["grad_norms"], **{"v:1d": 5.0})
    assert compare.norm_gap(norms, REFERENCE["grad_norms"]) == (0.0, "")
    assert compare.held("['fc']['w']:2d") and not compare.held("['b']:1d")


def test_vectors_are_held_kind_by_kind_where_the_limits_name_them():
    ref = {"['a']['bn']['scale']:1d": 3.0, "['b']['bn']['scale']:1d": 4.0,
           "['a']['bn']['bias']:1d": 1.0, "['fc']['w']:2d": 1.0}
    got = dict(ref, **{"['b']['bn']['scale']:1d": 0.0})
    assert compare.vector_gaps(got, ref) == {
        "bias": 0.0, "scale": pytest.approx(0.4)}       # 3 against 5
    assert compare.vector_gap(got, ref) == (pytest.approx(0.4), "scale")
    assert compare.vector_gap({"w:2d": 1.0}, {"w:2d": 1.0}) == (None, "")
    nan = dict(ref, **{"['a']['bn']['bias']:1d": math.nan})
    assert compare.vector_gap(nan, ref)[1] == "bias"
    # v, the one vector of REFERENCE, has lost its gradient
    wrong = rank_record(0, grad_norms=dict(REFERENCE["grad_norms"],
                                           **{"v:1d": 0.0}))
    correct, rows = compare.decide([wrong], REFERENCE, LIMITS)
    assert correct and ("vector_grad_norm_gap.v:1d", 1.0, "none", True) in rows
    correct, rows = compare.decide(
        [wrong], REFERENCE, dict(LIMITS, vector_grad_norm_gap=0.5))
    assert not correct
    assert [r[0] for r in rows if not r[3]] == ["vector_grad_norm_gap.v:1d"]


@pytest.mark.parametrize("changes, failing", [
    ({"first_losses": [2.0, 1.9, 1.9]}, "loss_rel.step3"),
    ({"grad_norms": {"a:2d": 1.2, "b:2d": 2.0, "c:4d": 1e-9, "v:1d": 1.0}},
     "grad_norm_gapa"),
    ({"grad_norms": {"a:2d": math.nan, "b:2d": 2.0, "c:4d": 0.0,
                     "v:1d": 1.0}}, "grad_norm_gapa"),
    # a step that returns its state unchanged: no change at all, gap 1
    ({"delta_norms": {"a:2d": 0.0, "b:2d": 0.0, "c:4d": 0.0, "v:1d": 0.0}},
     "delta_norm_gap"),
    ({"last_loss": math.inf}, "last_loss_finite"),
    ({"params_changed": False}, "params_changed"),
])
def test_compare_fails_on(changes, failing):
    correct, failed = failed_rows([rank_record(0, **changes)])
    assert not correct
    assert any(name.startswith(failing) for name in failed), failed


def test_ranks_that_hold_different_parameters_fail():
    correct, failed = failed_rows([rank_record(0),
                                   rank_record(1, digest="d1")])
    assert not correct and failed == ["ranks_hold_one_digest"]


def test_a_rank_is_held_to_its_own_shard_of_the_reference():
    wrong = rank_record(1, first_losses=REFERENCE["losses"][0])
    correct, failed = failed_rows([rank_record(0), wrong])
    assert not correct and failed[0].startswith("rank1.loss_rel")


def test_leaves_that_differ_from_the_reference_are_an_error():
    with pytest.raises(ValueError, match="leaves"):
        compare.norm_gap({"a:2d": 1.0}, REFERENCE["grad_norms"])
