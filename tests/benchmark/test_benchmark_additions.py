"""The harness takes additions as data: a configuration, a traffic mix, a
per-layer reader and a ``workloads`` entry dropped into a temporary copy
are found by name and run in rehearsal, and no file that was there is
edited (every one is byte for byte what it was)."""

import hashlib
import json
import os
import shutil
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import rehearsal  # noqa: E402

ROOT = rehearsal.ROOT


def digests(root):
    out = {}
    for base, dirs, files in os.walk(os.path.join(root, "benchmark")):
        dirs[:] = [d for d in dirs if d not in ("out", "__pycache__")]
        for name in files:
            path = os.path.join(base, name)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("checkout"))
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    os.symlink(os.path.join(ROOT, "horovod_tpu"),
               os.path.join(root, "horovod_tpu"))
    before = digests(root)

    # a configuration: the same family at other sizes
    with open(os.path.join(root, "benchmark/configs/resnet50.json")) as fh:
        config = json.load(fh)
    config.update(depth=34, reduced=[], source="https://example.org/resnet34")
    config["tiny"] = dict(config["tiny"], depth=34)
    # limits are a configuration's own, read on the chip; this one has none
    config["limits"] = {"loss_rel": 0.05, "grad_norm_gap": 0.5,
                        "delta_norm_gap": 0.5, "why": "a test"}
    with open(os.path.join(root, "benchmark/configs/resnet34.json"),
              "w") as fh:
        json.dump(config, fh)
    # a traffic mix: another batch, in one process
    with open(os.path.join(root, "benchmark/traffic/spmd-b32.json"),
              "w") as fh:
        json.dump({"step_mode": "spmd", "launch": "inproc", "chips": 1,
                   "batch_per_chip": 32, "warmup_steps": 3,
                   "trace_steps": 3,
                   "tiny": {"batch_per_chip": 4, "warmup_steps": 1,
                            "trace_steps": 2, "world": 1}}, fh)
    # a per-layer metric: a reader of its own
    with open(os.path.join(root,
                           "benchmark/layer_metrics/enqueue_host_ms.py"),
              "w") as fh:
        fh.write('"""Median host time to enqueue one step."""\n'
                 "import statistics\n\n\n"
                 "def read(ctx):\n"
                 "    spans = ctx['trace']['host_spans'].get("
                 "'bench/enqueue')\n"
                 "    return statistics.median(spans) * 1e3 if spans "
                 "else None\n")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    bench["configs"].append({
        "name": "resnet34", "source": config["source"],
        "file": "benchmark/configs/resnet34.json", "reduced": [],
        "why": "a test"})
    bench["workloads"].append({
        "name": "resnet34-spmd-b32", "config": "resnet34",
        "traffic": "spmd-b32", "chips": 1, "why": "a test"})
    # the cell joins the rate its step mode reports, and with it every
    # per-layer metric that moves that rate and lists no cells
    rate = next(m for m in bench["end_to_end"]
                if m["name"] == "items_per_s_per_chip.spmd")
    rate["workloads"].append("resnet34-spmd-b32")
    # a suffixed name is read by the reader of its first part
    bench["per_layer"].append({
        "name": "enqueue_host_ms.spmd", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "step programs",
        "moves": rate["name"], "workloads": ["resnet34-spmd-b32"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh)
    return root, before


def test_the_added_cell_runs_and_reports_the_added_metric(copy):
    root, before = copy
    line = rehearsal.rehearse("resnet34-spmd-b32", 1, root=root)
    rehearsal.check_line(line, "resnet34-spmd-b32", 1, 1, root=root)
    assert line["metrics"]["enqueue_host_ms.spmd"]["value"] > 0
    assert line["metrics"]["device_step_ms.spmd"]["value"] > 0
    line = rehearsal.rehearse("resnet34-spmd-b32", 0, root=root)
    rehearsal.check_line(line, "resnet34-spmd-b32", 0, 1, root=root)
    after = digests(root)
    assert {k: after[k] for k in before} == before      # nothing edited
    assert sorted(set(after) - set(before)) == [
        "benchmark/configs/resnet34.json",
        "benchmark/layer_metrics/enqueue_host_ms.py",
        "benchmark/traffic/spmd-b32.json"]


def test_a_cell_of_the_copy_is_untouched_by_the_additions(copy):
    root, _ = copy
    assert rehearsal.metrics_of("resnet50-spmd-1c", "per_layer", root) == \
        rehearsal.metrics_of("resnet50-spmd-1c", "per_layer", ROOT)


@pytest.mark.parametrize("cell", ["resnet50-spmd-1c", "resnet50-eager-1c"])
def test_the_benchmark_alone_without_the_program_fails(tmp_path, cell):
    """A directory that holds only BENCHMARK.json and the files under
    ``paths``: no result, a non-zero exit."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    proc = rehearsal.run(["--workload", cell, "--seed", "1", "--seconds",
                          "1", "--trace", "0", "--rehearse"], root=root)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
