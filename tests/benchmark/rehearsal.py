"""Shared by the rehearsal tests: run ``benchmark/run.py`` as a child
process with an environment of its own (``tests/conftest.py`` gives the
test process eight virtual devices, a cell wants its own world) and a time
limit, and check its last line against the contract.  Each cell has a test
file of its own so that the suite's workers take the cells in parallel."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}
# Interpreted on the CPU a Pallas kernel is plain XLA operations: the trace
# holds no kernel event, the reader finds nothing and returns nothing.
CHIP_ONLY = {"flash_roofline"}


def child_env():
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "PYTHONPATH", "BENCH_RUN")
           and not k.startswith(("HOROVOD_", "HVD_"))}
    env["JAX_PLATFORMS"] = "cpu"
    return env


def run(args, root=ROOT, timeout=240, env=None):
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py")] + args,
        cwd=root, env=env or child_env(), timeout=timeout,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)


def last_line(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def rehearse(workload, trace, seed=5, seconds=1.0, root=ROOT):
    return last_line(run(["--workload", workload, "--seed", str(seed),
                          "--seconds", str(seconds), "--trace", str(trace),
                          "--rehearse"], root=root))


def metrics_of(workload, kind, root=ROOT):
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    # a metric lists its cells; without a list an end-to-end metric is
    # every cell's, a per-layer one goes where the metric it moves goes
    reported = {m["name"]: m["unit"] for m in bench["end_to_end"]
                if workload in m.get("workloads", [workload])}
    if kind == "end_to_end":
        return reported
    return {m["name"]: m["unit"] for m in bench["per_layer"]
            if (workload in m["workloads"] if "workloads" in m
                else m["moves"] in reported)}


def check_line(line, workload, trace, chips, root=ROOT):
    """The contract's keys, and exactly the metrics BENCHMARK.json names
    for this cell, each a number with its unit."""
    assert RESULT_KEYS <= set(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    want = metrics_of(workload, "per_layer" if trace else "end_to_end", root)
    assert set(line["metrics"]) | (CHIP_ONLY & set(want)) == set(want), (
        sorted(line["metrics"]), sorted(want))
    for name, m in line["metrics"].items():
        assert m["unit"] == want[name]
        assert isinstance(m["value"], float) and m["value"] == m["value"]
    device = line["device"]
    assert DEVICE_KEYS <= set(device)
    assert device["platform"] == "cpu" and device["count"] == chips
    assert device["memory_peak_bytes"] > 0
    if trace:
        assert device["busy_s"] > 0 and device["window_s"] >= device["busy_s"]
        for key in ("device_ops", "idle_gaps"):
            rows = line["breakdown"][key]
            assert 1 <= len(rows) <= 10
            assert all(isinstance(n, str) and s >= 0 for n, s in rows)
    else:
        assert "breakdown" not in line
        assert line["metrics"]["setup_s"]["value"] > 0
        rates = [m for name, m in line["metrics"].items()
                 if name.startswith("items_per_s_per_chip")]
        assert len(rates) == 1 and rates[0]["value"] > 0
