"""The two reader files that ``jamba2_3b-14l-spmd-1c`` brings, and the six
readers it borrows by their suffix: on a trace of a program that has none
of their spans or kernels (the parent commit's, any other family's) each
returns nothing and raises nothing; the scan's share is its least bytes'
time over the device time under ``ssm/scan``; the attention kernels' share
divides by the flash kernels' time alone, beside the convolution's and the
scan's own custom calls."""

import lzma
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import cell as cells                             # noqa: E402
from benchmark import trace_reduce as tr                        # noqa: E402
from benchmark import trace_scopes                              # noqa: E402

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "mistral7b-4l-spmd-1c.xplane.pb.xz")
NEW = ("ssm_mixer_ms.jamba", "ssm_scan_ms.jamba", "conv_ms.jamba",
       "mlp_ms.jamba", "attn_ms.jamba", "head_ms.jamba",
       "selective_scan_roofline", "jamba_flash_roofline")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def reader_of(name):
    return cells.load_module("layer_metrics", cells.base(name))


@pytest.fixture(scope="module")
def another_programs_trace(tmp_path_factory):
    """A recorded device trace of ``mistral7b-4l-spmd-1c`` (an older
    program: its kernels' instructions are ``jvp__.N``)."""
    path = tmp_path_factory.mktemp("trace") / "recorded.xplane.pb"
    with lzma.open(RECORDED) as src, open(path, "wb") as dst:
        dst.write(src.read())
    return dict(tr.reduce_file(str(path)), path=str(path), steps=5)


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("kernel", [
    None,                                   # a family that counts nothing
    {"flops_per_step": 1e12, "bytes_per_step": 1e9},    # llama's record
    {"flops_per_step": 1e12, "bytes_per_step": 1e9, "scopes": {},
     "selective_scan": {"flops_per_step": 0.0, "bytes_per_step": 1e9}},
], ids=["no-kernel-record", "attention-counts", "this-familys-counts"])
def test_a_reader_finds_nothing_in_another_programs_trace(
        another_programs_trace, name, kernel):
    reader = reader_of(name)
    ctx = {"trace": another_programs_trace, "peaks": PEAKS,
           "record": {"kernel": kernel}}
    assert reader.read(ctx) is None
    assert reader.read({"trace": None, "peaks": PEAKS,
                        "record": {"kernel": kernel}}) is None


def test_every_new_entry_is_the_cells_alone_and_has_a_reader():
    bench = cells.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert entries[name]["workloads"] == ["jamba2_3b-14l-spmd-1c"]
        assert entries[name]["moves"] == "items_per_s_per_chip.spmd"
        assert callable(reader_of(name).read)
    # appended after everything that was there, in the issue's order
    assert tuple(list(entries)[-len(NEW):]) == NEW


def test_the_scans_share_is_its_bytes_time_over_the_scopes(monkeypatch):
    """Two steps; under ``ssm/scan`` a forward kernel, its recomputation
    and the backward kernel, 40 ms a step; the least bytes take 10 ms at
    the HBM's peak: 25 %, bound by memory (the record counts no matrix
    operation)."""
    events = {"/device:TPU:0": [
        ("selective_scan_fwd.1", 0.00, 0.01), ("fusion.7", 0.01, 0.02),
        ("selective_scan_fwd.2", 0.02, 0.03),
        ("selective_scan_bwd.1", 0.03, 0.05), ("fusion.9", 0.05, 0.09),
        ("selective_scan_fwd.1", 0.10, 0.11),
        ("selective_scan_fwd.2", 0.12, 0.13),
        ("selective_scan_bwd.1", 0.13, 0.15)]}
    monkeypatch.setattr(trace_scopes, "device_events", lambda path: events)
    scopes = {"selective_scan_fwd.1": "ssm/scan",
              "selective_scan_fwd.2": "ssm/scan",
              "selective_scan_bwd.1": "ssm/scan", "fusion.7": "ssm/proj",
              "fusion.9": "mlp"}
    ctx = {"trace": {"path": "x", "steps": 2}, "peaks": PEAKS,
           "record": {"kernel": {
               "scopes": scopes,
               "selective_scan": {"flops_per_step": 0.0,
                                  "bytes_per_step": 819e9 * 0.010},
               "counters": {"decay_stats": {"least_share": 0.08},
                            "selective_scan": {"kernel": 26, "plain": 0}}}}}
    reader = cells.load_module("layer_metrics", "selective_scan_roofline")
    assert reader.read(ctx) == pytest.approx(100.0 * 0.010 / 0.040)
    assert ctx["notes"]["selective_scan_bound"] == "memory"
    assert ctx["notes"]["selective_scan_paths"] == {"kernel": 26, "plain": 0}
    assert reader_of("ssm_scan_ms.jamba").read(ctx) == pytest.approx(40.0)
    assert reader_of("ssm_mixer_ms.jamba").read(ctx) == pytest.approx(45.0)
    assert reader_of("mlp_ms.jamba").read(ctx) == pytest.approx(20.0)
    assert ctx["notes"]["decay_stats"] == {"least_share": 0.08}


def test_the_attention_share_divides_by_the_flash_kernels_alone(monkeypatch):
    """Two steps of 10 ms of flash kernels beside 30 ms of the
    convolution's and the scan's kernels, which are ``tpu_custom_call``
    events too: the share is the least time over the 10 ms."""
    events = {"/device:TPU:0": [
        ("flash_fwd.3", 0.000, 0.004), ("jvp_flash_bwd_dq_.1", 0.004, 0.007),
        ("flash_bwd_dkv.1", 0.006, 0.010),
        ("causal_conv_fwd.4", 0.01, 0.02),
        ("selective_scan_bwd.2", 0.02, 0.04), ("fusion.12", 0.04, 0.05),
        ("flash_fwd.3", 0.100, 0.104), ("jvp_flash_bwd_dq_.1", 0.104, 0.107),
        ("flash_bwd_dkv.1", 0.106, 0.110),
        ("selective_scan_fwd.2", 0.11, 0.14),
    ]}
    monkeypatch.setattr(trace_scopes, "device_events", lambda path: events)
    reader = cells.load_module("layer_metrics", "jamba_flash_roofline")
    ctx = {"trace": {"path": "x", "steps": 2, "custom_call_s": 0.08},
           "peaks": PEAKS,
           "record": {"kernel": {"flops_per_step": 197e12 * 0.002,
                                 "bytes_per_step": 819e9 * 0.001}}}
    assert reader.read(ctx) == pytest.approx(100.0 * 0.002 / 0.010)
    assert ctx["notes"]["flash_bound"] == "compute"
    # the reader of a cell whose only custom calls are the flash kernels
    # divides by all of them
    other = cells.load_module("layer_metrics", "flash_roofline").read(ctx)
    assert other == pytest.approx(100.0 * 0.002 / 0.040)
