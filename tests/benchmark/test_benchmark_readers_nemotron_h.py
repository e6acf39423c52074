"""The six per-layer readers that ``nemotron3super-11l-spmd-1c`` brings: on
a trace of a program that has none of their spans or kernels (the parent
commit's, any other family's) each returns nothing and raises nothing; the
attention kernels' share divides by the flash kernels' time alone."""

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
NEW = ("ssm_mixer_ms", "ssm_scan_ms", "ssm_scan_roofline", "nemotron_moe_ms",
       "nemotron_expert_matmul_roofline", "nemotron_flash_roofline")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


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
     "ssm_scan": {"flops_per_step": 1e12, "bytes_per_step": 1e9},
     "experts": {"flops_per_step": 1e12, "bytes_per_step": 1e9}},
], ids=["no-kernel-record", "attention-counts", "this-familys-counts"])
def test_a_reader_finds_nothing_in_another_programs_trace(
        another_programs_trace, name, kernel):
    reader = cells.load_module("layer_metrics", name)
    ctx = {"trace": another_programs_trace, "peaks": PEAKS,
           "record": {"kernel": kernel}}
    assert reader.read(ctx) is None
    assert reader.read({"trace": None, "peaks": PEAKS,
                        "record": {"kernel": kernel}}) is None


def test_the_attention_share_divides_by_the_flash_kernels_alone(monkeypatch):
    """Two steps of 10 ms of flash kernels (two of them overlap) beside 30
    ms of grouped products, which are ``tpu_custom_call`` events too: the
    share is the least time over the 10 ms."""
    events = {"/device:TPU:0": [
        ("flash_fwd.3", 0.000, 0.004), ("jvp_flash_bwd_dq_.1", 0.004, 0.007),
        ("flash_bwd_dkv.1", 0.006, 0.010), ("ragged-dot-none.4", 0.01, 0.04),
        ("fusion.12", 0.04, 0.05),
        ("flash_fwd.3", 0.100, 0.104), ("jvp_flash_bwd_dq_.1", 0.104, 0.107),
        ("flash_bwd_dkv.1", 0.106, 0.110), ("ragged-dot-none.4", 0.11, 0.14),
    ]}
    monkeypatch.setattr(trace_scopes, "device_events", lambda path: events)
    reader = cells.load_module("layer_metrics", "nemotron_flash_roofline")
    ctx = {"trace": {"path": "x", "steps": 2, "custom_call_s": 0.08},
           "peaks": PEAKS,
           "record": {"kernel": {"flops_per_step": 197e12 * 0.002,
                                 "bytes_per_step": 819e9 * 0.001}}}
    assert reader.read(ctx) == pytest.approx(100.0 * 0.002 / 0.010)
    assert ctx["notes"]["flash_bound"] == "compute"
    # the reader every other cell uses divides by all custom calls
    other = cells.load_module("layer_metrics", "flash_roofline").read(ctx)
    assert other == pytest.approx(100.0 * 0.002 / 0.040)


def test_the_expert_layers_time_takes_the_latents_scope(monkeypatch):
    events = {"/device:TPU:0": [
        ("fusion.1", 0.00, 0.01), ("fusion.2", 0.01, 0.03),
        ("ragged-dot-none.1", 0.03, 0.04), ("fusion.3", 0.04, 0.08)]}
    monkeypatch.setattr(trace_scopes, "device_events", lambda path: events)
    ctx = {"trace": {"path": "x", "steps": 1}, "peaks": PEAKS,
           "record": {"kernel": {
               "scopes": {"fusion.1": "moe/latent", "fusion.2": "moe/route",
                          "fusion.3": "moe/shared"},
               "counters": {"expert_load": {"assignments_dropped": 0},
                            "decay_stats": {"least_share_carried": 0.2}}}}}
    mine = cells.load_module("layer_metrics", "nemotron_moe_ms")
    assert mine.read(ctx) == pytest.approx(40.0)    # latent, route, kernel
    assert cells.load_module("layer_metrics", "moe_ms").read(
        dict(ctx, notes={})) == pytest.approx(30.0)
    assert ctx["notes"]["expert_load"] == {"assignments_dropped": 0}
    cells.load_module("layer_metrics", "ssm_mixer_ms").read(ctx)
    assert ctx["notes"]["decay_stats"] == {"least_share_carried": 0.2}
