"""``conv_ms``, the per-layer reader that PR 39 brings for the three hybrid
cells: its ``BENCHMARK.json`` entry; on a trace of a program that has no
such scope (the parent commit's reader-less line, any other family's) it
returns nothing and raises nothing; it sums what the family's scope table
gives to ``gdn/conv`` or ``ssm/conv`` — XLA's fusions in the parent's
program, the Pallas kernels' custom calls in the change's."""

import json
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
HYBRID = ["qwen3next-4l-spmd-1c", "olmohybrid-4l-spmd-1c",
          "nemotron3super-11l-spmd-1c"]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
    BENCH = json.load(fh)


def test_the_entry_is_the_last_and_lists_the_three_hybrid_cells():
    assert BENCH["per_layer"][-1] == {
        "name": "conv_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "recurrent layers",
        "moves": "items_per_s_per_chip.spmd", "workloads": HYBRID}


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_the_cells_that_report_it_are_the_hybrid_ones(workload):
    cell = cells.load_cell(workload)
    names = {m["name"] for m in cell.per_layer}
    assert ("conv_ms" in names) == (workload in HYBRID)
    if workload in HYBRID:
        family = cells.load_module("families", cell.config["family"])
        reader = cells.load_module("layer_metrics", "conv_ms")
        # the family's table of scopes holds the one its mixer has
        assert set(reader.SCOPES) & set(family.SCOPES)
        assert "items_per_s_per_chip.spmd" in {
            m["name"] for m in cell.end_to_end}


@pytest.fixture(scope="module")
def another_programs_trace(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "recorded.xplane.pb"
    with lzma.open(RECORDED) as src, open(path, "wb") as dst:
        dst.write(src.read())
    return dict(tr.reduce_file(str(path)), path=str(path), steps=5)


@pytest.mark.parametrize("kernel", [
    None, {"flops_per_step": 1e12, "bytes_per_step": 1e9},
    {"scopes": {}}, {"scopes": {"fusion.1": "gdn/scan"}},
], ids=["no-kernel-record", "attention-counts", "an-empty-table",
        "another-scope"])
def test_it_finds_nothing_in_another_programs_trace(another_programs_trace,
                                                    kernel):
    reader = cells.load_module("layer_metrics", "conv_ms")
    assert reader.read({"trace": another_programs_trace, "peaks": PEAKS,
                        "record": {"kernel": kernel}}) is None
    assert reader.read({"trace": None, "peaks": PEAKS,
                        "record": {"kernel": kernel}}) is None


@pytest.mark.parametrize("scope", ["gdn/conv", "ssm/conv"])
def test_it_sums_fusions_and_kernels_under_the_scope(monkeypatch, scope):
    """Two steps: a fusion of 4 ms and the kernels' 3 + 5 ms of which 1 ms
    overlap the fusion, beside 20 ms under another scope."""
    events = {"/device:TPU:0": [
        ("pad_convert_fusion.2", 0.000, 0.004),
        ("causal_conv_fwd.3", 0.003, 0.006),
        ("causal_conv_bwd.1", 0.010, 0.015), ("fusion.9", 0.02, 0.04),
        ("pad_convert_fusion.2", 0.100, 0.104),
        ("causal_conv_fwd.3", 0.103, 0.106),
        ("causal_conv_bwd.1", 0.110, 0.115), ("fusion.9", 0.12, 0.14)]}
    monkeypatch.setattr(trace_scopes, "device_events", lambda path: events)
    ctx = {"trace": {"path": "x", "steps": 2}, "peaks": PEAKS,
           "record": {"kernel": {"scopes": {
               "pad_convert_fusion.2": scope, "causal_conv_fwd.3": scope,
               "causal_conv_bwd.1": scope, "fusion.9": "gdn/scan"}}}}
    reader = cells.load_module("layer_metrics", "conv_ms")
    assert reader.read(ctx) == pytest.approx(11.0)


def test_the_scope_table_takes_a_kernels_custom_call():
    """A Pallas kernel is one ``custom-call`` instruction whose metadata
    names the scope it was traced under, forward, recomputed and
    backward, as a fusion's does."""
    hlo = "\n".join([
        '  %causal_conv_fwd.3 = bf16[2,8192,8192]{2,1,0} custom-call(%a, %b)'
        ', custom_call_target="tpu_custom_call", backend_config={"x": 1}, '
        'metadata={op_name="jit(step)/forward/jvp(gdn/conv)/causal_conv_fwd'
        '/pallas_call" stack_frame_id=4}',
        '  %causal_conv_bwd.1 = (bf16[2,8192,8192]{2,1,0}, f32[2,5,8,8192])'
        ' custom-call(%a, %a, %c, %b), custom_call_target="tpu_custom_call",'
        ' metadata={op_name="jit(step)/backward/transpose(jvp(gdn/conv))/'
        'causal_conv_bwd/pallas_call"}',
        '  %causal_conv_fwd.4 = bf16[1,8192,10240]{2,1,0} custom-call(%a), '
        'custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/'
        'backward/rematted_computation/ssm/conv/causal_conv_fwd/pallas_call"}',
        '  %fusion.7 = f32[8]{0} fusion(%a), kind=kLoop, metadata={op_name='
        '"jit(step)/forward/jvp(gdn/scan)/mul"}'])
    assert trace_scopes.within(("gdn/conv", "ssm/conv"), hlo) == {
        "causal_conv_fwd.3": "gdn/conv", "causal_conv_bwd.1": "gdn/conv",
        "causal_conv_fwd.4": "ssm/conv"}
