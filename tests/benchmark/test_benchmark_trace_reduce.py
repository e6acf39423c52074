"""``benchmark/trace_reduce.py``: the interval arithmetic on small made-up
timelines with known answers, and the whole reduction on a trace recorded
on the chip in PR 24 (five steps of ``mistral7b-4l-spmd-1c``, TPU v5 lite,
kept xz-compressed)."""

import lzma
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import trace_reduce as tr     # noqa: E402

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "mistral7b-4l-spmd-1c.xplane.pb.xz")


# ------------------------------------------------------------- arithmetic
def test_union_merges_overlaps_and_drops_empty_intervals():
    assert tr.union([(3, 4), (0, 1), (0.5, 2), (5, 5), (4, 4.5)]) == [
        [0, 2], [3, 4.5]]
    assert tr.length(tr.union([(0, 1), (0.5, 2), (3, 4)])) == 3


def test_subtract_leaves_what_the_cover_does_not_reach():
    assert tr.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == [
        (0, 1), (2, 4), (6, 9)]
    assert tr.subtract([(0, 1), (2, 3)], []) == [(0, 1), (2, 3)]
    assert tr.subtract([(0, 1)], [(0, 1)]) == []
    assert tr.subtract([(0, 2), (3, 5)], [(1, 4)]) == [(0, 1), (4, 5)]


def test_clip():
    assert tr.clip([(0, 2), (3, 5), (6, 7)], 1, 4) == [(1, 2), (3, 4)]


@pytest.mark.parametrize("text, label, opcode, target", [
    ("%fusion.15 = (bf16[4096,32000]{1,0:T(8,128)(2,1)}, bf16[4]{0}) "
     "fusion(bf16[4096,32000]{1,0} %p), kind=kOutput, calls=%fc.18",
     "fusion.15 bf16[4096,32000]", "fusion", None),
    ('%jvp__.4 = bf16[32,4096,128]{2,1,0} custom-call(bf16[32,4096,128]{2,1,0}'
     ' %x), custom_call_target="tpu_custom_call", operand_layout={}',
     "jvp__.4 tpu_custom_call bf16[32,4096,128]", "custom-call",
     "tpu_custom_call"),
    ('%custom-call.46 = bf16[4096,1024]{1,0} custom-call(bf16[1024,1024]{1,0}'
     ' %slice-done.152), custom_call_target="ConcatBitcast"',
     "custom-call.46 ConcatBitcast bf16[4096,1024]", "custom-call",
     "ConcatBitcast"),
    ("%all-reduce-start.3 = f32[1024]{0} all-reduce-start(f32[1024]{0} %g), "
     "replica_groups={}", "all-reduce-start.3 f32[1024]", "all-reduce-start",
     None),
    # a fusion that only CONSUMES a kernel's result is no kernel
    ("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %custom-call.5), kind=kLoop",
     "fusion.3 f32[8]", "fusion", None),
    ("%iota.1 = s32[128,1,1]{0,2,1} iota(), iota_dimension=0",
     "iota.1 s32[128,1,1]", "iota", None),
    ("dot_general.72", "dot_general.72", "dot_general", None),
    ("all-reduce.1", "all-reduce.1", "all-reduce", None),
])
def test_describe_reads_an_operations_name(text, label, opcode, target):
    assert tr.describe(text) == (label, opcode, target)


def timeline():
    """One device, window [0, 10].  Compute 0-2 and 3-6, an all-reduce 5-8
    (5-6 under compute, 6-8 exposed), a kernel 8-9; idle 2-3 and 9-10."""
    kernel = ('%k.1 = f32[8]{0} custom-call(f32[8]{0} %x), '
              'custom_call_target="tpu_custom_call"')
    ops = [("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop", 0, 2),
           ("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop", 3, 6),
           ("%all-reduce.1 = f32[8]{0} all-reduce(f32[8]{0} %g)", 5, 8),
           (kernel, 8, 9)]
    host = [("bench/traced_window", 0, 10, "main"),
            ("bench/step", 0, 4, "main"), ("bench/update", 1.5, 3.5, "main"),
            ("bench/step", 4, 10, "main"), ("bench/drain", 8.5, 10, "main"),
            ("other thread", 0, 10, "worker")]
    return {"/device:TPU:0": ops}, host


def test_reduce_events_on_a_known_timeline():
    r = tr.reduce_events(*timeline())
    assert r["window_s"] == 10 and r["devices"] == 1
    assert r["busy_s"] == 8 and r["idle_pct"] == pytest.approx(20.0)
    assert r["collective_s"] == 3
    assert r["collective_exposed_s"] == 2      # 6-8: nothing else ran
    assert r["custom_call_s"] == 1
    assert r["device_ops"][0] == ["fusion.2 f32[8]", 3]
    assert dict(map(tuple, r["device_ops"]))["all-reduce.1 f32[8]"] == 3
    # the gap 2-3 began inside bench/update (innermost), 9-10 inside drain
    assert sorted(map(tuple, r["idle_gaps"])) == [("bench/drain", 1.0),
                                                  ("bench/update", 1.0)]
    assert r["host_spans"]["bench/step"] == [4, 6]
    assert r["host_spans"]["bench/update"] == [2.0]


def test_async_collectives_last_from_start_to_done():
    ops = [("%all-reduce-start.1 = f32[8]{0} all-reduce-start(f32[8]{0} %g)",
            1, 1.1),
           ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop", 1.1, 3),
           ("%all-reduce-done.1 = f32[8]{0} all-reduce-done(f32[8]{0} %s)",
            3, 5)]
    r = tr.reduce_events({"/device:TPU:0": ops}, [])
    assert r["collective_s"] == pytest.approx(4.0)           # 1 to 5
    assert r["collective_exposed_s"] == pytest.approx(2.1)   # 1-1.1, 3-5
    assert r["window_s"] == pytest.approx(4.0)               # no window span


def test_busy_is_averaged_over_the_devices_and_clipped_to_the_window():
    ops = lambda a, b: [("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %a)", a, b)]
    r = tr.reduce_events({"/device:TPU:0": ops(-1, 2),
                          "/device:TPU:1": ops(1, 5)},
                         [("bench/traced_window", 0, 4, "main")])
    # the window grows to the end of work begun inside it: [0, 5]
    assert r["window_s"] == 5 and r["devices"] == 2
    assert r["busy_s"] == pytest.approx((2 + 4) / 2)


def test_no_device_operation_is_an_empty_result():
    assert tr.reduce_events({}, [("bench/step", 0, 1, "main")])["devices"] == 0


# -------------------------------------------------------- the recorded trace
@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "recorded.xplane.pb"
    with lzma.open(RECORDED) as src, open(path, "wb") as dst:
        dst.write(src.read())
    return tr.reduce_file(str(path))


def test_recorded_trace_busy_and_idle(recorded):
    assert recorded["devices"] == 1
    assert recorded["window_s"] == pytest.approx(1.029679392, rel=1e-9)
    assert recorded["busy_s"] == pytest.approx(1.027079578, rel=1e-9)
    assert recorded["idle_pct"] == pytest.approx(0.25248771804, rel=1e-6)


def test_recorded_trace_kernels_and_operations(recorded):
    # five steps, 4 layers: forward and two backward kernels a layer
    assert recorded["custom_call_s"] == pytest.approx(0.17476099, rel=1e-7)
    assert recorded["collective_s"] == 0 == recorded["collective_exposed_s"]
    top = recorded["device_ops"][0]
    assert top[0] == "fusion.15 bf16[4096,32000]"
    assert top[1] == pytest.approx(0.032061996, rel=1e-7)
    labels = [label for label, _ in recorded["device_ops"]]
    assert "jvp__.4 tpu_custom_call bf16[32,4096,128]" in labels
    assert len(recorded["device_ops"]) == 10
    assert recorded["device_op_kinds"] == 1328


def test_recorded_trace_host_spans_and_gaps(recorded):
    spans = recorded["host_spans"]
    assert len(spans["bench/step"]) == 5 and len(spans["bench/enqueue"]) == 5
    assert sum(spans["bench/step"]) == pytest.approx(0.823379718, rel=1e-8)
    # the device waited 2.3 ms of the second while the host read a loss
    name, seconds = recorded["idle_gaps"][0]
    assert name == "np.asarray(jax.Array)"
    assert seconds == pytest.approx(0.0022967449999977, rel=1e-6)
    total = sum(s for _, s in recorded["idle_gaps"])
    assert total == pytest.approx(
        recorded["window_s"] - recorded["busy_s"], rel=1e-6)


def test_flash_roofline_reader_on_the_recorded_trace(recorded):
    from benchmark import cell as cells
    from benchmark.families import llama as family
    reader = cells.load_module("layer_metrics", "flash_roofline")
    sizes = cells.load_cell("mistral7b-4l-spmd-1c").sizes
    ctx = {"trace": dict(recorded, steps=5),
           "peaks": cells.peaks_for("TPU v5 lite"),
           "record": {"kernel": {
               "flops_per_step": family.attention_flops(sizes),
               "bytes_per_step": family.attention_bytes(sizes)}}}
    share = reader.read(ctx)
    # 1.65e12 FLOPs at 197 TFLOP/s is 8.4 ms; the kernels took 35.0 ms
    assert share == pytest.approx(23.96, abs=0.05)
    assert ctx["notes"]["flash_bound"] == "compute"
    idle = cells.load_module("layer_metrics", "device_idle_pct").read(ctx)
    assert idle == pytest.approx(0.2525, abs=1e-4)
    step = cells.load_module("layer_metrics", "device_step_ms").read(ctx)
    assert step == pytest.approx(205.416, abs=1e-3)
