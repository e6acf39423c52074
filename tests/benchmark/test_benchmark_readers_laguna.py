"""The four reader files that ``laguna_s2_1-5l-spmd-1c`` brings (one of them
``expert_matmul_roofline``'s reader under a name of the cell's own), and the
four readers it borrows by their suffix: on a trace of a program that has
none of their spans or kernels (the parent commit's, any other family's)
each returns nothing and raises nothing; a layer kind's share divides by
the flash kernels that the family's table gives to that kind's scope, the
other kind's kernels and the grouped products beside them left out."""

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
NEW = ("attn_window_ms", "attn_ms.laguna", "moe_ms.laguna", "mlp_ms.laguna",
       "head_ms.laguna", "laguna_expert_matmul_roofline",
       "window_flash_roofline", "full_flash_roofline")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
COUNTS = {"flops_per_step": 1e12, "bytes_per_step": 1e9}


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
    COUNTS,                                 # llama's record
    {"scopes": {}, "full_flash": COUNTS, "window_flash": COUNTS,
     "experts": COUNTS, "counters": {"attention": {"full_flash": 6}}},
], ids=["no-kernel-record", "attention-counts", "this-familys-counts"])
def test_a_reader_finds_nothing_in_another_programs_trace(
        another_programs_trace, name, kernel):
    reader = reader_of(name)
    ctx = {"trace": another_programs_trace, "peaks": PEAKS,
           "record": {"kernel": kernel}}
    assert reader.read(ctx) is None
    assert reader.read({"trace": None, "peaks": PEAKS,
                        "record": {"kernel": kernel}}) is None
    assert reader.read({"trace": {"path": None}, "peaks": PEAKS,
                        "record": {}}) is None


def test_every_new_entry_is_the_cells_alone_and_has_a_reader():
    bench = cells.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert entries[name]["workloads"] == ["laguna_s2_1-5l-spmd-1c"]
        assert entries[name]["moves"] == "items_per_s_per_chip.spmd"
        assert callable(reader_of(name).read)
    # in the issue's order, after what PR 43 brought; a later PR's entries
    # follow them (no test here holds that these are the last)
    names = list(entries)
    assert [n for n in names if n in NEW] == list(NEW)
    assert names.index(NEW[0]) > names.index("jamba_flash_roofline")
    cell = next(w for w in bench["workloads"]
                if w["name"] == "laguna_s2_1-5l-spmd-1c")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "laguna-s-2_1-5l", "spmd-t16384-b1", 1)
    assert "laguna_s2_1-5l-spmd-1c" in next(
        m for m in bench["end_to_end"]
        if m["name"] == "items_per_s_per_chip.spmd")["workloads"]


def test_a_layer_kinds_share_divides_by_its_own_flash_kernels(monkeypatch):
    """Two steps.  The sliding layers' kernels take 10 ms a step, the full
    layers' 30 ms, the grouped products 20 ms (a ``tpu_custom_call`` too)
    and a fusion under ``attn/window`` 5 ms: each kind's share is its least
    time over its own kernels' time alone."""
    events = {"/device:TPU:0": [
        ("flash_fwd.11", 0.000, 0.004), ("flash_bwd_dq.6", 0.004, 0.007),
        ("flash_bwd_dkv.6", 0.007, 0.010), ("fusion.3", 0.010, 0.015),
        ("flash_fwd.10", 0.015, 0.025), ("flash_bwd_dkv.5", 0.025, 0.045),
        ("ragged-dot-none.3", 0.045, 0.065),
        ("flash_fwd.11", 0.100, 0.104), ("flash_bwd_dq.6", 0.104, 0.107),
        ("flash_bwd_dkv.6", 0.107, 0.110), ("fusion.3", 0.110, 0.115),
        ("flash_fwd.10", 0.115, 0.125), ("flash_bwd_dkv.5", 0.125, 0.145),
        ("ragged-dot-none.3", 0.145, 0.165)]}
    monkeypatch.setattr(trace_scopes, "device_events", lambda path: events)
    scopes = {"flash_fwd.11": "attn/window", "flash_bwd_dq.6": "attn/window",
              "flash_bwd_dkv.6": "attn/window", "fusion.3": "attn/window",
              "flash_fwd.10": "attn/full", "flash_bwd_dkv.5": "attn/full",
              "ragged-dot-none.3": "moe/experts"}
    ctx = {"trace": {"path": "x", "steps": 2}, "peaks": PEAKS,
           "record": {"kernel": {
               "scopes": scopes,
               "window_flash": {"flops_per_step": 197e12 * 0.002,
                                "bytes_per_step": 819e9 * 0.001},
               "full_flash": {"flops_per_step": 197e12 * 0.012,
                              "bytes_per_step": 819e9 * 0.001},
               "experts": {"flops_per_step": 197e12 * 0.001,
                           "bytes_per_step": 819e9 * 0.004},
               "counters": {"assignments": 8, "attention": {
                   "full_flash": 6, "full_plain": 0, "window_flash": 9,
                   "window_plain": 0}}}}}
    window = reader_of("window_flash_roofline").read(ctx)
    full = reader_of("full_flash_roofline").read(ctx)
    assert window == pytest.approx(100.0 * 0.002 / 0.010)
    assert full == pytest.approx(100.0 * 0.012 / 0.030)
    assert ctx["notes"]["window_flash_bound"] == "compute"
    assert ctx["notes"]["full_flash_bound"] == "compute"
    assert ctx["notes"]["attention_paths"]["window_flash"] == 9
    # the scopes' own times: the kernels and what else is under the scope
    assert reader_of("attn_window_ms").read(ctx) == pytest.approx(15.0)
    assert reader_of("attn_ms.laguna").read(ctx) == pytest.approx(30.0)
    assert reader_of("moe_ms.laguna").read(ctx) == pytest.approx(20.0)
    assert reader_of("laguna_expert_matmul_roofline").read(
        ctx) == pytest.approx(100.0 * 0.004 / 0.020)
    assert ctx["notes"]["expert_matmul_bound"] == "memory"
    assert ctx["notes"]["expert_load"]["assignments"] == 8
    # a table without the kind's kernels: nothing, not zero
    ctx["record"]["kernel"]["scopes"] = {"fusion.3": "attn/window"}
    assert reader_of("window_flash_roofline").read(ctx) is None
