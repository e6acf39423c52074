"""The five reader files that ``joyai_flash-5l-spmd-1c`` brings (one of them
``expert_matmul_roofline``'s reader under a name of the cell's own) and the
three readers it borrows by their suffix: on a trace of a program that has
none of their spans or kernels (the parent commit's, any other family's)
each returns nothing and raises nothing; on planted events each reads its
own scope, the prediction module's instructions going to ``mtp_ms`` alone
and the main layers' flash kernels to ``latent_flash_roofline``."""

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
CELL = "joyai_flash-5l-spmd-1c"
NEW = ("attn_latent_ms", "latent_proj_ms", "mtp_ms", "moe_ms.joyai",
       "mlp_ms.joyai", "head_ms.joyai", "latent_flash_roofline",
       "joyai_expert_matmul_roofline")
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
    {"scopes": {}, "latent_flash": COUNTS, "experts": COUNTS,
     "counters": {"attention": {"latent_flash": 6}}},
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
        assert entries[name]["workloads"] == [CELL]
        assert entries[name]["moves"] == "items_per_s_per_chip.spmd"
        assert callable(reader_of(name).read)
    # found by name and held in the issue's order among themselves; a later
    # PR's entries follow them (no test here holds that these are the last)
    names = list(entries)
    assert [n for n in names if n in NEW] == list(NEW)
    assert names.index(NEW[0]) > names.index("full_flash_roofline")
    assert {entries[n]["layer"] for n in NEW} == {
        "step programs", "expert layer", "kernels"}
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "joyai-llm-flash-5l", "spmd-t16384-b1", 1)
    assert CELL in next(m for m in bench["end_to_end"]
                        if m["name"] == "items_per_s_per_chip.spmd")[
                            "workloads"]
    config = next(c for c in bench["configs"]
                  if c["name"] == "joyai-llm-flash-5l")
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_each_reader_reads_its_own_scope_and_mtp_takes_the_modules(
        monkeypatch):
    """Two steps.  A main layer's flash kernels take 12 ms a step and its
    low-rank paths 3 ms, ``W_o`` 1 ms; the module's flash kernel 4 ms and
    its head pass 2 ms (both ``mtp``'s by the table); the grouped products
    20 ms (named by the compiler, the module's among them), the dense MLP 5
    ms, the head 6 ms."""
    step = [("flash_fwd.3", 0.000, 0.004), ("flash_bwd_dq.3", 0.004, 0.008),
            ("flash_bwd_dkv.3", 0.008, 0.012), ("fusion.proj", 0.012, 0.015),
            ("fusion.wo", 0.015, 0.016), ("flash_fwd.9", 0.016, 0.020),
            ("fusion.mtphead", 0.020, 0.022),
            ("ragged-dot-none.3", 0.022, 0.042), ("fusion.mlp", 0.042, 0.047),
            ("fusion.head", 0.047, 0.053)]
    events = {"/device:TPU:0": step + [(n, a + 0.1, b + 0.1)
                                       for n, a, b in step]}
    monkeypatch.setattr(trace_scopes, "device_events", lambda path: events)
    scopes = {"flash_fwd.3": "attn/latent", "flash_bwd_dq.3": "attn/latent",
              "flash_bwd_dkv.3": "attn/latent",
              "fusion.proj": "attn/latent/proj", "fusion.wo": "attn/latent",
              "flash_fwd.9": "mtp", "fusion.mtphead": "mtp",
              "fusion.mlp": "mlp", "fusion.head": "head"}
    ctx = {"trace": {"path": "x", "steps": 2}, "peaks": PEAKS,
           "record": {"kernel": {
               "scopes": scopes,
               "latent_flash": {"flops_per_step": 197e12 * 0.003,
                                "bytes_per_step": 819e9 * 0.001},
               "experts": {"flops_per_step": 197e12 * 0.001,
                           "bytes_per_step": 819e9 * 0.005},
               "counters": {"assignments": 8, "router_bias": {
                   "raised": 3, "lowered": 2, "largest_move": 0.003},
                   "attention": {"latent_flash": 6, "latent_plain": 0}}}}}
    assert reader_of("attn_latent_ms").read(ctx) == pytest.approx(16.0)
    assert reader_of("latent_proj_ms").read(ctx) == pytest.approx(3.0)
    assert reader_of("mtp_ms").read(ctx) == pytest.approx(6.0)
    assert reader_of("mlp_ms.joyai").read(ctx) == pytest.approx(5.0)
    assert reader_of("head_ms.joyai").read(ctx) == pytest.approx(6.0)
    assert reader_of("moe_ms.joyai").read(ctx) == pytest.approx(20.0)
    # the main layers' kernels alone: the module's flash_fwd.9 is mtp's
    assert reader_of("latent_flash_roofline").read(ctx) == pytest.approx(
        100.0 * 0.003 / 0.012)
    assert reader_of("joyai_expert_matmul_roofline").read(
        ctx) == pytest.approx(100.0 * 0.005 / 0.020)
    notes = ctx["notes"]
    assert notes["latent_flash_bound"] == "compute"
    assert notes["expert_matmul_bound"] == "memory"
    assert notes["attention_paths"] == {"latent_flash": 6, "latent_plain": 0}
    assert notes["expert_load"]["router_bias"]["raised"] == 3
    # a table without the main layers' kernels: nothing, not zero
    ctx["record"]["kernel"]["scopes"] = {"flash_fwd.9": "mtp"}
    assert reader_of("latent_flash_roofline").read(ctx) is None
    assert reader_of("attn_latent_ms").read(ctx) is None
