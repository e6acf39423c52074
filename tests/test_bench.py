"""bench.py smoke tier: the driver runs bench.py at end of round and its
ONE JSON line is the round's perf record — two rounds died to bench
breakage before this guard existed.  Runs every mode on the CPU mesh with
tiny sizes and asserts the line parses with the expected fields."""

import json
import os
import subprocess
import sys

import pytest

# Integration tier: real subprocess launches (see pyproject markers);
# the fast hermetic tier excludes these with `-m 'not slow'`.
pytestmark = pytest.mark.slow

from test_examples import _example_env

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")

def _run_bench(extra_env):
    env = _example_env(
        XLA_FLAGS="--xla_force_host_platform_device_count=8", **extra_env)
    r = subprocess.run([sys.executable, BENCH], env=env,
                       capture_output=True, text=True, timeout=720)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    lines = [l for l in r.stdout.splitlines() if l.startswith("{")]
    assert len(lines) == 1, r.stdout[-2000:]
    return json.loads(lines[0])


def test_bench_minimal_mode():
    out = _run_bench({"HVD_BENCH_MINIMAL": "1",
                      "HVD_BENCH_SIZES_MB": "0.125,1"})
    assert out["metric"] == "allreduce_engine_busbw_GBps"
    assert out["value"] and out["value"] > 0
    assert out["errors"] == {}
    assert out["world"] == 8
    # Trace A/B on every line: the armed window's phase breakdown must
    # partition the measured lifecycle (queue+negotiation+copy_in+reduce+
    # drain re-adds to cycle_us), and the overhead bound is recorded.
    ab = out["trace_ab"]
    assert set(ab["phases_us"]) == {"queue", "negotiation", "copy_in",
                                    "reduce", "drain"}
    assert ab["spans"] > 0 and ab["cycle_us"] > 0
    assert ab["phase_sum_consistent"] is True, ab
    assert "within_noise" in ab and "overhead_pct" in ab
    # Latency fast lane A/B on every line: both lanes bitwise-identical,
    # the lane + pinned-program path actually engaged, and the per-lane
    # phase breakdown carries the copy_in+drain evidence.
    fl = out["fast_lane_ab"]
    assert fl["bitwise_identical"] is True, fl
    assert fl["fast_lane_dispatches"] > 0 and fl["pin_hits"] > 0, fl
    assert "copy_in_drain_us_on" in fl and "within_noise" in fl, fl
    # crossover_mb rides every JSON line (null in engine-only sweeps),
    # and the busbw sweep scales iterations toward the wall target: the
    # small 128KB point is fast enough on the CPU mesh that a ≥200ms wall
    # needs strictly MORE than the 10-iteration floor (a probe-timing
    # regression that always returns the floor fails here).
    iters = out["allreduce_busbw_GBps"]["iters"]
    assert iters["1MB"] >= 10
    assert iters["0.125MB"] > 10, iters
    # Control-plane scale-out section (ISSUE 9) on every line: simulated
    # worlds through the real native server, flat vs hierarchical, with
    # the root-service scoreboard mirrored to the top-level flat_vs_hier.
    ns = out["negotiation_scaling"]
    assert set(ns["sizes"]) == {"8", "32", "128"}, ns
    for rec in ns["sizes"].values():
        assert rec["flat_root_us"] > 0 and rec["hier_root_us"] > 0, rec
        assert rec["flat_round_us"] > 0 and rec["hier_round_us"] > 0, rec
    assert out["flat_vs_hier"] == ns["flat_vs_hier"], (
        out["flat_vs_hier"], ns["flat_vs_hier"])
    # The tentpole's claim, measurable even on this shared box: at the
    # largest world the flat root does multiples of the hierarchical
    # root's serialized per-round work (128 connections vs 8).
    assert ns["sizes"]["128"]["flat_vs_hier"] > 1.5, ns
    # ISSUE 12: the sweep now injects churn MID-RUN (a preemption-notice
    # drain -> clean LEAVEs, the drained host's agent dying, a join
    # epoch) in BOTH planes — every world must survive it (no abort, all
    # departures clean), the verdict is mirrored onto the top-level line,
    # and the hierarchical root's slope stays ~flat THROUGH the churn
    # (post-churn phases measured separately).
    assert ns["churn_survived"] is True, ns
    assert out["churn_survived"] is True, out["churn_survived"]
    for rec in ns["sizes"].values():
        assert rec["churn_survived"] is True, rec
        assert rec["hier_root_us_post_churn"] > 0, rec
    assert ns["hier_slope_post"] is not None, ns
    # Generous bound for a shared noisy box; the real evidence rides the
    # recorded slope values (hier ~1x while flat tracks the world size).
    assert ns["hier_slope"] < ns["flat_slope"], ns
    # Autoscale section (ISSUE 10) on every line: policy decision latency
    # plus the clean-LEAVE drain round-trip through a real native server —
    # the survivor must actually OBSERVE the leave notice.
    asc = out["autoscale"]
    assert asc["decision_us"] > 0, asc
    assert asc["leave_sent"] is True, asc
    assert asc["left_observed"] is True, asc
    assert asc["drain_roundtrip_us"] > 0, asc
    # Restore A/B (ISSUE 14) on every line: disk-vs-peer recovery wall
    # time over the real state plane — both paths restore the identical
    # blob, and the peer path never opens a checkpoint file.  (No
    # which-is-faster assertion: on a local tmpfs the disk path can win;
    # the production claim is about remote/networked checkpoint storage.)
    rab = out["restore_ab"]
    assert rab["disk_restore_us"] > 0 and rab["peer_restore_us"] > 0, rab
    assert rab["bitwise_identical"] is True, rab
    assert rab["peer_disk_reads"] == 0, rab
    assert rab["peer_shards_fetched"] == rab["world"], rab
    # Sharded-optimizer A/B (ISSUE 15) on every line: optimizer-state
    # bytes/rank scale ~1/N (asserted by the section itself), the
    # sharded pipeline's modeled wire bytes sit strictly below the
    # allreduce-based sharded baseline, and both paths converge on the
    # same parameters.
    sab = out["sharded_ab"]
    assert sab["world"] == 8, sab
    assert sab["one_over_n"] is True, sab
    assert sab["opt_state_bytes_per_rank"] < \
        sab["opt_state_bytes_per_rank_replicated"] / 4, sab
    assert sab["wire_bytes_per_step_sharded"] < \
        sab["wire_bytes_per_step_allreduce"], sab
    assert sab["params_match"] is True, sab
    assert sab["step_ms_sharded"] > 0 and sab["step_ms_replicated"] > 0, sab
    # FSDP A/B (ISSUE 18) on every line: full parameter sharding keeps
    # resident params + opt state ≈ 1/N of the replicated total
    # (asserted by the section), its modeled wire bytes equal the ZeRO-1
    # pipeline's (full sharding is a memory win at equal wire), and the
    # gathered parameters match the replicated run.
    fab = out["fsdp_ab"]
    assert fab["world"] == 8, fab
    assert fab["one_over_n"] is True, fab
    assert fab["resident_bytes_full"] < \
        fab["resident_bytes_replicated"] / 4, fab
    assert fab["resident_bytes_full"] < fab["resident_bytes_sharded"], fab
    assert fab["wire_full_eq_sharded"] is True, fab
    assert fab["wire_bytes_per_step_full"] < \
        fab["wire_bytes_per_step_allreduce"], fab
    assert fab["params_match"] is True, fab
    assert fab["step_ms_full"] > 0 and fab["step_ms_replicated"] > 0, fab
    # Two-level allreduce A/B (ISSUE 17) on every line: flat-vs-hier
    # bitwise identity on integer payloads, the leg counters proving the
    # two-level path ran, the modeled cross-slice (DCN) wire bytes ≤
    # ~1/local_size of the flat ring's, and the crossover_mb key present
    # (null is legitimate: on a CPU mesh the three-launch pipeline
    # usually never beats one flat launch).
    hab = out["hierarchical_ab"]
    assert hab["world"] == 8 and hab["local_size"] == 4, hab
    assert hab["bitwise_identical"] is True, hab
    assert hab["hier_dispatches"] > 0, hab
    assert hab["hier_intra_legs"] == 2 * hab["hier_dispatches"], hab
    assert hab["hier_cross_legs"] == hab["hier_dispatches"], hab
    assert "crossover_mb" in hab, hab
    for rec in hab["sizes"]:
        assert rec["bitwise_identical"] is True, rec
        assert rec["cross_leq_flat_over_local"] is True, rec
        assert rec["wire_bytes_cross"] <= \
            rec["wire_bytes_flat"] / hab["local_size"] + 1, rec
        assert rec["flat_ms"] > 0 and rec["hier_ms"] > 0, rec
    # Zero-RTT A/B (ISSUE 11) on every line: with speculation on, warm
    # cycles stop paying the negotiation round trip (< 1 per cycle, hit
    # rate ≥ 90% on this stable workload) while every rank's verdict
    # order is identical on-vs-off — the bitwise-invariance evidence.
    zrt = out["zero_rtt_ab"]
    assert zrt["spec_hit_rate"] is not None and \
        zrt["spec_hit_rate"] >= 0.9, zrt
    assert zrt["round_trips_per_cycle_on"] < 1, zrt
    assert zrt["round_trips_per_cycle_off"] == 1.0, zrt
    assert zrt["orders_identical"] is True, zrt
    assert zrt["negotiation_us_per_cycle_on"] > 0, zrt
    assert zrt["negotiation_us_per_cycle_off"] > 0, zrt
    # ...and the live-engine stats block carries the zero_rtt keys.
    assert "zero_rtt" in out and "spec_hits" in out["zero_rtt"], out.keys()
    # Serving plane (ISSUE 19) on every line: batched-vs-sequential
    # bitwise parity through the padded-bucket jitted forward, the
    # recompile pin under batch-size churn, the p50/p99-vs-offered-load
    # sweep, the scripted ramp → scale_out → drain scenario with the live
    # drain contract, and the 13 B warm-frame guard with serving active.
    srv = out["serving"]
    assert srv["parity_bitwise"] is True, srv
    assert srv["batch_churn_bounded"] is True, srv
    assert len(srv["load_sweep"]) == 3, srv
    for pt in srv["load_sweep"]:
        assert pt["offered_qps"] > 0 and pt["achieved_qps"] > 0, pt
        assert pt["batches"] > 0, pt
    sc = srv["scenario"]
    assert sc["scale_out_fired"] is True and sc["drain_fired"] is True, sc
    assert sc["drain_completed_inflight"] is True, sc
    assert sc["drain_refused_new"] is True, sc
    fg = srv["frame_guard"]
    assert fg["held"] is True, fg
    assert fg["full_announce_delta"] == 0, fg
    assert fg["serve_requests_during_window"] > 0, fg
    # Serving fault tolerance (ISSUE 20) on every line: an injected
    # replica fault mid-batch under concurrent front-door load must lose
    # ZERO accepted requests (every one gets exactly one terminal 200,
    # bitwise-correct, the interrupted bucket via retries), availability
    # stays 1.0, and recovery-time-to-ready is recorded.
    sf = out["serving_faults"]
    assert sf["zero_lost"] is True, sf
    assert sf["lost_requests"] == 0, sf
    assert sf["ok_responses"] == sf["requests"], sf
    assert sf["results_correct"] is True, sf
    assert sf["replica_faults"] == 1 and sf["retried_requests"] > 0, sf
    assert sf["quarantined"] == 0, sf
    assert sf["availability"] == 1.0, sf
    assert sf["recovery_to_ready_s"] is not None \
        and sf["recovery_to_ready_s"] < 30, sf


def test_bench_default_resnet():
    out = _run_bench({"HVD_BENCH_BATCH": "2", "HVD_BENCH_STEPS": "2",
                      "HVD_BENCH_IMAGE": "32", "HVD_BENCH_SKIP_BUSBW": "1",
                      "HVD_BENCH_SKIP_RAW": "1"})
    assert out["metric"].startswith("resnet50")
    assert out["value"] and out["value"] > 0, out
    assert out["errors"] == {}, out


def test_bench_llama_mode():
    out = _run_bench({"HVD_BENCH_MODEL": "llama", "HVD_BENCH_BATCH": "2",
                      "HVD_BENCH_STEPS": "2"})
    assert out["metric"].startswith("llama")
    assert out["value"] and out["value"] > 0, out
    assert out["errors"] == {}, out


def test_bench_tf_step_mode():
    """TF binding per-step cost decomposition (VERDICT r3 missing #3)."""
    out = _run_bench({"HVD_BENCH_MODEL": "tf_step", "HVD_BENCH_STEPS": "5"})
    assert out["metric"] == "tf_binding_step_overhead_pct"
    assert out["value"] is not None, out
    assert out["tf_step_plain_ms"] > 0
    assert out["tf_grouped_allreduce_ms"] > 0
    assert out["errors"] == {}, out


def test_bench_bert_mode():
    out = _run_bench({"HVD_BENCH_MODEL": "bert", "HVD_BENCH_BATCH": "2",
                      "HVD_BENCH_STEPS": "2", "HVD_BENCH_SKIP_BUSBW": "1"})
    assert out["metric"].startswith("bert")
    assert out["value"] and out["value"] > 0, out
    assert out["errors"] == {}, out


def test_bench_llama_seq_and_evidence_knobs():
    """HVD_BENCH_SEQ stretches the llama context; the record carries the
    analytic-FLOPs/MFU evidence fields and the requested seq/remat."""
    out = _run_bench({"HVD_BENCH_MODEL": "llama", "HVD_BENCH_BATCH": "2",
                      "HVD_BENCH_STEPS": "2", "HVD_BENCH_SEQ": "256",
                      "HVD_BENCH_REMAT": "1"})
    assert out["value"] and out["value"] > 0, out
    te = out["timing_evidence"]["llama"]
    assert te["seq"] == 256
    assert te["n_params"] > 0
    assert te["analytic_step_flops"] > 0
    assert out["errors"] == {}, out


def test_bench_bert_seq_knob():
    """HVD_BENCH_SEQ reaches the bert mode too (the non-causal crossover
    bench vehicle) with the same evidence fields."""
    out = _run_bench({"HVD_BENCH_MODEL": "bert", "HVD_BENCH_BATCH": "2",
                      "HVD_BENCH_STEPS": "2", "HVD_BENCH_SEQ": "128",
                      "HVD_BENCH_SKIP_BUSBW": "1"})
    assert out["value"] and out["value"] > 0, out
    te = out["timing_evidence"]["bert"]
    assert te["seq"] == 128
    assert te["n_params"] > 0
    assert out["errors"] == {}, out


def test_bench_decode_mode():
    """Inference mode: prefill + KV-cache decode through the flagship."""
    out = _run_bench({"HVD_BENCH_MODEL": "decode", "HVD_BENCH_STEPS": "2",
                      "HVD_BENCH_DECODE_BATCH": "2"})
    assert out["metric"] == "llama_decode_tokens_per_sec"
    assert out["value"] and out["value"] > 0, out
    assert out["errors"] == {}, out


def test_bench_vit_mode():
    out = _run_bench({"HVD_BENCH_MODEL": "vit", "HVD_BENCH_BATCH": "1",
                      "HVD_BENCH_STEPS": "2", "HVD_BENCH_IMAGE": "32"})
    assert out["metric"].startswith("vit")
    assert out["value"] and out["value"] > 0, out
    te = out["timing_evidence"]["vit"]
    assert te["n_params"] > 0 and te["seq"] == 5  # 32/16 grid + CLS
    assert out["errors"] == {}, out
