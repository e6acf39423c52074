"""Multi-process integration tier: real torovodrun launches on localhost,
full negotiate (native TCP controller) -> fuse -> XLA-collective path across
processes — the rebuild's equivalent of the reference's Gloo-on-localhost
hermetic tier (SURVEY.md §4 "fake backends").
"""

import os
import subprocess
import sys

import pytest

# Integration tier: real subprocess launches (see pyproject markers);
# the fast hermetic tier excludes these with `-m 'not slow'`.
pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "data", "worker_collectives.py")


def _run_torovodrun(np_, script, timeout=300, extra_args=(), extra_env=None):
    env = dict(os.environ)
    other_paths = [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                   if p]
    env["PYTHONPATH"] = os.pathsep.join([REPO] + other_paths)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("HOROVOD_TIMELINE", None)
    if extra_env:
        env.update(extra_env)
    cmd = [sys.executable, "-m", "horovod_tpu.runner.launch",
           "-np", str(np_), *extra_args, sys.executable, script]
    return subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=timeout)


def test_native_controller_builds():
    from horovod_tpu.common import native
    lib = native.load()
    assert lib is not None


def _free_port():
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_controller_negotiation_unit():
    """Server + 2 client threads, no jax: readiness protocol only."""
    import threading
    from horovod_tpu.common.controller import TCPController

    port = _free_port()
    results = {}

    def worker(rank):
        class E:
            def __init__(self, name):
                self.name = name
        ctl = TCPController("127.0.0.1", port, rank=rank, world=2,
                            stall_warn_s=60.0)
        try:
            if rank == 0:
                # announce a; peer announces b first, then a
                r1, _ = ctl.negotiate([E("a")])
                r2, _ = ctl.negotiate([E("a"), E("b")])
                r3, _ = ctl.negotiate([E("b")] if not any(
                    e.name == "b" for e in r2) else [])
                results[rank] = [[e.name for e in r] for r in (r1, r2, r3)]
            else:
                r1, _ = ctl.negotiate([E("b")])
                r2, _ = ctl.negotiate([E("b"), E("a")])
                r3, _ = ctl.negotiate([E("a")] if not any(
                    e.name == "a" for e in r2) else [])
                results[rank] = [[e.name for e in r] for r in (r1, r2, r3)]
        finally:
            ctl.shutdown() if rank != 0 else None
        # rank 0 keeps server alive until both done; shutdown at end
        if rank == 0:
            ctl.shutdown()

    ts = [threading.Thread(target=worker, args=(r,)) for r in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=30)
    assert 0 in results and 1 in results
    # Round 1: nothing globally ready (disjoint names). Round 2+: both a
    # and b become ready, in the same global order on both ranks.
    flat0 = [n for r in results[0] for n in r]
    flat1 = [n for r in results[1] for n in r]
    assert sorted(flat0) == ["a", "b"], results
    assert sorted(flat1) == ["a", "b"], results
    assert flat0 == flat1, results


def test_controller_response_cache_shrinks_steady_state():
    """Reference N8 (response_cache.cc): after the first announce of a
    (name, digest) tuple, re-announces ride a 4-byte cache id — identical
    verdicts, much smaller steady-state request frames."""
    import threading
    from horovod_tpu.common.controller import TCPController

    port = _free_port()
    results = {}

    class E:
        def __init__(self, name):
            self.name = name

    names = [f"grad.{i}.with.a.long.parameter.path" for i in range(16)]

    def worker(rank):
        ctl = TCPController("127.0.0.1", port, rank=rank, world=2,
                            stall_warn_s=60.0)
        try:
            per_round = []
            orders = []
            for step in range(4):
                before = ctl.bytes_sent
                got = []
                entries = [E(n) for n in names]
                while len(got) < len(names):
                    ready, errs = ctl.negotiate(entries)
                    assert not errs
                    got += [e.name for e in ready]
                    entries = [e for e in entries
                               if e.name not in set(got)]
                per_round.append(ctl.bytes_sent - before)
                orders.append(tuple(got))
            results[rank] = (per_round, orders)
        finally:
            if rank != 0:
                ctl.shutdown()
            else:
                import time
                deadline = time.time() + 30
                while len(results) < 2 and time.time() < deadline:
                    time.sleep(0.01)   # keep the server up for the peer
                ctl.shutdown()

    t1 = threading.Thread(target=worker, args=(1,))
    t1.start()
    worker(0)
    t1.join(timeout=60)
    assert set(results) == {0, 1}
    for rank, (per_round, orders) in results.items():
        # Steady state (round 2+) must be far smaller than the cold round:
        # 16 cached announces ≈ 16*(4+2+2) + 8 bytes vs full names+digests.
        assert per_round[2] < per_round[0] / 3, (rank, per_round)
        assert per_round[3] <= per_round[1], (rank, per_round)
    # Verdict order identical across ranks every round.
    assert results[0][1] == results[1][1]


@pytest.mark.parametrize("np_", [2, 3])
def test_torovodrun_collectives(np_):
    res = _run_torovodrun(np_, WORKER)
    ok = res.stdout.count("WORKER_OK")
    assert res.returncode == 0 and ok == np_, (
        f"rc={res.returncode}\nstdout:\n{res.stdout[-3000:]}\n"
        f"stderr:\n{res.stderr[-3000:]}")


WORKER_HIER = os.path.join(REPO, "tests", "data", "worker_hierarchical.py")


def test_hierarchical_two_slices():
    """Cross-slice emulation (VERDICT r4 next #6): 2 processes × 4 local
    devices — intra-process = one slice's ICI domain, the gloo TCP hop =
    DCN — with hierarchical allreduce RS(local)→AR(cross)→AG(local)
    end-to-end through the engine.  The worker asserts size=8, local=4,
    the engine flag, and flat-equivalent numerics (single + fused)."""
    res = _run_torovodrun(2, WORKER_HIER,
                          extra_args=("--hierarchical-allreduce",),
                          extra_env={"HOROVOD_ONE_PROC_PER_HOST": "1"})
    ok = res.stdout.count("WORKER_OK")
    assert res.returncode == 0 and ok == 2, (
        f"rc={res.returncode}\nstdout:\n{res.stdout[-3000:]}\n"
        f"stderr:\n{res.stderr[-3000:]}")


WORKER_HIER_PARITY = os.path.join(REPO, "tests", "data", "worker_hier.py")


@pytest.mark.parametrize("controller", ["flat", "hier"])
def test_torovodrun_hier_parity(controller):
    """ISSUE 17 acceptance: after 10 steps on a mixed fp32/bf16/scalar
    integer-valued gradient tree over 2 simulated slices (2 procs × 4
    local devices, HOROVOD_SLICE_MAP=4), parameters from the two-level
    RS(local)→AR(cross)→AG(local) pipeline are BITWISE identical to the
    flat ring's, the leg counters prove the path ran, and toggling the
    mode mid-run cost zero warm-path control bytes (assertions live in
    the worker).  Runs against both control planes — the per-host agent
    must forward the unchanged digests identically."""
    extra = (("--hierarchical-controller",) if controller == "hier"
             else ())
    res = _run_torovodrun(2, WORKER_HIER_PARITY, timeout=300,
                          extra_args=extra,
                          extra_env={"HOROVOD_ONE_PROC_PER_HOST": "1",
                                     "HOROVOD_SLICE_MAP": "4"})
    ok = res.stdout.count("HIER_OK")
    assert res.returncode == 0 and ok == 2, (
        f"rc={res.returncode}\nstdout:\n{res.stdout[-3000:]}\n"
        f"stderr:\n{res.stderr[-3000:]}")


WORKER_TORCH = os.path.join(REPO, "tests", "data", "worker_torch.py")


@pytest.mark.parametrize("np_", [2])
def test_torovodrun_torch_binding(np_):
    res = _run_torovodrun(np_, WORKER_TORCH)
    ok = res.stdout.count("WORKER_OK")
    assert res.returncode == 0 and ok == np_, (
        f"rc={res.returncode}\nstdout:\n{res.stdout[-3000:]}\n"
        f"stderr:\n{res.stderr[-3000:]}")


def test_controller_digest_mismatch_unit():
    """Two client threads announce the same name with divergent shapes: both
    get a per-tensor error naming both ranks; a later consistent collective
    still negotiates (runtime survives)."""
    import threading
    import numpy as np
    from horovod_tpu.common.controller import TCPController

    port = _free_port()
    results = {}

    class E:
        def __init__(self, name, shape):
            self.name = name
            self.tensor = np.zeros((2,) + shape, np.float32)

    def worker(rank):
        ctl = TCPController("127.0.0.1", port, rank=rank, world=2,
                            stall_warn_s=60.0)
        try:
            shape = (4,) if rank == 0 else (8,)
            err = None
            for _ in range(20):
                ready, errored = ctl.negotiate([E("t", shape)])
                if errored:
                    err = errored[0][1]
                    break
            # after the failure, a consistent name must still become ready
            ok = []
            for _ in range(20):
                ready, errored = ctl.negotiate([E("t2", (3,))])
                if ready:
                    ok = [e.name for e in ready]
                    break
            results[rank] = (err, ok)
        finally:
            ctl.shutdown()

    ts = [threading.Thread(target=worker, args=(r,)) for r in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=30)
    assert 0 in results and 1 in results, results
    for rank in (0, 1):
        err, ok = results[rank]
        assert err is not None and "ranks [0]" in err and "ranks [1]" in err, \
            results
        assert "(4,)" in err and "(8,)" in err, results
        assert ok == ["t2"], results


WORKER_MISMATCH = os.path.join(REPO, "tests", "data", "worker_mismatch.py")


def test_torovodrun_shape_mismatch_fails_fast():
    """Full-stack parity with the reference controller's consistency check:
    mismatched shapes under one name fail that collective on BOTH ranks with
    rank attribution, and the world keeps working afterwards."""
    res = _run_torovodrun(2, WORKER_MISMATCH, timeout=300)
    ok = res.stdout.count("MISMATCH_OK")
    assert res.returncode == 0 and ok == 2, (
        f"rc={res.returncode}\nstdout:\n{res.stdout[-3000:]}\n"
        f"stderr:\n{res.stderr[-3000:]}")


WORKER_JOIN = os.path.join(REPO, "tests", "data", "worker_join.py")


def test_torovodrun_join_uneven_batches():
    """Real hvd.join() semantics (VERDICT missing #6): rank r trains r+1
    batches then joins; peers keep reducing with the joined rank
    auto-contributing zeros; join returns the last rank; world resumes."""
    # Tiny fusion threshold: every cluster flushes its own batch, so a
    # joined rank that loses peers' group structure would split a grouped
    # collective into mismatched per-process programs (and hang).
    res = _run_torovodrun(2, WORKER_JOIN, timeout=300,
                          extra_env={"HOROVOD_FUSION_THRESHOLD": "1"})
    ok = res.stdout.count("JOIN_OK")
    assert res.returncode == 0 and ok == 2, (
        f"rc={res.returncode}\nstdout:\n{res.stdout[-3000:]}\n"
        f"stderr:\n{res.stderr[-3000:]}")


def test_controller_join_unit():
    """Protocol-level join: rank 1 joins; rank 0's tensor becomes ready on
    both sides (rank 1 synthesizing); then rank 0 joins and both observe
    the all-joined epoch end."""
    import threading
    import numpy as np
    from horovod_tpu.common.controller import TCPController

    port = _free_port()
    results = {}

    class E:
        def __init__(self, name):
            self.name = name
            self.tensor = np.zeros((2, 3), np.float32)

    def worker(rank):
        ctl = TCPController("127.0.0.1", port, rank=rank, world=2,
                            stall_warn_s=60.0)
        synthesized = []
        ctl.synthesizer = lambda name, digest, gid: ("zeros", name, digest)
        try:
            # No background engine thread here: each side must keep driving
            # lock-step rounds itself until the all-joined verdict lands.
            if rank == 1:
                ctl.request_join()
                got = []
                for _ in range(60):
                    ready, _err = ctl.negotiate([])
                    got += ready
                    if ctl._join_event.is_set():
                        break
                results[1] = (got, ctl.join_wait(timeout=1))
            else:
                ready = []
                announced = False
                for _ in range(60):
                    r, _err = ctl.negotiate(
                        [E("t")] if not announced else [])
                    announced = True
                    ready += r
                    if ready and not ctl._join_pending and not ctl._joined \
                            and not ctl._join_event.is_set():
                        ctl.request_join()
                    if ctl._join_event.is_set():
                        break
                results[0] = ([e.name for e in ready],
                              ctl.join_wait(timeout=1))
        finally:
            ctl.shutdown()

    ts = [threading.Thread(target=worker, args=(r,)) for r in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=60)
    assert 0 in results and 1 in results, results
    names, last = results[0]
    assert names == ["t"] and last == 0, results
    syn, last1 = results[1]
    assert last1 == 0, results
    assert len(syn) == 1 and syn[0][0] == "zeros" and syn[0][1] == "t", results
    assert "float32" in syn[0][2] and "(3,)" in syn[0][2], results


WORKER_TF = os.path.join(REPO, "tests", "data", "worker_tf_keras.py")


def test_torovodrun_tensorflow_keras():
    """TF/Keras binding across real processes (VERDICT missing #2): rank-
    dependent collectives, DistributedGradientTape averaging,
    broadcast_variables, and a Keras fit that leaves ranks bit-identical."""
    res = _run_torovodrun(2, WORKER_TF, timeout=420)
    ok = res.stdout.count("TF_OK")
    assert res.returncode == 0 and ok == 2, (
        f"rc={res.returncode}\nstdout:\n{res.stdout[-3000:]}\n"
        f"stderr:\n{res.stderr[-3000:]}")


def test_controller_group_structure_mismatch_unit():
    """Grouped on one rank, ungrouped on the other: per-tensor error naming
    both sides (batching would diverge at the fusion threshold), while
    legitimately drifted group IDS (both grouped) stay fine."""
    import threading
    import numpy as np
    from horovod_tpu.common.controller import TCPController

    port = _free_port()
    results = {}

    class E:
        def __init__(self, name, gid):
            self.name = name
            self.group_id = gid
            self.tensor = np.zeros((2, 3), np.float32)

    def worker(rank):
        ctl = TCPController("127.0.0.1", port, rank=rank, world=2,
                            stall_warn_s=60.0)
        try:
            # FIXED round count on both ranks: the protocol is lock-step
            # (one frame per rank per round), so break-on-verdict loops
            # would let one rank stop calling rounds while its peer still
            # needs them — the peer then blocks forever or dies when the
            # early finisher tears down.  Announce both tensors every
            # round; verdicts land within the first rounds.
            err, ok = None, []
            for _ in range(6):
                ready, errored = ctl.negotiate(
                    # "t": grouped on rank 0, ungrouped on rank 1 → error;
                    # "t2": grouped on BOTH with drifted ids → fine.
                    [E("t", 5 if rank == 0 else -1),
                     E("t2", 7 if rank == 0 else 99)])
                for e, msg in errored:
                    assert e.name == "t", (e.name, msg)
                    err = err or msg
                ok += [e.name for e in ready]
            results[rank] = (err, ok)
        finally:
            ctl.shutdown()

    ts = [threading.Thread(target=worker, args=(r,)) for r in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=30)
    assert 0 in results and 1 in results, results
    for r in (0, 1):
        err, ok = results[r]
        assert err is not None and "GROUPED" in err, results
        assert "ranks [0]" in err and "ranks [1]" in err, results
        # "t2" renegotiates fine every round it is (re-)announced; "t"
        # must never come back ready.
        assert ok and set(ok) == {"t2"}, results


def test_torovodrun_with_network_interface():
    """--network-interface triggers the bootstrap probe phase and selects
    the control-plane address (VERDICT missing #4: the flag used to be
    parsed and ignored)."""
    res = _run_torovodrun(2, WORKER, extra_args=("--network-interface", "lo"))
    ok = res.stdout.count("WORKER_OK")
    assert res.returncode == 0 and ok == 2, (
        f"rc={res.returncode}\nstdout:\n{res.stdout[-3000:]}\n"
        f"stderr:\n{res.stderr[-3000:]}")


WORKER_SANITIZER = os.path.join(REPO, "tests", "data", "worker_sanitizer.py")


def test_sanitizer_catches_divergent_collective_order():
    """HVD_TPU_SANITIZER=1 acceptance: two ranks submit identical-signature
    allreduces in opposite order from different call sites; the sanitizer's
    seq/call-site digest tag turns it into a fail-fast NegotiationError
    naming the diverging ranks and both call sites (the worker asserts the
    attribution, then prints SANITIZER_OK)."""
    res = _run_torovodrun(2, WORKER_SANITIZER, timeout=300,
                          extra_env={"HVD_TPU_SANITIZER": "1"})
    ok = res.stdout.count("SANITIZER_OK")
    assert res.returncode == 0 and ok == 2, (
        f"rc={res.returncode}\nstdout:\n{res.stdout[-3000:]}\n"
        f"stderr:\n{res.stderr[-3000:]}")


def test_sanitizer_hash_catches_divergent_content_same_site():
    """HVD_TPU_SANITIZER=hash acceptance (the same-site blind spot): two
    ranks submit divergent DATA through one call site with identical
    seq/site tags; only the content digest folded into the tag can tell
    them apart.  The worker asserts rank attribution + the hash field in
    the error, then proves a replicated control collective still
    negotiates (runtime survives)."""
    res = _run_torovodrun(2, WORKER_SANITIZER, timeout=300,
                          extra_env={"HVD_TPU_SANITIZER": "hash"})
    ok = res.stdout.count("SANITIZER_HASH_OK")
    assert res.returncode == 0 and ok == 2, (
        f"rc={res.returncode}\nstdout:\n{res.stdout[-3000:]}\n"
        f"stderr:\n{res.stderr[-3000:]}")


def test_sanitizer_off_misses_divergent_order():
    """Control run: without the sanitizer the same divergence sails through
    negotiation (signatures match) and corrupts silently — the documented
    gap the sanitizer exists to close."""
    res = _run_torovodrun(2, WORKER_SANITIZER, timeout=300)
    ok = res.stdout.count("SANITIZER_MISSED")
    assert res.returncode == 0 and ok == 2, (
        f"rc={res.returncode}\nstdout:\n{res.stdout[-3000:]}\n"
        f"stderr:\n{res.stderr[-3000:]}")


WORKER_PS = os.path.join(REPO, "tests", "data", "worker_process_sets.py")


def test_process_set_namespaced_sanitizer_attribution(tmp_path):
    """ISSUE 16 acceptance: two tenant process sets run collectives
    concurrently with world traffic; the ranks deliberately swap the WORLD
    lane's submission order.  The namespaced sanitizer must attribute the
    divergence to the world namespace (seq=0:<i> tags), leave each
    tenant's per-set ledger view clean (exactly its own submission at
    seq=<set>:0), and — via HVD_TPU_SANITIZER_STATIC_INDEX — name the
    HVD111 node the whole-package analyzer pinned on these very sites
    before launch."""
    import json
    from horovod_tpu.analysis.whole_package import build_static_index

    index = build_static_index([WORKER_PS])
    flagged = [k for k, v in index["sites"].items()
               if "HVD111" in v.get("rules", ())]
    assert flagged, index  # the analyzer must flag the worker's own sites
    idx_path = tmp_path / "worker_ps_index.json"
    idx_path.write_text(json.dumps(index))

    res = _run_torovodrun(
        2, WORKER_PS, timeout=300,
        extra_env={"HVD_TPU_SANITIZER": "1",
                   "HVD_TPU_SANITIZER_STATIC_INDEX": str(idx_path)})
    ok = res.stdout.count("PROCESS_SET_OK")
    assert res.returncode == 0 and ok == 2, (
        f"rc={res.returncode}\nstdout:\n{res.stdout[-3000:]}\n"
        f"stderr:\n{res.stderr[-3000:]}")


WORKER_EST = os.path.join(REPO, "tests", "data", "worker_estimator.py")


def test_torovodrun_estimator_sharded_training(tmp_path):
    """Estimator pipeline across real processes (VERDICT missing #3):
    shared-store materialization, per-rank shard reads, coordinator-avg
    gradients, identical final params on every rank."""
    res = _run_torovodrun(2, WORKER_EST, timeout=300,
                          extra_env={"EST_DIR": str(tmp_path)})
    ok = res.stdout.count("EST_OK")
    assert res.returncode == 0 and ok == 2, (
        f"rc={res.returncode}\nstdout:\n{res.stdout[-3000:]}\n"
        f"stderr:\n{res.stderr[-3000:]}")


WORKER_CACHE = os.path.join(REPO, "tests", "data", "worker_cache.py")


def test_torovodrun_response_cache_steady_state():
    """PR 2 acceptance: after warm-up, steady-state cycles exchange only
    the bitvector frame (frame-count assertion inside the worker), a shape
    change falls back to full negotiation on all ranks, and bf16-wire
    allreduce matches fp32 while reusing one cached program."""
    res = _run_torovodrun(2, WORKER_CACHE, timeout=300)
    ok = res.stdout.count("CACHE_OK")
    assert res.returncode == 0 and ok == 2, (
        f"rc={res.returncode}\nstdout:\n{res.stdout[-3000:]}\n"
        f"stderr:\n{res.stderr[-3000:]}")


WORKER_PIPELINE = os.path.join(REPO, "tests", "data", "worker_pipeline.py")


def test_torovodrun_pipeline():
    """PR 3 acceptance: chunked fused collectives + in-flight dispatch
    window + priority drain produce bitwise-identical results vs the
    legacy single-chunk inline path (with and without bf16 wire
    compression), the steady-state response-cache frame guarantee holds
    with the pipeline on, and the FusedProgramCache stays bounded by
    chunk-count keying (assertions live in the worker)."""
    res = _run_torovodrun(2, WORKER_PIPELINE, timeout=300)
    ok = res.stdout.count("PIPELINE_OK")
    assert res.returncode == 0 and ok == 2, (
        f"rc={res.returncode}\nstdout:\n{res.stdout[-3000:]}\n"
        f"stderr:\n{res.stderr[-3000:]}")


WORKER_FASTLANE = os.path.join(REPO, "tests", "data", "worker_fastlane.py")


def test_torovodrun_fast_lane():
    """ISSUE 8 acceptance: the latency fast lane (single-tensor dispatch
    through slot-pinned persistent programs) + ByteScheduler partitioning
    produce bitwise-identical results vs the fused whole-tensor path
    (with and without bf16 wire compression), the steady-state response-
    cache frame guarantee holds with both knobs on, the negotiation round
    count per step is unchanged, and the pinned-program path actually
    served warm dispatches (assertions live in the worker)."""
    res = _run_torovodrun(2, WORKER_FASTLANE, timeout=300)
    ok = res.stdout.count("FASTLANE_OK")
    assert res.returncode == 0 and ok == 2, (
        f"rc={res.returncode}\nstdout:\n{res.stdout[-3000:]}\n"
        f"stderr:\n{res.stderr[-3000:]}")


WORKER_FLAT = os.path.join(REPO, "tests", "data", "worker_flat.py")


def test_torovodrun_flat_gradients():
    """ISSUE 34: a gradient tree through the engine as one flat buffer a
    dtype, across two real processes — reduced gradients, updates and
    optimizer state bitwise those of the leaf-an-item path on a
    rank-dependent stream (assertions live in the worker)."""
    res = _run_torovodrun(2, WORKER_FLAT, timeout=300)
    ok = res.stdout.count("FLAT_OK")
    assert res.returncode == 0 and ok == 2, (
        f"rc={res.returncode}\nstdout:\n{res.stdout[-3000:]}\n"
        f"stderr:\n{res.stderr[-3000:]}")


WORKER_SHARDED = os.path.join(REPO, "tests", "data", "worker_sharded.py")


def test_torovodrun_sharded_optimizer():
    """ISSUE 15 acceptance: DistributedOptimizer(sharded=True) — per-
    bucket reduce-scatter, 1/N shard update, allgather — produces
    BITWISE-identical parameters to the replicated path after 10 steps on
    the same gradient stream, optimizer-state bytes/rank scale ~1/N, the
    steady-state warm path stays on the pinned bitvector frame, and the
    chunked scatter→update→gather pipeline engages with results unchanged
    (assertions live in the worker)."""
    res = _run_torovodrun(2, WORKER_SHARDED, timeout=300)
    ok = res.stdout.count("SHARDED_OK")
    assert res.returncode == 0 and ok == 2, (
        f"rc={res.returncode}\nstdout:\n{res.stdout[-3000:]}\n"
        f"stderr:\n{res.stderr[-3000:]}")


def test_torovodrun_sharded_optimizer_hierarchical():
    """The same ZeRO acceptance through the two-level control plane: the
    per-host agent aggregates the sharded ops' warm-path frames exactly
    like allreduce's — parity, 1/N state and the frame guard must all
    hold behind an agent."""
    res = _run_torovodrun(2, WORKER_SHARDED, timeout=300,
                          extra_args=("--hierarchical-controller",))
    ok = res.stdout.count("SHARDED_OK")
    assert res.returncode == 0 and ok == 2, (
        f"rc={res.returncode}\nstdout:\n{res.stdout[-3000:]}\n"
        f"stderr:\n{res.stderr[-3000:]}")


WORKER_FSDP = os.path.join(REPO, "tests", "data", "worker_fsdp.py")


def test_torovodrun_full_sharding():
    """ISSUE 18 acceptance: DistributedOptimizer(sharded="full") — the
    ZeRO-3/FSDP pipeline (prefetch-lane parameter allgather, gradient
    reduce-scatter into the resident 1/N shard, shard-local update) —
    produces BITWISE-identical parameters to the replicated path after 10
    steps on the same gradient stream, resident param+opt bytes scale
    ~1/N, bucket k+1's gather overlaps bucket k (prefetch counters), the
    warm path stays on the pinned bitvector frame with prefetch armed,
    and the shard-native saveable round-trips (assertions live in the
    worker)."""
    res = _run_torovodrun(2, WORKER_FSDP, timeout=300)
    ok = res.stdout.count("FSDP_OK")
    assert res.returncode == 0 and ok == 2, (
        f"rc={res.returncode}\nstdout:\n{res.stdout[-3000:]}\n"
        f"stderr:\n{res.stderr[-3000:]}")


def test_torovodrun_full_sharding_hierarchical():
    """The same FSDP acceptance through the two-level control plane: the
    per-host agent aggregates the prefetch-lane gathers' warm-path frames
    exactly like allreduce's — parity, 1/N residency, overlap and the
    frame guard must all hold behind an agent."""
    res = _run_torovodrun(2, WORKER_FSDP, timeout=300,
                          extra_args=("--hierarchical-controller",))
    ok = res.stdout.count("FSDP_OK")
    assert res.returncode == 0 and ok == 2, (
        f"rc={res.returncode}\nstdout:\n{res.stdout[-3000:]}\n"
        f"stderr:\n{res.stderr[-3000:]}")


WORKER_SERVE = os.path.join(REPO, "tests", "data", "worker_serve.py")


def test_torovodrun_serving():
    """ISSUE 19 acceptance: the data-parallel serving plane across real
    processes — version-stamped weight fan-out over the collective
    broadcast path (rank 1 starts from zeros, ends bitwise identical;
    re-delivery is a no-op; a rolling update re-broadcasts without
    restart), batched-vs-sequential forward bitwise parity with the
    per-bucket program cache pinned, the serving-mode ScalePolicy's
    scripted ramp → scale_out → drain sequence, and the drain contract
    under live load (in-flight requests complete, new admissions
    refused).  Assertions live in the worker."""
    res = _run_torovodrun(2, WORKER_SERVE, timeout=300)
    ok = res.stdout.count("SERVE_OK")
    assert res.returncode == 0 and ok == 2, (
        f"rc={res.returncode}\nstdout:\n{res.stdout[-3000:]}\n"
        f"stderr:\n{res.stderr[-3000:]}")


def test_torovodrun_serving_hierarchical():
    """The same serving acceptance through the two-level control plane:
    the per-host agent aggregates the broadcast fan-out's warm-path
    frames exactly like allreduce's — fan-out parity, the version-stamp
    no-op and the drain contract must all hold behind an agent."""
    res = _run_torovodrun(2, WORKER_SERVE, timeout=300,
                          extra_args=("--hierarchical-controller",))
    ok = res.stdout.count("SERVE_OK")
    assert res.returncode == 0 and ok == 2, (
        f"rc={res.returncode}\nstdout:\n{res.stdout[-3000:]}\n"
        f"stderr:\n{res.stderr[-3000:]}")


WORKER_SERVE_FAULTS = os.path.join(REPO, "tests", "data",
                                   "worker_serve_faults.py")


@pytest.mark.parametrize("controller", ["flat", "hierarchical"])
def test_torovodrun_serving_fault_recovery(tmp_path, controller):
    """ISSUE 20 acceptance (the scripted chaos scenario, both control
    planes): under the elastic driver, HVD_TPU_FAULT=replica_crash:1@3
    kills rank 1 uncleanly inside its 3rd dispatched batch while 24
    concurrent front-door requests are in flight.  The survivor's serve
    loop fails the interrupted batch RETRYABLY, preserves the queued
    buckets with their original deadlines, re-raises the typed verdict,
    heals through the elastic path (re-rendezvous + no-op versioned
    re-arm), and the SAME batcher resumes: the interrupted requests
    re-enter via front-door retries and complete BITWISE identical to
    their per-request references — zero accepted requests lost, exactly
    one terminal response each.  The proof is the result file the
    survivor writes; the driver exits 0."""
    import json
    hostfile = tmp_path / "hosts.txt"
    hostfile.write_text("localhost:1\n127.0.0.1:1\n")
    result = tmp_path / "serve_fault_result.json"
    env = dict(os.environ)
    other_paths = [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                   if p]
    env["PYTHONPATH"] = os.pathsep.join([REPO] + other_paths)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("HOROVOD_TIMELINE", None)
    env.update({
        "FAULT_RESULT": str(result),
        "HVD_TPU_FAULT": "replica_crash:1@3",
        "HOROVOD_ROUND_TIMEOUT_S": "30",
    })
    cmd = [sys.executable, "-m", "horovod_tpu.runner.launch",
           "--host-discovery-script", f"cat {hostfile}",
           "--min-np", "1", "--max-np", "2"]
    if controller == "hierarchical":
        cmd.append("--hierarchical-controller")
    cmd += [sys.executable, WORKER_SERVE_FAULTS]
    res = subprocess.run(cmd, env=env, capture_output=True, text=True,
                         timeout=300)
    assert res.returncode == 0, (
        f"rc={res.returncode}\nstdout:\n{res.stdout[-3000:]}\n"
        f"stderr:\n{res.stderr[-3000:]}")
    assert result.exists(), res.stdout[-3000:]
    data = json.loads(result.read_text())
    assert data["ok"], data
    assert data["lost"] == 0, data
    assert data["retried"] == 4, data            # the interrupted bucket
    assert data["requeued"] == 8, data           # the two preserved ones
    assert data["availability"] == 1.0, data
    assert data["final_size"] == 1, data
    assert data["faults"], data
    assert data["recovery_s"] < 60, data


WORKER_MONITOR = os.path.join(REPO, "tests", "data", "worker_monitor.py")


def test_torovodrun_monitor_acceptance():
    """Monitor-subsystem acceptance (the tentpole's two-process proof):
    cross-rank snapshot aggregation through the coordinator side-channel,
    the steady-state frame guard holding with monitoring ON, a forced
    stall on rank 1 producing an HVD302 report on rank 0 that quotes rank
    1's ledger tail, and /health reflecting the stall then recovering.
    Assertions live in the worker."""
    port = _free_port()
    res = _run_torovodrun(2, WORKER_MONITOR, timeout=300, extra_env={
        "HOROVOD_MONITOR": "1",
        "HOROVOD_MONITOR_INTERVAL": "0.2",
        "HOROVOD_MONITOR_PORT": str(port),
        "HVD_TPU_SANITIZER": "1",
        "HVD_TPU_SANITIZER_TIMEOUT": "2",
    })
    ok = res.stdout.count("MONITOR_OK")
    assert res.returncode == 0 and ok == 2, (
        f"rc={res.returncode}\nstdout:\n{res.stdout[-3000:]}\n"
        f"stderr:\n{res.stderr[-3000:]}")


WORKER_TRACE = os.path.join(REPO, "tests", "data", "worker_trace.py")


def test_torovodrun_trace_acceptance(tmp_path):
    """ISSUE 6 acceptance: two ranks run with --trace-filename +
    HOROVOD_MONITOR=1; in-worker assertions cover the armed tracer, the
    phase-sum/lifecycle consistency, the steady-state frame guard with
    tracing ON (digest inside the size cap) and the peer's digest arriving
    over the MON1 side-channel.  Launcher-side, `python -m
    horovod_tpu.trace` merges the two per-rank files into one chrome trace
    with a lane per rank and cycle-correlated flow arrows."""
    base = str(tmp_path / "tr")
    res = _run_torovodrun(2, WORKER_TRACE, timeout=300,
                          extra_args=("--trace-filename", base),
                          extra_env={
                              "HOROVOD_MONITOR": "1",
                              "HOROVOD_MONITOR_INTERVAL": "0.2",
                          })
    ok = res.stdout.count("TRACE_OK")
    assert res.returncode == 0 and ok == 2, (
        f"rc={res.returncode}\nstdout:\n{res.stdout[-3000:]}\n"
        f"stderr:\n{res.stderr[-3000:]}")
    assert os.path.exists(base + ".0") and os.path.exists(base + ".1")
    merged_path = str(tmp_path / "merged.json")
    import json
    merge = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.trace", base,
         "-o", merged_path, "--report"],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "PYTHONPATH": REPO})
    assert merge.returncode == 0, (merge.stdout, merge.stderr)
    assert "critical-path attribution" in merge.stdout
    with open(merged_path) as fh:
        merged = json.load(fh)
    ev = merged["traceEvents"]
    # One lane per rank...
    names = {e["args"]["name"] for e in ev
             if e.get("ph") == "M" and e["name"] == "process_name"}
    assert names == {"rank 0", "rank 1"}, names
    assert {e["pid"] for e in ev if e.get("ph") == "X"} == {0, 1}
    # ...with cycle-correlated flows: each flow id starts on one rank and
    # finishes on the other (the same lock-step round on both lanes).
    starts = {e["id"]: e["pid"] for e in ev if e.get("ph") == "s"}
    ends = {e["id"]: e["pid"] for e in ev if e.get("ph") == "f"}
    common = set(starts) & set(ends)
    assert common, (starts, ends)
    assert all(starts[c] != ends[c] for c in common)
    # Both ranks' tensor lanes carry the five phases.
    phases = {e["name"] for e in ev if e.get("ph") == "X"
              and e.get("tid", 0) != 0}
    assert {"QUEUE", "NEGOTIATION", "COPY_IN", "REDUCE",
            "DRAIN"} <= phases, phases


WORKER_FAULTS = os.path.join(REPO, "tests", "data", "worker_faults.py")


@pytest.mark.parametrize("pipeline", [1, 2], ids=["lockstep", "pipelined"])
def test_torovodrun_dead_rank_aborts_with_attribution(tmp_path, pipeline):
    """ISSUE 5 acceptance (static half): with HVD_TPU_FAULT=
    mid_round_exit:1:crash, rank 1 dies uncleanly mid-negotiation and rank
    0 raises a typed HVD303 PeerFailureError naming rank 1 within
    HOROVOD_ROUND_TIMEOUT_S — no hang, no wedged waiters (a pre-existing
    pending handle settles with the fault, new work fails fast).  The
    proof is the result file rank 0 writes before the launcher reaps it;
    the launcher's nonzero exit (rank 1's crash) is expected.  Swept with
    HOROVOD_ROUND_PIPELINE=2 (ISSUE 11): a deferred response must carry
    the typed abort to the survivor exactly like a lock-step one."""
    import json
    result = tmp_path / "fault_result.json"
    res = _run_torovodrun(2, WORKER_FAULTS, timeout=300, extra_env={
        "FAULT_MODE": "static",
        "FAULT_RESULT": str(result),
        "HVD_TPU_FAULT": "mid_round_exit:1:crash:300",
        "HOROVOD_ROUND_TIMEOUT_S": "30",
        "HOROVOD_ROUND_PIPELINE": str(pipeline),
    })
    assert res.returncode != 0, (
        "rank 1's unclean crash must fail the launch\n"
        f"stdout:\n{res.stdout[-2000:]}")
    assert result.exists(), (
        f"rank 0 never recorded the typed abort\nstdout:\n"
        f"{res.stdout[-3000:]}\nstderr:\n{res.stderr[-3000:]}")
    data = json.loads(result.read_text())
    assert data["ok"] and data["mode"] == "static", data
    assert data["dead_ranks"] == [1] and data["hvd303"], data
    assert data["elapsed_s"] < 30, data


def test_torovodrun_elastic_rerendezvous_after_crash(tmp_path):
    """ISSUE 5 acceptance (elastic half): the same mid-negotiation crash
    under the elastic driver.  Two single-slot local 'hosts' (localhost +
    127.0.0.1) so blacklisting the crashed host leaves a surviving world:
    the survivor catches the typed PeerFailureError, restores committed
    state, re-rendezvouses into the shrunk generation and completes every
    epoch; the driver exits 0."""
    import json
    hostfile = tmp_path / "hosts.txt"
    hostfile.write_text("localhost:1\n127.0.0.1:1\n")
    result = tmp_path / "fault_result.json"
    env = dict(os.environ)
    other_paths = [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                   if p]
    env["PYTHONPATH"] = os.pathsep.join([REPO] + other_paths)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("HOROVOD_TIMELINE", None)
    env.update({
        "FAULT_MODE": "elastic",
        "FAULT_RESULT": str(result),
        "FAULT_EPOCHS": "6",
        "HVD_TPU_FAULT": "mid_round_exit:1:crash:600",
        "HOROVOD_ROUND_TIMEOUT_S": "30",
    })
    cmd = [sys.executable, "-m", "horovod_tpu.runner.launch",
           "--host-discovery-script", f"cat {hostfile}",
           "--min-np", "1", "--max-np", "2",
           sys.executable, WORKER_FAULTS]
    res = subprocess.run(cmd, env=env, capture_output=True, text=True,
                         timeout=300)
    assert res.returncode == 0, (
        f"rc={res.returncode}\nstdout:\n{res.stdout[-3000:]}\n"
        f"stderr:\n{res.stderr[-3000:]}")
    assert result.exists(), res.stdout[-3000:]
    data = json.loads(result.read_text())
    assert data["ok"] and data["mode"] == "elastic", data
    assert data["epochs"] == 6, data
    assert data["final_size"] == 1, data
    assert data["resets"] >= 1, data
    # The reset was triggered by the TYPED control-plane error, not a
    # blind socket failure.
    assert any(kind == "PeerFailureError" and ranks == [1]
               for kind, ranks in data["caught"]), data


def test_torovodrun_hierarchical_controller_collectives():
    """ISSUE 9 acceptance (happy path): the two-level control plane across
    two simulated hosts — each worker talks to its host's aggregation
    agent, the root sees one connection per host — produces the same
    collective results as flat mode (the worker's own assertions)."""
    res = _run_torovodrun(2, WORKER,
                          extra_args=("-H", "localhost:1,127.0.0.1:1",
                                      "--hierarchical-controller"))
    ok = res.stdout.count("WORKER_OK")
    assert res.returncode == 0 and ok == 2, (
        f"rc={res.returncode}\nstdout:\n{res.stdout[-3000:]}\n"
        f"stderr:\n{res.stderr[-3000:]}")


def test_torovodrun_hierarchical_single_host_agent():
    """Both ranks behind ONE agent (the -np 2 localhost default): the
    agent aggregates its whole world and the root negotiates with a single
    connection."""
    res = _run_torovodrun(2, WORKER, extra_args=("--hierarchical-controller",))
    ok = res.stdout.count("WORKER_OK")
    assert res.returncode == 0 and ok == 2, (
        f"rc={res.returncode}\nstdout:\n{res.stdout[-3000:]}\n"
        f"stderr:\n{res.stderr[-3000:]}")


@pytest.mark.parametrize("pipeline", [1, 2], ids=["lockstep", "pipelined"])
def test_torovodrun_hierarchical_agent_crash_attributed(tmp_path, pipeline):
    """ISSUE 9 acceptance (fault half, the 2-proc/2-'host' worker): rank
    1 — alone on its simulated host — crashes mid-negotiation, killing its
    host agent with it.  The root attributes the severed AGENT connection
    to the host's ranks, and rank 0 records a typed HVD303
    PeerFailureError naming rank 1 within the round deadline — no wedged
    waiters (same contract as the flat-mode test above, now through two
    agents).  Swept with HOROVOD_ROUND_PIPELINE=2 (ISSUE 11): agent death
    must surface through a deferred read too."""
    import json
    hostfile = tmp_path / "hosts.txt"
    hostfile.write_text("localhost slots=1\n127.0.0.1 slots=1\n")
    result = tmp_path / "fault_result.json"
    res = _run_torovodrun(2, WORKER_FAULTS, timeout=300,
                          extra_args=("--hostfile", str(hostfile),
                                      "--hierarchical-controller"),
                          extra_env={
                              "FAULT_MODE": "static",
                              "FAULT_RESULT": str(result),
                              "HVD_TPU_FAULT": "mid_round_exit:1:crash:300",
                              "HOROVOD_ROUND_TIMEOUT_S": "30",
                              "HOROVOD_ROUND_PIPELINE": str(pipeline),
                          })
    assert res.returncode != 0, (
        "rank 1's unclean crash must fail the launch\n"
        f"stdout:\n{res.stdout[-2000:]}")
    assert result.exists(), (
        f"rank 0 never recorded the typed abort\nstdout:\n"
        f"{res.stdout[-3000:]}\nstderr:\n{res.stderr[-3000:]}")
    data = json.loads(result.read_text())
    assert data["ok"] and data["mode"] == "static", data
    assert data["dead_ranks"] == [1] and data["hvd303"], data
    assert data["elapsed_s"] < 30, data


def test_torovodrun_hierarchical_monitor_acceptance():
    """Monitor fan-in through the agents: cross-rank aggregation, the
    HVD302 peer-ledger report and /health must all survive the MON1 blobs
    being deduplicated into per-host uplinks (worker assertions unchanged
    from the flat monitor acceptance)."""
    port = _free_port()
    res = _run_torovodrun(2, WORKER_MONITOR, timeout=300,
                          extra_args=("--hierarchical-controller",),
                          extra_env={
                              "HOROVOD_MONITOR": "1",
                              "HOROVOD_MONITOR_INTERVAL": "0.2",
                              "HOROVOD_MONITOR_PORT": str(port),
                              "HVD_TPU_SANITIZER": "1",
                              "HVD_TPU_SANITIZER_TIMEOUT": "2",
                          })
    ok = res.stdout.count("MONITOR_OK")
    assert res.returncode == 0 and ok == 2, (
        f"rc={res.returncode}\nstdout:\n{res.stdout[-3000:]}\n"
        f"stderr:\n{res.stderr[-3000:]}")


def test_torovodrun_sanitizer_catches_divergence_on_cached_path():
    """PR 2 acceptance: HVD_TPU_SANITIZER=1 still catches divergent
    submission order when both ranks are on the cached/bitvector path (the
    worker asserts zero full announces during the divergent cycle)."""
    res = _run_torovodrun(2, WORKER_CACHE, timeout=300,
                          extra_env={"HVD_TPU_SANITIZER": "1"})
    ok = res.stdout.count("CACHE_SANITIZER_OK")
    assert res.returncode == 0 and ok == 2, (
        f"rc={res.returncode}\nstdout:\n{res.stdout[-3000:]}\n"
        f"stderr:\n{res.stderr[-3000:]}")


WORKER_LEAVE = os.path.join(REPO, "tests", "data", "worker_leave.py")


def _leave_env(result, mode, pipeline=1, spec=0):
    return {
        "LEAVE_MODE": mode,
        "LEAVE_RESULT": str(result),
        "HOROVOD_ROUND_TIMEOUT_S": "30",
        "HOROVOD_MONITOR": "1",
        "HOROVOD_MONITOR_INTERVAL": "0.2",
        "HOROVOD_ROUND_PIPELINE": str(pipeline),
        "HOROVOD_SPEC_READY_AFTER": str(spec),
    }


def _assert_clean_leave(res, result):
    import json
    assert res.returncode == 0, (
        f"clean LEAVE must not fail the launch (rc={res.returncode})\n"
        f"stdout:\n{res.stdout[-3000:]}\nstderr:\n{res.stderr[-3000:]}")
    assert result.exists(), (
        f"rank 0 never recorded the leave\nstdout:\n{res.stdout[-3000:]}\n"
        f"stderr:\n{res.stderr[-3000:]}")
    data = json.loads(result.read_text())
    assert data["ok"] and data["mode"] == "clean", data
    assert data["verdict"] == "PeerLeftInterrupt", data
    assert data["left_ranks"] == [1], data
    assert data["fault"] is None, data
    assert data["health_status"] == "ok", data
    assert data["health_left"] == [1], data
    with open(str(result) + ".r1") as fh:
        r1 = json.load(fh)
    assert r1["ok"] and r1["leave_sent"] is True, r1


@pytest.mark.parametrize("pipeline,spec", [(1, 0), (2, 0), (1, 1)],
                         ids=["lockstep", "pipelined", "speculative"])
def test_torovodrun_clean_leave_vs_sever(tmp_path, pipeline, spec):
    """ISSUE 10 acceptance (both halves, one worker script): a worker that
    sends the protocol-v6 LEAVE mid-run exits 0 with the survivor
    continuing — PeerLeftInterrupt (a HostsUpdatedInterrupt), engine.fault
    None, /health ok with rank 1 reported left, launcher rc 0 — while the
    SAME sever without a LEAVE frame still produces the typed attributed
    HVD303 abort naming rank 1.  The frame, not timing luck, is what
    disambiguates.  Swept with ISSUE 11's knobs: HOROVOD_ROUND_PIPELINE=2
    (the leaver drains its in-flight window before the LEAVE goes out, so
    the v6 semantics hold with rounds in flight) and
    HOROVOD_SPEC_READY_AFTER=1 (the v7 machinery armed across a clean
    departure; the spec-dispatch-raced-a-LEAVE window is closed by the
    engine settling its in-flight ring with the same interrupt)."""
    import json
    # Half 1: clean.
    result = tmp_path / "leave_clean.json"
    res = _run_torovodrun(2, WORKER_LEAVE, timeout=300,
                          extra_env=_leave_env(result, "clean", pipeline,
                                               spec))
    _assert_clean_leave(res, result)

    # Half 2: the control — same departure point, no LEAVE frame.
    result2 = tmp_path / "leave_sever.json"
    res2 = _run_torovodrun(2, WORKER_LEAVE, timeout=300,
                           extra_env=_leave_env(result2, "sever", pipeline,
                                                spec))
    assert res2.returncode != 0, (
        "the unclean sever must fail the launch\n"
        f"stdout:\n{res2.stdout[-2000:]}")
    assert result2.exists(), (
        f"rank 0 never recorded the typed abort\nstdout:\n"
        f"{res2.stdout[-3000:]}\nstderr:\n{res2.stderr[-3000:]}")
    data = json.loads(result2.read_text())
    assert data["ok"] and data["mode"] == "sever", data
    assert data["verdict"] == "PeerFailureError", data
    assert data["dead_ranks"] == [1] and data["hvd303"], data


def test_torovodrun_clean_leave_hierarchical(tmp_path):
    """The PR 8 follow-up, end to end: the same clean LEAVE through the
    per-host agent (protocol v5 + v6 composed) — the host's uplink
    shrinks, the survivor continues, /health stays ok."""
    result = tmp_path / "leave_hier.json"
    res = _run_torovodrun(2, WORKER_LEAVE, timeout=300,
                          extra_args=("--hierarchical-controller",),
                          extra_env=_leave_env(result, "clean"))
    _assert_clean_leave(res, result)


@pytest.mark.parametrize("knobs", [
    {"HOROVOD_SPEC_READY_AFTER": "1"},
    {"HOROVOD_ROUND_PIPELINE": "2"},
    {"HOROVOD_SPEC_READY_AFTER": "1", "HOROVOD_ROUND_PIPELINE": "2"},
], ids=["spec", "pipeline", "both"])
def test_torovodrun_zero_rtt_collectives(knobs):
    """ISSUE 11 acceptance (results half): the full collective worker —
    which asserts numeric correctness of every op against the expected
    values — runs green with speculative readiness and/or pipelined
    rounds on.  Zero-RTT changes WHEN verdicts return, never what
    executes: the same assertions that pin lock-step results pin these."""
    res = _run_torovodrun(2, WORKER, extra_env=knobs)
    ok = res.stdout.count("WORKER_OK")
    assert res.returncode == 0 and ok == 2, (
        f"rc={res.returncode}\nstdout:\n{res.stdout[-3000:]}\n"
        f"stderr:\n{res.stderr[-3000:]}")


def test_torovodrun_zero_rtt_hierarchical_collectives():
    """ISSUE 11 through the per-host agents: speculation's confirm-bearing
    warm frames must keep aggregating (host_agent treats an identical
    ZRT7 confirm as part of the warm core) while results stay correct."""
    res = _run_torovodrun(2, WORKER,
                          extra_args=("--hierarchical-controller",),
                          extra_env={"HOROVOD_SPEC_READY_AFTER": "1"})
    ok = res.stdout.count("WORKER_OK")
    assert res.returncode == 0 and ok == 2, (
        f"rc={res.returncode}\nstdout:\n{res.stdout[-3000:]}\n"
        f"stderr:\n{res.stderr[-3000:]}")


WORKER_AUTOSCALE = os.path.join(REPO, "tests", "data",
                                "worker_autoscale.py")


@pytest.mark.parametrize("hier", [False, True], ids=["flat", "hier"])
def test_autoscale_simulated_load_scenario(tmp_path, hier):
    """ISSUE 10 acceptance: the closed loop, end to end, over real
    processes and the real wire stack (rendezvous + native lock-step
    negotiation — flat and through real per-host agents — + MON1 monitor
    aggregation + rank-0 /health + DRAIN pings + protocol-v6 LEAVEs):

    traffic ramp → policy scales OUT (scale command adds a host, the
    world grows) → injected straggler → policy EVICTS it with monitor
    attribution (drain → clean LEAVE → exit 0, host cordoned, never
    blacklisted) → world heals → idle → policy scales IN → the run ends
    with every worker exiting 0 and the driver returning success."""
    import json
    import threading as _threading
    import time as _time

    from horovod_tpu.common.net import free_ports
    from horovod_tpu.elastic.autoscale import ScalePolicy
    from horovod_tpu.elastic.discovery import HostDiscoveryScript
    from horovod_tpu.elastic.driver import ElasticDriver

    sdir = tmp_path / "autoscale"
    sdir.mkdir()
    hosts = tmp_path / "hosts"
    hosts.write_text("127.0.0.1:1\n127.0.0.2:1\n")
    (sdir / "load").write_text("0")
    (sdir / "straggler").write_text("")
    scale_sh = tmp_path / "scale.sh"
    scale_sh.write_text(f"""#!/bin/sh
case "$HVD_AUTOSCALE_ACTION" in
  scale_out)
    grep -q '^127.0.0.3:' {hosts} || echo '127.0.0.3:1' >> {hosts} ;;
  evict|scale_in)
    grep -v "^$HVD_AUTOSCALE_HOST:" {hosts} > {hosts}.tmp
    mv {hosts}.tmp {hosts} ;;
esac
""")
    scale_sh.chmod(0o755)

    (monitor_port,) = free_ports(1)
    env = {k: v for k, v in os.environ.items()}
    other_paths = [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                   if p]
    extra_env = {
        "PYTHONPATH": os.pathsep.join([REPO] + other_paths),
        "AUTOSCALE_DIR": str(sdir),
        "HOROVOD_MONITOR_PORT": str(monitor_port),
    }
    if hier:
        extra_env["HOROVOD_HIERARCHICAL_CONTROLLER"] = "1"

    policy = ScalePolicy(min_np=1, max_np=3, queue_high=10.0,
                         queue_trend_up=1e9,   # absolute threshold drives
                         straggler_factor=3.0, persistence=2,
                         cooldown_s=2.0, idle_s=2.0)
    logs = tmp_path / "logs"
    d = ElasticDriver(
        HostDiscoveryScript(f"cat {hosts}"),
        [sys.executable, WORKER_AUTOSCALE],
        min_np=1, max_np=3, env=extra_env,
        discovery_interval_s=0.25, start_timeout_s=120,
        autoscale_policy=policy, autoscale_interval_s=0.4,
        scale_command=f"sh {scale_sh}", verbose=1,
        output_filename=str(logs))

    rc = {}
    t = _threading.Thread(target=lambda: rc.update(code=d.run()),
                          daemon=True)
    t.start()

    def wait_for(cond, what, timeout=60):
        deadline = _time.monotonic() + timeout
        while _time.monotonic() < deadline:
            if cond():
                return
            if rc:
                raise AssertionError(
                    f"driver exited rc={rc} while waiting for {what}; "
                    f"events={d.events}")
            _time.sleep(0.1)
        raise AssertionError(f"timed out waiting for {what}; "
                             f"events={d.events} assigned="
                             f"{sorted(d._assigned)} procs="
                             f"{sorted(d._procs)}")

    try:
        # Phase 0: the initial 2-host world forms.
        wait_for(lambda: len(d._procs) == 2, "initial world")

        # Phase 1: traffic ramp → scale out → the world grows to 3.
        (sdir / "load").write_text("40")
        wait_for(lambda: any(e["action"] == "scale_out"
                             for e in d.events), "scale_out decision")
        wait_for(lambda: len(d._assigned) == 3 and len(d._procs) == 3,
                 "world grown to 3")

        # Phase 2: straggler injected on rank 1 → attributed evict →
        # drain → clean exit → the world heals WITHOUT 127.0.0.2.
        straggler_identity = next(
            i for i, a in d._assigned.items() if a["rank"] == 1)
        straggler_host = d._assigned[straggler_identity]["hostname"]
        (sdir / "straggler").write_text("1")
        wait_for(lambda: any(e["action"] == "evict" for e in d.events),
                 "evict decision")
        ev = next(e for e in d.events if e["action"] == "evict")
        assert ev["evict_rank"] == 1, ev
        assert ev["host"] == straggler_host, ev
        assert "monitor attribution" in ev["reason"], ev["reason"]
        (sdir / "straggler").write_text("")
        wait_for(lambda: straggler_host in d._cordoned
                 and len(d._assigned) == 2
                 and straggler_host not in
                 {a["hostname"] for a in d._assigned.values()},
                 "world healed without the straggler")
        assert not d.registry.is_blacklisted(straggler_host)
        assert d.registry.state_of(straggler_identity) == "LEFT"

        # Phase 3: idle → scale in → the world shrinks.
        (sdir / "load").write_text("0")
        wait_for(lambda: any(e["action"] == "scale_in"
                             for e in d.events), "scale_in decision")
        wait_for(lambda: len(d._assigned) == 1, "world shrunk to 1")

        # Phase 4: done → every worker exits 0 → driver succeeds.
        (sdir / "done").write_text("1")
        t.join(timeout=60)
        assert not t.is_alive(), "driver never finished"
        assert rc.get("code") == 0, (rc, d.events)

        actions = [e["action"] for e in d.events]
        assert actions.index("scale_out") < actions.index("evict") \
            < actions.index("scale_in"), actions
        # Clean departures only: nothing was ever blacklisted.
        assert d.registry.blacklist() == set(), d.registry.blacklist()

        # ISSUE 12 — checkpoint pacing: every non-hold decision is
        # preceded by a COMMIT ping; at least one live worker logged the
        # paced commit request.
        all_logs = "".join(p.read_text()
                           for p in logs.glob("*/stdout") if p.exists())
        assert "commit requested by the driver" in all_logs, (
            all_logs[-3000:])
        if hier:
            # ISSUE 12 acceptance — elastic × hierarchical: the SAME
            # agent object (same process, same listen port) served >= 2
            # re-rendezvous generations on the long-lived coordinator
            # host, instead of the fleet being silently forced flat.
            coord_log = (logs / "127.0.0.1.0" / "stdout").read_text()
            assert "agent generation 1" in coord_log, coord_log[-3000:]
            assert "agent generation 2" in coord_log, coord_log[-3000:]
    finally:
        (sdir / "done").write_text("1")
        _time.sleep(0.5)
        d._shutdown_workers()


@pytest.mark.parametrize("hier", [False, True], ids=["flat", "hier"])
def test_preemption_drain_scenario(tmp_path, hier):
    """ISSUE 12 acceptance — preemption-driven drains, end to end over
    real processes and the real wire stack, flat AND hierarchical: a
    discovery preemption notice for one host makes the driver request a
    state commit (checkpoint pacing), cordon the host and DRAIN its
    worker — which finishes, sends the protocol-v6 clean LEAVE, and exits
    0 — so the departure is classified LEFT (never blacklisted, never an
    HVD303 dead-peer verdict) and the world heals without the host."""
    import json
    import threading as _threading
    import time as _time

    from horovod_tpu.elastic.discovery import HostDiscoveryScript
    from horovod_tpu.elastic.driver import ElasticDriver

    sdir = tmp_path / "autoscale"
    sdir.mkdir()
    hosts = tmp_path / "hosts"
    hosts.write_text("127.0.0.1:1\n127.0.0.2:1\n")
    (sdir / "load").write_text("1")       # busy: rounds keep turning
    (sdir / "straggler").write_text("")
    notices = tmp_path / "notices"

    class _NoticeScript(HostDiscoveryScript):
        def preemption_notices(self):
            try:
                return {ln.strip() for ln in notices.read_text().split()
                        if ln.strip()}
            except OSError:
                return set()

    env = {k: v for k, v in os.environ.items()}
    other_paths = [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                   if p]
    extra_env = {
        "PYTHONPATH": os.pathsep.join([REPO] + other_paths),
        "AUTOSCALE_DIR": str(sdir),
    }
    if hier:
        extra_env["HOROVOD_HIERARCHICAL_CONTROLLER"] = "1"

    logs = tmp_path / "logs"
    d = ElasticDriver(
        _NoticeScript(f"cat {hosts}"),
        [sys.executable, WORKER_AUTOSCALE],
        min_np=1, max_np=2, env=extra_env,
        discovery_interval_s=0.25, start_timeout_s=120, verbose=1,
        preempt_grace_s=30.0, output_filename=str(logs))

    rc = {}
    t = _threading.Thread(target=lambda: rc.update(code=d.run()),
                          daemon=True)
    t.start()

    def wait_for(cond, what, timeout=60):
        deadline = _time.monotonic() + timeout
        while _time.monotonic() < deadline:
            if cond():
                return
            if rc:
                raise AssertionError(
                    f"driver exited rc={rc} while waiting for {what}; "
                    f"events={d.events}")
            _time.sleep(0.1)
        raise AssertionError(f"timed out waiting for {what}; "
                             f"events={d.events} assigned="
                             f"{sorted(d._assigned)} procs="
                             f"{sorted(d._procs)}")

    try:
        wait_for(lambda: len(d._procs) == 2, "initial world")
        # Let a few rounds turn so the drain lands mid-run, then post the
        # preemption notice for the second host.
        _time.sleep(1.0)
        notices.write_text("127.0.0.2\n")
        wait_for(lambda: any(e["action"] == "preempt_drain"
                             for e in d.events), "preempt_drain event")
        ev = next(e for e in d.events if e["action"] == "preempt_drain")
        assert ev["host"] == "127.0.0.2", ev
        assert "preemption notice" in ev["reason"], ev
        wait_for(lambda: "127.0.0.2" in d._cordoned
                 and d.registry.state_of("127.0.0.2:0") == "LEFT"
                 and len(d._assigned) == 1
                 and "127.0.0.2" not in
                 {a["hostname"] for a in d._assigned.values()},
                 "world healed without the preempted host")
        # Clean departure: LEFT, never blacklisted.
        assert not d.registry.is_blacklisted("127.0.0.2")
        assert d.registry.blacklist() == set(), d.registry.blacklist()

        (sdir / "done").write_text("1")
        t.join(timeout=60)
        assert not t.is_alive(), "driver never finished"
        assert rc.get("code") == 0, (rc, d.events)

        # The preempted worker took the PACED, CLEAN path: the commit
        # request arrived before the drain, the drain surfaced as
        # DrainRequested -> clean LEAVE, and no dead-peer verdict
        # (HVD303 / PeerFailureError) ever reached it.
        drained_log = (logs / "127.0.0.2.0" / "stdout").read_text()
        assert "commit requested by the driver" in drained_log, (
            drained_log[-3000:])
        assert "drain requested -> clean LEAVE" in drained_log, (
            drained_log[-3000:])
        assert "HVD303" not in drained_log, drained_log[-3000:]
        assert "PeerFailureError" not in drained_log, drained_log[-3000:]
        if hier:
            # The survivor's generation-surviving agent crossed into the
            # healed generation: the same object served both.
            coord_log = (logs / "127.0.0.1.0" / "stdout").read_text()
            assert "agent generation 2" in coord_log, coord_log[-3000:]
    finally:
        (sdir / "done").write_text("1")
        _time.sleep(0.5)
        d._shutdown_workers()


WORKER_STATEPLANE = os.path.join(REPO, "tests", "data",
                                 "worker_stateplane.py")
WORKER_LITE = os.path.join(REPO, "tests", "data",
                           "worker_scenario_lite.py")


@pytest.mark.parametrize("hier", [False, True], ids=["flat", "hier"])
def test_stateplane_peer_restore_scenario(tmp_path, hier):
    """ISSUE 14 acceptance: the resilient state plane end to end over
    real processes and the real wire stack, flat AND hierarchical —
    preempt notice → paced commit (acked) → drain → clean LEAVE → a
    REPLACEMENT host joins and its worker restores the committed state
    FROM THE SURVIVOR'S SHARD SERVER: source=peer, zero disk reads,
    digest bitwise-identical to the survivor's committed epoch."""
    import json
    import re as _re
    import threading as _threading
    import time as _time

    from horovod_tpu.elastic.discovery import HostDiscoveryScript
    from horovod_tpu.elastic.driver import ElasticDriver

    sdir = tmp_path / "stateplane"
    sdir.mkdir()
    ckpt = tmp_path / "ckpt"
    hosts = tmp_path / "hosts"
    hosts.write_text("127.0.0.1:1\n127.0.0.2:1\n")
    notices = tmp_path / "notices"

    class _NoticeScript(HostDiscoveryScript):
        def preemption_notices(self):
            try:
                return {ln.strip() for ln in notices.read_text().split()
                        if ln.strip()}
            except OSError:
                return set()

    env = {k: v for k, v in os.environ.items()}
    other_paths = [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                   if p]
    extra_env = {
        "PYTHONPATH": os.pathsep.join([REPO] + other_paths),
        "STATEPLANE_DIR": str(sdir),
        "HOROVOD_CKPT_DIR": str(ckpt),
    }
    if hier:
        extra_env["HOROVOD_HIERARCHICAL_CONTROLLER"] = "1"

    logs = tmp_path / "logs"
    d = ElasticDriver(
        _NoticeScript(f"cat {hosts}"),
        [sys.executable, WORKER_STATEPLANE],
        min_np=1, max_np=2, env=extra_env,
        discovery_interval_s=0.25, start_timeout_s=120, verbose=1,
        preempt_grace_s=30.0, output_filename=str(logs))

    rc = {}
    t = _threading.Thread(target=lambda: rc.update(code=d.run()),
                          daemon=True)
    t.start()

    def wait_for(cond, what, timeout=90):
        deadline = _time.monotonic() + timeout
        while _time.monotonic() < deadline:
            if cond():
                return
            if rc:
                raise AssertionError(
                    f"driver exited rc={rc} while waiting for {what}; "
                    f"events={d.events}")
            _time.sleep(0.1)
        raise AssertionError(f"timed out waiting for {what}; "
                             f"events={d.events} assigned="
                             f"{sorted(d._assigned)}")

    def log_of(identity):
        p = logs / identity.replace(":", ".") / "stdout"
        return p.read_text() if p.exists() else ""

    try:
        wait_for(lambda: len(d._procs) == 2, "initial world")
        # Let both workers commit a few epochs.
        wait_for(lambda: "committed epoch=" in log_of("127.0.0.1:0")
                 and "committed epoch=" in log_of("127.0.0.2:0"),
                 "first commits")

        # Preemption notice for the second host: paced commit (acked) →
        # cordon → drain → clean LEAVE → LEFT.
        notices.write_text("127.0.0.2\n")
        wait_for(lambda: any(e["action"] == "preempt_drain"
                             for e in d.events), "preempt_drain event")
        wait_for(lambda: d.registry.state_of("127.0.0.2:0") == "LEFT"
                 and len(d._assigned) == 1,
                 "world healed without the preempted host")
        # ISSUE 14 bugfix evidence: the paced-commit fan-out recorded
        # per-worker acks BEFORE the cordon.
        ack_ev = next(e for e in d.events
                      if e["action"] == "commit_request")
        assert ack_ev["acks"], ack_ev

        # The REPLACEMENT host appears; the survivor's newest commit is
        # what the new worker must receive peer-to-peer.
        hosts.write_text("127.0.0.1:1\n127.0.0.3:1\n")
        wait_for(lambda: "restored epoch=" in log_of("127.0.0.3:0"),
                 "replacement restored")
        m = _re.search(
            r"restored epoch=(\d+) source=(\w+) digest=(\S+) "
            r"disk_reads=(\d+)", log_of("127.0.0.3:0"))
        assert m, log_of("127.0.0.3:0")[-3000:]
        epoch, source, digest, disk_reads = (
            int(m.group(1)), m.group(2), m.group(3), int(m.group(4)))
        # Zero disk reads, peer source.
        assert source == "peer", (source, log_of("127.0.0.3:0")[-2000:])
        assert disk_reads == 0
        # ...and bitwise-identical to the survivors' epoch: SOME rank
        # committed exactly this (epoch, digest) pair.
        commits = _re.findall(r"committed epoch=(\d+) digest=(\S+)",
                              log_of("127.0.0.1:0")
                              + log_of("127.0.0.2:0"))
        assert (str(epoch), digest) in commits, (
            epoch, digest, commits[-5:])

        # No dead-peer verdicts anywhere on this path.
        drained_log = log_of("127.0.0.2:0")
        assert "HVD303" not in drained_log, drained_log[-2000:]
        assert "PeerFailureError" not in drained_log, drained_log[-2000:]

        (sdir / "done").write_text("1")
        t.join(timeout=90)
        assert not t.is_alive(), "driver never finished"
        assert rc.get("code") == 0, (rc, d.events)
        assert d.registry.blacklist() == set(), d.registry.blacklist()
    finally:
        (sdir / "done").write_text("1")
        _time.sleep(0.5)
        d._shutdown_workers()


def test_many_host_churn_scenario_with_lite_workers(tmp_path):
    """ISSUE 14 satellite (carried from PR 12): the DRIVER-level churn
    scenario at 64 simulated hosts, using the lightweight jax-free
    worker — world forms, a batch of hosts is preempt-drained (clean
    LEFT, never blacklisted, commit pings acked at scale), replacements
    join, and the run ends clean.  What previously capped at 2–3 hosts
    end-to-end now runs at 64+."""
    import threading as _threading
    import time as _time

    from horovod_tpu.elastic.discovery import HostDiscoveryScript
    from horovod_tpu.elastic.driver import ElasticDriver

    n_hosts = 64
    drained_n = 8
    sdir = tmp_path / "scenario"
    sdir.mkdir()
    hosts = tmp_path / "hosts"
    all_hosts = [f"127.0.1.{i}" for i in range(1, n_hosts + 1)]
    hosts.write_text("".join(f"{h}:1\n" for h in all_hosts))
    notices = tmp_path / "notices"

    class _NoticeScript(HostDiscoveryScript):
        def preemption_notices(self):
            try:
                return {ln.strip() for ln in notices.read_text().split()
                        if ln.strip()}
            except OSError:
                return set()

    env = {k: v for k, v in os.environ.items()}
    other_paths = [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                   if p]
    extra_env = {
        "PYTHONPATH": os.pathsep.join([REPO] + other_paths),
        "SCENARIO_DIR": str(sdir),
    }
    d = ElasticDriver(
        _NoticeScript(f"cat {hosts}"),
        [sys.executable, WORKER_LITE],
        min_np=8, max_np=n_hosts + drained_n, env=extra_env,
        discovery_interval_s=0.5, start_timeout_s=240, verbose=0,
        preempt_grace_s=60.0)

    rc = {}
    t = _threading.Thread(target=lambda: rc.update(code=d.run()),
                          daemon=True)
    t.start()

    def wait_for(cond, what, timeout=240):
        deadline = _time.monotonic() + timeout
        while _time.monotonic() < deadline:
            if cond():
                return
            if rc:
                raise AssertionError(
                    f"driver exited rc={rc} while waiting for {what}")
            _time.sleep(0.25)
        raise AssertionError(
            f"timed out waiting for {what}; procs={len(d._procs)} "
            f"assigned={len(d._assigned)} events={d.events[-5:]}")

    try:
        wait_for(lambda: len(d._procs) == n_hosts,
                 f"initial {n_hosts}-host world")
        # READINESS, not just spawn: the notification port registers a
        # few seconds after exec (64 simultaneous interpreter startups);
        # draining before that would take the termination fallback.
        wait_for(lambda: len(d.rendezvous.notification_ports())
                 >= n_hosts, "all notification ports registered")

        # Preempt-drain a batch of hosts: every one takes the paced
        # clean path (commit ping -> DRAIN -> exit 0 -> LEFT).
        doomed = all_hosts[-drained_n:]
        notices.write_text("".join(f"{h}\n" for h in doomed))
        wait_for(lambda: sum(1 for e in d.events
                             if e["action"] == "preempt_drain")
                 == drained_n, "preempt_drain events")
        wait_for(lambda: all(
            d.registry.state_of(f"{h}:0") == "LEFT" for h in doomed)
            and len(d._assigned) == n_hosts - drained_n,
            "world healed without the drained batch")
        assert d.registry.blacklist() == set(), d.registry.blacklist()
        # Commit acks recorded at scale: the fan-out reached (and was
        # acked by) a large share of the live fleet.
        ack_ev = next(e for e in d.events
                      if e["action"] == "commit_request")
        assert len(ack_ev["acked"]) >= (n_hosts - drained_n) // 2, (
            len(ack_ev["acked"]))

        # Replacements join: the world grows back.
        extra = [f"127.0.2.{i}" for i in range(1, drained_n + 1)]
        hosts.write_text("".join(
            f"{h}:1\n" for h in all_hosts[:-drained_n] + extra))
        wait_for(lambda: len(d._assigned) == n_hosts, "world re-grown")

        (sdir / "done").write_text("1")
        t.join(timeout=120)
        assert not t.is_alive(), "driver never finished"
        assert rc.get("code") == 0, rc
    finally:
        (sdir / "done").write_text("1")
        _time.sleep(0.5)
        d._shutdown_workers()
