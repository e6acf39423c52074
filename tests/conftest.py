"""Test harness: hermetic 8-virtual-device CPU mesh.

Mirrors the reference's hermetic test tier (SURVEY.md §4): where the
reference uses multi-process Gloo on localhost as the no-cluster backend, we
use JAX's virtual CPU devices (``--xla_force_host_platform_device_count=8``)
so the full enqueue → negotiate → fuse → XLA-collective path runs with 8
ranks in one process.  Must be set before jax imports anywhere.
"""

import os
import sys

# Overwrite, not setdefault: a TPU machine's environment names its own
# platforms, and tests must run hermetically on virtual CPU devices
# regardless.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()



def _share_the_cores():
    """Under pytest-xdist, pin this worker (and the child processes its
    tests start, which inherit it) to half of the machine's cores, the
    halves staggered over the workers.  XLA's CPU backend sizes its thread
    pool from the cores it may run on, so six workers and their
    ``benchmark/run.py`` children otherwise put eight spinning threads each
    on eight cores, and a rehearsed cell's 3 s window can pass without one
    step completing (``tests/benchmark/test_benchmark_rehearse_*``: under
    six such neighbours a step of 84 ms took 540 ms, with four cores each
    230).  One process alone (no xdist) keeps every core."""
    worker = os.environ.get("PYTEST_XDIST_WORKER", "")
    count = int(os.environ.get("PYTEST_XDIST_WORKER_COUNT", "0") or 0)
    if not (worker.startswith("gw") and worker[2:].isdigit() and count > 2
            and hasattr(os, "sched_setaffinity")):
        return
    cores = sorted(os.sched_getaffinity(0))
    if len(cores) < 4:
        return
    start = int(worker[2:]) * len(cores) // count
    try:
        os.sched_setaffinity(0, {cores[(start + j) % len(cores)]
                                 for j in range(len(cores) // 2)})
    except OSError:  # pragma: no cover - a sandbox that forbids it
        pass


_share_the_cores()

if "jax" in sys.modules:  # pragma: no cover - belt and braces
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 8)

import pytest  # noqa: E402


@pytest.fixture()
def hvd():
    import horovod_tpu as hvd
    hvd.init()
    yield hvd
    # Engines/timelines are cheap; keep runtime initialized across tests for
    # speed (matching how real training uses one init per process).


@pytest.fixture()
def one_rank(hvd):
    """A one-rank process set of the 8-virtual-device mesh."""
    ps = hvd.add_process_set([0])
    yield ps
    hvd.remove_process_set(ps)


@pytest.fixture()
def per_process(hvd, one_rank, monkeypatch):
    """This process as one rank of ``torovodrun -np 1``: the per-process
    branch over ``one_rank``, no controller (the cycle runs inline)."""
    from horovod_tpu.common import basics
    from horovod_tpu.ops import eager
    monkeypatch.setattr(basics._get_state().config, "controller_addr",
                        "stub:0")
    assert eager.per_process_mode()
    return one_rank


@pytest.fixture(scope="session")
def world_size():
    import jax
    return jax.device_count()


@pytest.fixture()
def sim_slices():
    """The N-slice in-process harness (tests/slice_harness.py): a context
    manager arming an engine's two-level mode over a simulated N×L split
    of the 8-device CPU mesh, restoring every knob on exit."""
    from slice_harness import simulated_slices
    return simulated_slices
