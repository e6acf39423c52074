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
