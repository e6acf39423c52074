"""One import site for the JAX names the package leans on.

The package runs on one installation (jax 0.9.0).  Every module imports
``shard_map`` and ``axis_size`` from here, and the analyzer keys on these
names (``horovod_tpu.compat.shard_map``), so they stay; there is no branch
for any other JAX release.

Usage::

    from horovod_tpu.compat import shard_map     # instead of `from jax import shard_map`
"""

from __future__ import annotations

from jax import shard_map  # noqa: F401 - re-exported
from jax.lax import axis_size  # noqa: F401 - re-exported


def set_host_device_count(n: int):
    """Declare ``n`` virtual CPU devices, BEFORE backend init.

    It must run before the CPU backend initializes (first ``jax.devices()``
    etc.); an already-initialized backend keeps its device count and this
    call has no effect on it.
    """
    import os

    import jax
    # Strip any stale count flag: an inherited
    # --xla_force_host_platform_device_count (e.g. a parent harness that
    # stacked its own flags into XLA_FLAGS before spawning us) would
    # override the config option at backend init and silently pin the OLD
    # count.  Stripping makes stacked callers compose — last caller before
    # backend init wins.
    os.environ["XLA_FLAGS"] = " ".join(
        f for f in os.environ.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f)
    jax.config.update("jax_num_cpu_devices", int(n))


def tpu_compiler_params(**kwargs):
    """Pallas-TPU compiler params (``pltpu.CompilerParams``)."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(**kwargs)


def abstract_mesh(axis_sizes, axis_names):
    """``jax.sharding.AbstractMesh`` from parallel size/name sequences."""
    from jax.sharding import AbstractMesh
    return AbstractMesh(tuple(axis_sizes), tuple(axis_names))


def jax_export():
    """The ``jax.export`` module."""
    import jax.export as _export
    return _export
