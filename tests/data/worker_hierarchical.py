"""Two-slice topology worker: 2 processes × 4 local devices each.

Emulates a cross-slice TPU deployment on CPU (SURVEY.md §5 "DCN
collectives between slices"): the intra-process device group stands in
for one slice's ICI domain, the gloo TCP hop between the two processes
for DCN.  With ``HOROVOD_HIERARCHICAL_ALLREDUCE=1`` the engine runs
RS(local) → AR(cross) → AG(local) — the reduce-scatter and all-gather
stay inside each "slice", only the reduced shards cross the process
boundary — end-to-end through negotiate → fuse → execute.

Launched by test_multiprocess.py::test_hierarchical_two_slices with
``torovodrun -np 2 --hierarchical-allreduce``.
"""

import os
import sys

# 4 virtual CPU devices per process — the "slice" — via the shared
# harness (tests/slice_harness.py): strips the inherited 8-device flag,
# declares the local count (``jax_num_cpu_devices``), pins CPU + gloo.
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from slice_harness import configure_slice_world

jax = configure_slice_world(4)

import numpy as np
import horovod_tpu as hvd


def main():
    hvd.init()
    size, local = hvd.size(), hvd.local_size()
    proc = jax.process_index()
    assert jax.process_count() == 2, jax.process_count()
    assert size == 8, f"expected 8 global device ranks, got {size}"
    assert local == 4, f"expected 4 local devices per slice, got {local}"

    from horovod_tpu.common import basics
    eng = basics._get_state().engine
    assert eng.hierarchical_allreduce, \
        "HOROVOD_HIERARCHICAL_ALLREDUCE did not reach the engine"

    # Rank-dependent contributions: this process speaks for 4 global
    # ranks [4*proc, 4*proc+4); the hierarchical allreduce must land on
    # the same global sum a flat one would.
    my_ranks = range(4 * proc, 4 * proc + 4)
    x = np.stack([np.arange(8, dtype=np.float32) + 10.0 * r
                  for r in my_ranks])
    out = hvd.to_local(hvd.allreduce(x, name="hier_ar", op=hvd.Sum))
    expected = sum(np.arange(8, dtype=np.float32) + 10.0 * r
                   for r in range(8))
    np.testing.assert_allclose(np.asarray(out), expected, rtol=1e-6)

    # Fused batch through the same hierarchical path (two tensors, one
    # cycle) + average op.
    outs = hvd.grouped_allreduce(
        [np.stack([np.full((4,), float(r + 1), np.float32)
                   for r in my_ranks]),
         np.stack([np.full((2, 2), float(r), np.float32)
                   for r in my_ranks])],
        name="hier_grp", op=hvd.Average)
    np.testing.assert_allclose(
        np.asarray(hvd.to_local(outs[0])),
        np.full((4,), np.mean([r + 1.0 for r in range(8)])), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(hvd.to_local(outs[1])),
        np.full((2, 2), np.mean([float(r) for r in range(8)])), rtol=1e-6)

    hvd.barrier()
    print(f"WORKER_OK proc={proc} size={size} local={local}")
    hvd.shutdown()


if __name__ == "__main__":
    main()
