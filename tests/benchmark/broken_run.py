"""Drives a rehearsal run with the timed path broken underneath (a child
process of ``test_benchmark_broken.py``): the family's ``build`` is wrapped
so that the step misbehaves, everything else is ``run.py`` as it stands."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run                       # noqa: E402
from benchmark.families import resnet           # noqa: E402


def broken(build, fault):
    def wrapped(hvd, cell, key, annotate):
        built = build(hvd, cell, key, annotate)
        step = built["step"]
        if fault == "state_unchanged":
            # the step computes its loss and throws its update away
            def bad(state, batch):
                import jax
                keep = jax.tree_util.tree_map(lambda x: x + 0, state)
                _, loss = step(state, batch)
                return keep, loss
        elif fault == "half_batch":
            # the second half of the batch never reaches the step
            import jax.numpy as jnp
            images, labels = built["batch"]
            half = images.shape[0] // 2
            built["batch"] = (
                jnp.concatenate([images[:half], images[:half]]),
                jnp.concatenate([labels[:half], labels[:half]]))
            bad = step
        elif fault == "loss_altered":
            def bad(state, batch):
                state, loss = step(state, batch)
                return state, loss * 1.5
        elif fault == "bn_scale_gradient":
            # the batch-norm scales' gradients never reach the optimizer:
            # the scales and their momentum stay where they were
            def bad(state, batch):
                import jax
                old = jax.tree_util.tree_map(lambda x: x + 0, tuple(state))
                new, loss = step(state, batch)      # donates its state
                keep = lambda path, old, new: (
                    old if jax.tree_util.keystr(path).endswith("['scale']")
                    else new)
                return jax.tree_util.tree_map_with_path(
                    keep, old, tuple(new)), loss
        else:
            raise SystemExit(f"unknown fault {fault}")
        built["step"] = bad
        return built
    return wrapped


if __name__ == "__main__":
    fault = sys.argv.pop(1)
    resnet.build = broken(resnet.build, fault)
    run.main()
