"""The seam the decoder families share (``horovod_tpu/models/blocks.py``):
each family's ``make_train_step`` is ``blocks.train_step`` of the family
module's own ``loss_fn``, **looked up when the step runs**.  The benchmark's
planted faults (``tests/benchmark/broken_run_*.py``) set a broken ``loss_fn``
on the module and expect the step to take it; a step that bound the name
when it was built, or at import, would turn such a run into a sound one.
Trace only (``jax.eval_shape`` at ``tiny()``): nothing is compiled."""

import importlib

import jax
import jax.numpy as jnp
import optax
import pytest

FAMILIES = ("qwen3_next", "olmo_hybrid", "nemotron_h", "ouro", "jamba")


@pytest.mark.parametrize("name", FAMILIES)
def test_the_step_calls_the_loss_fn_the_module_has_when_it_runs(monkeypatch,
                                                                name):
    module = importlib.import_module(f"horovod_tpu.models.{name}")
    cfg, optimizer = module.tiny(), optax.sgd(0.1)
    step = module.make_train_step(cfg, optimizer)
    # PR 38's compile ledger and the HLO module's name read it
    assert step.__name__ == "step"
    seen = []

    def planted(params, tokens, targets, cfg):
        seen.append((tokens.shape, targets.shape, cfg))
        return sum(jnp.sum(jnp.square(w.astype(jnp.float32)))
                   for w in jax.tree_util.tree_leaves(params))

    monkeypatch.setattr(module, "loss_fn", planted)     # after the step's made
    params = jax.eval_shape(lambda k: module.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    new, _, loss = jax.eval_shape(
        step, params, jax.eval_shape(optimizer.init, params), tokens, tokens)
    assert seen == [((2, 16), (2, 16), cfg)]
    assert loss.shape == () and jax.tree_util.tree_structure(
        new) == jax.tree_util.tree_structure(params)
