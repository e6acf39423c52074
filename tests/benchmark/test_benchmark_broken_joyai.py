"""A ``joyai`` step that is broken underneath has to come out as not
correct: each fault with the number that catches it.  All four in this
process at the files' tiny sizes — the broken program's three first steps
against the reference's, as ``compare.py`` holds them, beside the limits of
the configuration file — and one of them end to end as a child process,
through ``run.py`` as it stands.  PERF.md section 6 (PR 50) has what the
planted controls read at the cell's own sizes on the chip."""

import json
import os
import subprocess
import sys

import jax
import optax
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import broken_run_joyai as broken  # noqa: E402
import rehearsal  # noqa: E402

from benchmark import compare                                   # noqa: E402
from benchmark.families import joyai as family                  # noqa: E402
from benchmark.reference import joyai as ref                    # noqa: E402
from benchmark.reference.common import leaf_norms               # noqa: E402
from horovod_tpu.models import joyai                            # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "joyai_flash-5l-spmd-1c"
with open(os.path.join(rehearsal.ROOT, "benchmark", "configs",
                       "joyai-llm-flash-5l.json")) as fh:
    CONFIG = json.load(fh)
TINY = {**{k: v for k, v in CONFIG.items()
           if not isinstance(v, (dict, list))}, **CONFIG["tiny"],
        "batch_per_chip": 1, "seq_len": 96, "use_flash": False}
KEY = jax.random.PRNGKey(9)
# what the fault does, and the number of ``compare.py`` that shows it
FAULTS = {
    # the module's term dropped: the loss lacks 0.3 x ln(256), a fifth of
    # itself, and every leaf of the module takes no gradient at all
    "module_term_dropped": "loss_rel",
    # a third of a score's terms gone: W_qb's rope columns and W_kva's take
    # no gradient
    "rope_score_dropped": "grad_norm_gap",
    # the bias stays the seed's: its change's norm is 0 where the
    # reference's is three steps of 0.001 on most of 16 experts; no loss
    # and no first gradient shows it, the vectors' kinds do
    "bias_left_unchanged": "vector_delta_norm_gap",
    # the weights read the bias (made 50 times itself, so that a
    # float32 run shows it): the experts' gradients move
    "bias_weighs": "grad_norm_gap",
}


def test_every_fault_of_the_driver_is_held_here():
    assert set(FAULTS) == set(broken.FAULTS)


@pytest.fixture(scope="module")
def compared():
    """``rows(fault)``: ``compare.decide``'s rows for the program's three
    first steps, broken by ``fault`` (or sound), against the
    reference's, under the configuration file's limits."""
    reference = ref.follow(TINY, KEY, 1, 3)
    cfg = family.config_of(TINY)
    seeded = joyai.from_published(ref.init_weights(KEY, TINY), cfg)
    toks, tgts = ref.make_batch(KEY, TINY, 0)
    adam = ref.ADAM
    opt = optax.adam(adam["lr"], b1=adam["b1"], b2=adam["b2"],
                     eps=adam["eps"])

    def rows(fault=None):
        module, name, change = broken.FAULTS.get(
            fault, (joyai, "loss_fn", lambda f: f))
        sound = getattr(module, name)
        jax.clear_caches()      # a checkpointed region traced before is kept
        setattr(module, name, change(sound))
        try:
            step = jax.jit(joyai.make_train_step(cfg, opt))
            p, state, losses = seeded, opt.init(seeded), []
            with jax.default_matmul_precision("highest"):
                for i in range(3):
                    p, state, loss = step(p, state, toks, tgts)
                    losses.append(float(loss))
                    if i == 0:
                        grads = leaf_norms(jax.tree_util.tree_map(
                            lambda m: m / (1 - adam["b1"]), state[0].mu))
        finally:
            setattr(module, name, sound)
            jax.clear_caches()
        record = {"rank": 0, "first_losses": losses, "grad_norms": grads,
                  "delta_norms": leaf_norms(p, minus=seeded), "digest": "",
                  "last_loss": losses[-1], "params_changed": True}
        return compare.decide([record], reference, CONFIG["limits"])

    return rows


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_steps_numbers_are_outside_the_limits(compared, fault):
    correct, rows = compared(fault)
    assert not correct
    failed = {name: value for name, value, _, ok in rows if not ok}
    caught = [v for n, v in failed.items() if n.startswith(FAULTS[fault])]
    assert caught, failed
    assert max(caught) > 3 * CONFIG["limits"][FAULTS[fault]]
    if fault == "bias_left_unchanged":
        # the kind's change is nothing; the later steps' choices differ a
        # little for it, which a matrix's change may or may not show
        assert failed["vector_delta_norm_gap.router_bias"] == 1.0
        assert not any(n.startswith(("loss_rel", "grad_norm_gap"))
                       for n in failed)


def test_the_sound_steps_numbers_are_inside_them(compared):
    correct, rows = compared()
    assert correct, rows
    for name, value, limit, _ in rows:
        if isinstance(limit, float):
            assert value < 0.1 * limit, name
    # the vectors' change is held by its worst kind, the selection biases'
    # among them
    assert any(n.startswith("vector_delta_norm_gap") and isinstance(
        limit, float) for n, _, limit, _ in rows)


def test_a_broken_run_is_not_correct():
    """The one child of this file: the step that leaves the bias alone,
    through ``run.py``; ``vector_delta_norm_gap`` of the kind
    ``router_bias`` reads 1 and is what fails."""
    fault = "bias_left_unchanged"
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "broken_run_joyai.py"),
         fault, "--workload", CELL, "--seed", "9", "--seconds", "1",
         "--trace", "0", "--rehearse"],
        cwd=rehearsal.ROOT, env=rehearsal.child_env(), timeout=300,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is False
    failed = [r.split()[1] for r in proc.stderr.splitlines()
              if r.startswith("compare") and r.endswith("FAILED")]
    assert "vector_delta_norm_gap.router_bias" in failed, failed
    assert not any(n.startswith(("loss_rel", "grad_norm_gap"))
                   for n in failed), failed
    assert line["compared"]["vector_delta_norm_gap.router_bias"][
        "value"] == 1.0


def test_an_unknown_fault_is_refused():
    with pytest.raises(SystemExit, match="unknown fault"):
        broken.plant("no_such_fault")
