"""A ``laguna`` step that is broken underneath has to come out as not
correct: each fault with the number that catches it.  All eight in this
process at the files' tiny sizes — the broken program's first gradient
against the reference's, as ``compare.py`` holds it, beside the limit of the
configuration file — and two of them end to end as a child process, through
``run.py`` as it stands.  PERF.md section 6 (PR 47) has what the same eight
read at the cell's own sizes on the chip."""

import json
import os
import subprocess
import sys

import jax
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import broken_run_laguna as broken  # noqa: E402
import rehearsal  # noqa: E402

from benchmark import compare                                   # noqa: E402
from benchmark.families import laguna as family                 # noqa: E402
from benchmark.reference import laguna as ref                   # noqa: E402
from benchmark.reference.common import leaf_norms               # noqa: E402
from horovod_tpu.models import laguna                           # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "laguna_s2_1-5l-spmd-1c"
with open(os.path.join(rehearsal.ROOT, "benchmark", "configs",
                       "laguna-s-2_1-5l.json")) as fh:
    CONFIG = json.load(fh)
TINY = {**{k: v for k, v in CONFIG.items()
           if not isinstance(v, (dict, list))}, **CONFIG["tiny"],
        "batch_per_chip": 1, "seq_len": 200, "use_flash": False}
KEY = jax.random.PRNGKey(9)
# what the fault does, and why the first gradient shows it
FAULTS = {
    # a sliding layer attends everything: at 200 tokens under a window of
    # 64 two thirds of a query's keys are ones it should not see
    "window_left_off": "grad_norm_gap",
    # every gate one where the draw has gates about a half: Wg takes no
    # gradient at all, and Wo's doubles
    "gate_left_out": "grad_norm_gap",
    # a full layer turned over all 16 numbers of a head, not its first 8
    "whole_head_turned": "grad_norm_gap",
    # YaRN's cos and sin unscaled: the full layers' scores lose a factor
    # of (0.1 ln 4 + 1)^2 = 1.3
    "attention_factor_left_out": "grad_norm_gap",
    # the routed experts weigh 1 in all where the model says 2.5
    "routed_scale_left_out": "grad_norm_gap",
    # the chosen three weigh by their scores (about a half each) as they
    # are, not over their sum
    "not_renormalised": "grad_norm_gap",
    # layer 0 runs an expert layer: its SwiGLU takes no gradient at all
    "layer_0_given_an_expert_layer": "grad_norm_gap",
    # the loss over the first half of each sequence
    "half_the_batch": "grad_norm_gap",
}


def test_every_fault_of_the_driver_is_held_here():
    assert set(FAULTS) == set(broken.FAULTS)


@pytest.fixture(scope="module")
def first_gradient():
    """``gap(fault)``: the worst matrix's gap of the program's first
    gradient, broken by ``fault`` (or sound), against the reference's."""
    want = ref.follow(TINY, KEY, 1, 1)["grad_norms"]
    params = ref.init_weights(KEY, TINY)
    toks, tgts = ref.make_batch(KEY, TINY, 0)
    cfg = family.config_of(TINY)

    def gap(fault=None):
        module, name, change = broken.FAULTS.get(fault, (laguna, "loss_fn",
                                                         lambda f: f))
        sound = getattr(module, name)
        jax.clear_caches()      # a checkpointed region traced before is kept
        setattr(module, name, change(sound))
        try:
            with jax.default_matmul_precision("highest"):
                grads = jax.jit(jax.grad(lambda p: laguna.loss_fn(
                    p, toks, tgts, cfg)))(params)
        finally:
            setattr(module, name, sound)
            jax.clear_caches()
        return compare.norm_gap(leaf_norms(grads), want)[0]

    return gap


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_steps_first_gradient_is_outside_the_limit(first_gradient,
                                                            fault):
    limit = CONFIG["limits"][FAULTS[fault]]
    assert first_gradient(fault) > 10 * limit


def test_the_sound_steps_first_gradient_is_inside_it(first_gradient):
    assert first_gradient() < 0.1 * CONFIG["limits"]["grad_norm_gap"]


@pytest.mark.parametrize("fault", ["window_left_off", "half_the_batch"])
def test_a_broken_run_is_not_correct(fault):
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "broken_run_laguna.py"),
         fault, "--workload", CELL, "--seed", "9", "--seconds", "1",
         "--trace", "0", "--rehearse"],
        cwd=rehearsal.ROOT, env=rehearsal.child_env(), timeout=300,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is False
    failed = [r.split()[1] for r in proc.stderr.splitlines()
              if r.startswith("compare") and r.endswith("FAILED")]
    assert any(name.startswith(FAULTS[fault]) for name in failed), failed


def test_an_unknown_fault_is_refused():
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "broken_run_laguna.py"),
         "no_such_fault", "--workload", CELL],
        cwd=rehearsal.ROOT, env=rehearsal.child_env(), timeout=120,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    assert proc.returncode != 0 and "unknown fault" in proc.stderr
