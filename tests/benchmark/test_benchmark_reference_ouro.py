"""The plain float32 Ouro reference against ``horovod_tpu/models`` at tiny
sizes on the CPU, the reference a piece at a time against the reference in
one traced function, the counts the family makes from the shapes, and the
control: a run below the configuration's precision has to fail the
comparison that a sound run passes."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import compare                                   # noqa: E402
from benchmark.families import ouro as family                   # noqa: E402
from benchmark.reference import ouro as ref                     # noqa: E402
from benchmark.reference.resnet import scalars                  # noqa: E402
from horovod_tpu.models import ouro                             # noqa: E402

with open(os.path.join(ROOT, "benchmark", "configs",
                       "ouro-2_6b-16l.json")) as fh:
    CONFIG = json.load(fh)
with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
    BENCH = json.load(fh)
PUBLISHED = {k: v for k, v in CONFIG.items()
             if not isinstance(v, (dict, list))}
TINY = dict(PUBLISHED, **CONFIG["tiny"], batch_per_chip=2, seq_len=70)
# float32 against float32 at these sizes differs by reassociation only.
SOUND = {"loss_rel": 1e-5, "grad_norm_gap": 2e-4, "delta_norm_gap": 2e-3}
KEY = jax.random.PRNGKey(5)


def as_record(followed, rank=0):
    return {"rank": rank, "first_losses": followed["losses"][rank],
            "grad_norms": followed["grad_norms"],
            "delta_norms": followed["delta_norms"], "digest": "",
            "last_loss": 1.0, "params_changed": True}


def test_the_reference_imports_nothing_of_the_program():
    with open(ref.__file__) as fh:
        assert "horovod_tpu" not in fh.read().replace(
            "imported from ``horovod_tpu``", "")


def test_the_configuration_keeps_every_published_number():
    """The catalog row's ``config`` (model-configs guide), key for key but
    for the depth; ``reduced`` names the depth and nothing else."""
    published = {
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5632, "max_position_embeddings": 65536,
        "max_window_layers": 48, "model_type": "ouro",
        "num_attention_heads": 16, "num_key_value_heads": 16,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "total_ut_steps": 4, "early_exit_threshold": 1,
        "use_sliding_window": False, "vocab_size": 49152}
    assert {k: CONFIG[k] for k in published} == published
    assert CONFIG["layer_types"] == ["full_attention"] * 48
    assert CONFIG["reduced"] == ["num_hidden_layers"]
    assert (CONFIG["num_hidden_layers"],
            CONFIG["num_hidden_layers_published"]) == (16, 48)
    entry = next(c for c in BENCH["configs"] if c["name"] == "ouro-2_6b-16l")
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["source"] == CONFIG["source"]
    assert set(CONFIG["assumed"]) >= {
        "four_norms", "final_norm_in_loop", "gate", "entropy_beta",
        "early_exit_threshold", "loss"}
    for key in ("deployment", "memory_analysis"):
        assert CONFIG[key]
    assert len(CONFIG["limits"]["why"]) > 200
    cfg = family.config_of(PUBLISHED)
    assert (cfg.n_layers, cfg.total_ut_steps, cfg.d_model, cfg.n_heads,
            cfg.head_dim, cfg.d_ff, cfg.vocab_size) == (
                16, 4, 2048, 16, 128, 5632, 49152)
    assert cfg.rope_theta == 1e6 and cfg.entropy_beta == 0.05


def test_the_drawn_gate_leaves_every_pass_a_share():
    """The seeded weights spread ``lam`` round a half: no pass's mean exit
    probability is near 0, so the mixture of the four losses is in play
    (set-up refuses a batch under 0.05)."""
    params = ref.init_weights(KEY, TINY)
    toks, tgts = ref.make_batch(KEY, TINY, 0)
    cfg = family.config_of(dict(TINY, use_flash=False))
    counted = family.counters(ouro.exit_stats(params, toks, tgts, cfg), TINY)
    assert counted["layer_applications_per_step"] == 2 * 4
    assert len(counted["exit_p_mean"]) == len(counted["nll_mean"]) == 4
    assert abs(sum(counted["exit_p_mean"]) - 1.0) < 1e-5
    assert counted["least_exit_p_mean"] > 2 * family.LEAST_MEAN_EXIT
    assert 0.3 < counted["exit_p_mean"][0] < 0.7
    assert 0.5 < counted["exit_entropy_mean"] < np.log(4)
    for leaf in ("attn_norm", "attn_out_norm", "mlp_norm", "mlp_out_norm"):
        w = np.asarray(params["layers"][leaf])
        assert 0.5 <= w.min() < 0.6 and 1.4 < w.max() <= 1.5


def test_a_missing_norm_is_far_off():
    """The program handed weights of one for the sublayers' OUTPUT norms
    (the same as leaving those weights out) is caught by the loss."""
    params = ref.init_weights(KEY, TINY)
    toks, tgts = ref.make_batch(KEY, TINY, 0)
    cfg = family.config_of(dict(TINY, use_flash=False))
    ones = dict(params, layers=dict(
        params["layers"],
        attn_out_norm=jnp.ones_like(params["layers"]["attn_out_norm"]),
        mlp_out_norm=jnp.ones_like(params["layers"]["mlp_out_norm"])))
    loss = jax.jit(lambda p: ouro.loss_fn(p, toks, tgts, cfg))
    sound, plain = float(loss(params)), float(loss(ones))
    want = float(jax.jit(lambda p: ref.loss_fn(p, toks, tgts, TINY))(params))
    assert abs(sound - want) <= 1e-5 * want
    assert abs(plain - want) > 0.002 * want


def test_the_pieces_give_what_the_one_traced_function_gives():
    """``add_gradient`` (one jitted call a layer application, the backward
    pass by hand from the last pass, four additions into a shared weight's
    total) against ``jax.value_and_grad`` of ``loss_fn``, and on top of a
    total that is not zero."""
    params = ref.init_weights(KEY, TINY)
    toks, tgts = ref.make_batch(KEY, TINY, 0)
    pieces = ref._pieces(scalars(TINY), "float32")
    with jax.default_matmul_precision("highest"):
        want, grads = jax.value_and_grad(ref.loss_fn)(
            params, toks, tgts, TINY)
        got, total = ref.add_gradient(
            pieces, jax.tree_util.tree_map(jnp.ones_like, params), params,
            toks, tgts, TINY)
    assert abs(got - float(want)) <= 1e-6 * float(want)
    for (path, g), t in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree_util.tree_leaves(total)):
        assert float(jnp.max(jnp.abs(t - 1.0 - g))) <= 5e-4 * float(
            jnp.max(jnp.abs(g))) + 2e-7, jax.tree_util.keystr(path)


def test_the_running_total_is_float32_whatever_the_weights_type():
    """``follow`` sums the four contributions to a shared weight, and the
    sequences' on top of them, unrounded: with bfloat16 weights its first
    gradient is ``add_gradient`` into a float32 total, sequence by sequence
    (the program sums in bfloat16, and the gap says what that costs)."""
    from benchmark.reference.common import leaf_norms
    sizes = dict(TINY, dtype="bfloat16")
    params = ref.init_weights(KEY, sizes)
    toks, tgts = ref.make_batch(KEY, sizes, 0)
    pieces = ref._pieces(scalars(sizes), "float32")
    with jax.default_matmul_precision("highest"):
        total = jax.tree_util.tree_map(
            lambda w: jnp.zeros(w.shape, jnp.float32), params)
        for b in range(toks.shape[0]):
            _, total = ref.add_gradient(pieces, total, params, toks[b:b + 1],
                                        tgts[b:b + 1], sizes)
        assert {t.dtype for t in jax.tree_util.tree_leaves(total)} == {
            jnp.dtype("float32")}
        want = leaf_norms(jax.tree_util.tree_map(
            lambda t: t / toks.shape[0], total))
        got = ref.follow(sizes, KEY, 1, 1)["grad_norms"]
    assert set(got) == set(want)
    for leaf, norm in want.items():
        assert abs(got[leaf] - norm) <= 1e-6 * norm, leaf


# ------------------------------------------------- counts from the shapes
def test_the_stage_holds_1_023_545_345_parameters():
    shapes = jax.eval_shape(lambda k: ref.init_weights(k, PUBLISHED), KEY)
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert n == 16 * 51_388_416 + 2 * 100_663_296 + 2048 + 2049 \
        == 1_023_545_345
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert family.matmul_params(PUBLISHED) == (
        16 * layer + 2048 * 49152 + 2048) == 922_748_928
    assert family.layer_applications(PUBLISHED) == 64


def test_flops_and_bytes_from_the_shapes():
    sizes = dict(PUBLISHED, seq_len=8192, batch_per_chip=1)
    assert family.attention_flops(sizes) == (
        12.0 * (8192 * 8193 // 2) * 128 * 16 * 64)
    flops = family.model_flops_per_item(sizes)
    # 6 a matmul parameter in EVERY pass, the head four times
    assert flops == pytest.approx(
        6.0 * 4 * 922_748_928 + family.attention_flops(sizes) / 8192)
    assert flops == pytest.approx(28.59e9, rel=1e-3)    # the issue's 28.6
    assert flops * 8192 == pytest.approx(234.2e12, rel=1e-3)
    # q, k, v, o forward; q, k, v, o, do, dq, dk, dv backward: 12 x [T, d]
    # in bfloat16, a layer application
    assert family.attention_bytes(sizes) == 2 * 12 * 8192 * 2048 * 64


def test_grouped_attention_and_a_window_are_refused():
    with pytest.raises(SystemExit, match="grouped"):
        family.config_of(dict(TINY, num_key_value_heads=2))
    with pytest.raises(SystemExit, match="window"):
        family.config_of(dict(TINY, sliding_window=64))


# -------------------------------------------------------------- the control
def test_bfloat16_fails_a_float32_ouro():
    """The control at test size: the reference put in the program's place
    and computed in bfloat16 under a float32 configuration comes out as
    not correct, on three seeds, by the gradient norms; the reference
    itself passes."""
    for seed in (1, 2, 3):
        key = jax.random.PRNGKey(seed)
        reference = ref.follow(TINY, key, 1, 3)
        assert compare.decide([as_record(reference)], reference, SOUND)[0]
        low = ref.follow(TINY, key, 1, 3, "bfloat16")
        correct, rows = compare.decide([as_record(low)], reference, SOUND)
        assert not correct
        failed = [name for name, _, _, ok in rows if not ok]
        assert any(n.startswith("grad_norm_gap") for n in failed), rows


def test_the_program_passes_where_the_control_fails():
    """The model in float32 against the float32 reference of a float32
    configuration passes the limits the bfloat16 control fails."""
    from benchmark.reference.common import leaf_norms
    reference = ref.follow(TINY, KEY, 1, 1)
    params = ref.init_weights(KEY, TINY)
    toks, tgts = ref.make_batch(KEY, TINY, 0)
    cfg = family.config_of(dict(TINY, use_flash=False))
    with jax.default_matmul_precision("highest"):
        grads = leaf_norms(jax.jit(jax.grad(
            lambda p: ouro.loss_fn(p, toks, tgts, cfg)))(params))
    sound, _ = compare.norm_gap(grads, reference["grad_norms"])
    gaps = [compare.norm_gap(
        ref.follow(TINY, KEY, 1, 1, p)["grad_norms"],
        reference["grad_norms"])[0] for p in ("bfloat16", "float8")]
    assert sound <= SOUND["grad_norm_gap"] < min(gaps)
    assert min(gaps) >= 3 * sound


def test_two_ranks_average_their_gradients():
    """``follow`` at a world of two: a loss a rank, and the first gradient
    is the mean of the two ranks' own."""
    from benchmark.reference.common import leaf_norms
    both = ref.follow(TINY, KEY, 2, 1)
    assert len(both["losses"]) == 2 and both["losses"][0] != both["losses"][1]
    params = ref.init_weights(KEY, TINY)
    with jax.default_matmul_precision("highest"):
        grads = [jax.grad(ref.loss_fn)(params, *ref.make_batch(KEY, TINY, r),
                                       TINY) for r in (0, 1)]
    mean = leaf_norms(jax.tree_util.tree_map(lambda a, b: (a + b) / 2,
                                             *grads))
    assert compare.norm_gap(both["grad_norms"], mean)[0] <= 2e-4
