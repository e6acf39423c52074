"""The plain float32 Olmo-Hybrid reference against ``horovod_tpu/models`` at
tiny sizes on the CPU, the counts the family makes from the shapes, and the
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
from benchmark.families import olmo_hybrid as family            # noqa: E402
from benchmark.reference import olmo_hybrid as ref              # noqa: E402
from horovod_tpu.models import olmo_hybrid                      # noqa: E402

with open(os.path.join(ROOT, "benchmark", "configs",
                       "olmo-hybrid-7b-4l.json")) as fh:
    CONFIG = json.load(fh)
PUBLISHED = {k: v for k, v in CONFIG.items()
             if not isinstance(v, (dict, list))}
TINY = dict(PUBLISHED, **CONFIG["tiny"], batch_per_chip=2, seq_len=200)
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
    for the two that are reduced; the pattern the program runs is the
    published ``layer_types``' first period."""
    published = {
        "model_type": "olmo_hybrid", "hidden_size": 3840,
        "intermediate_size": 11008, "num_attention_heads": 30,
        "num_key_value_heads": 30, "hidden_act": "silu",
        "max_position_embeddings": 65536, "attention_bias": False,
        "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
        "linear_num_key_heads": 30, "linear_num_value_heads": 30,
        "linear_key_head_dim": 96, "linear_value_head_dim": 192,
        "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
        "rope_parameters": {"rope_theta": None}}
    assert {k: CONFIG[k] for k in published} == published
    assert CONFIG["layer_types"] == (["linear_attention"] * 3
                                     + ["full_attention"]) * 8
    assert CONFIG["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert (CONFIG["num_hidden_layers"], CONFIG["vocab_size"],
            CONFIG["vocab_size_published"]) == (4, 12544, 100352)
    assert tuple(CONFIG["layer_types"][:4]) == family.config_of(
        PUBLISHED).layer_types
    assert set(CONFIG["assumed"]) >= {"norm_placement", "qk_norm",
                                      "no_rotary", "gated_delta_net"}


def test_the_drawn_weights_put_half_of_the_betas_past_one():
    """Decays between 0.9 and 0.999 for every head, norm weights away from
    one, an embedding of unit size, and ``b = x W_ba`` wide enough that
    the negative eigenvalues are in play in every layer."""
    sizes = dict(TINY, linear_num_key_heads=32, linear_num_value_heads=32,
                 hidden_size=128, num_attention_heads=4)
    params = ref.init_weights(KEY, sizes)
    for layer in params["layers"][:3]:
        p = layer["gdn"]
        decay = np.exp(-np.exp(p["A_log"]) * np.log1p(np.exp(p["dt_bias"])))
        assert decay.shape == (32,)
        assert (decay > 0.89).all() and (decay < 0.9995).all()
        assert decay.max() > 0.99 and decay.min() < 0.96
        assert float(jnp.mean(jnp.abs(layer["mixer_norm"] - 1.0))) > 0.2
    assert 0.9 < float(jnp.std(params["embed"])) < 1.1
    toks, _ = ref.make_batch(KEY, sizes, 0)
    share, largest = olmo_hybrid.beta_stats(params, toks,
                                            family.config_of(sizes))
    counted = family.counters(share, largest, dict(sizes, seq_len=200))
    assert 0.4 < counted["least_share_over_one"] < 0.6
    assert min(counted["beta_largest"]) > 1.9
    assert counted["chunks_per_sequence"] == 4


def test_a_missing_norm_is_far_off():
    """The program handed norm weights of one (the same as leaving the
    weights out) is caught by the loss."""
    params = ref.init_weights(KEY, TINY)
    toks, tgts = ref.make_batch(KEY, TINY, 0)
    cfg = family.config_of(dict(TINY, use_flash=False))
    ones = jax.tree_util.tree_map_with_path(
        lambda path, w: jnp.ones_like(w) if jax.tree_util.keystr(
            path).endswith(("['mixer_norm']", "['mlp_norm']",
                            "['final_norm']")) else w, params)
    loss = jax.jit(lambda p: olmo_hybrid.loss_fn(p, toks, tgts, cfg))
    sound, plain = float(loss(params)), float(loss(ones))
    want = float(jax.jit(lambda p: ref.loss_fn(p, toks, tgts, TINY))(params))
    assert abs(sound - want) <= 1e-5 * want
    assert abs(plain - want) > 0.01 * want


# ------------------------------------------------- counts from the shapes
def test_the_stage_holds_928_862_196_parameters():
    shapes = jax.eval_shape(lambda k: ref.init_weights(k, PUBLISHED), KEY)
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert n == 928_862_196
    gdn = 3840 * (2 * 2880 + 2 * 5760 + 60) + 5760 * 3840
    mlp = 3 * 3840 * 11008
    assert family.matmul_params(PUBLISHED) == (
        3 * gdn + 4 * 3840 * 3840 + 4 * mlp + 3840 * 12544) == 880_512_000
    assert family.layer_kinds(PUBLISHED) == (3, 1)
    assert family.head_dim(PUBLISHED) == 128


def test_flops_and_bytes_from_the_shapes():
    sizes = dict(PUBLISHED, seq_len=16384, batch_per_chip=1)
    chunk = 64 * 64 * (5 * 96 + 3 * 192) + 6 * 64 * 96 * 192
    assert family.chunks_per_sequence(sizes) == 256
    assert family.gdn_scan_flops(sizes) == 3.0 * chunk * 256 * 30 * 3
    assert family.attention_flops(sizes) == (
        12.0 * (16384 * 16385 // 2) * 128 * 30)
    flops = family.model_flops_per_item(sizes)
    assert flops == pytest.approx(
        6.0 * 880_512_000 + (family.attention_flops(sizes)
                             + family.gdn_scan_flops(sizes)) / 16384)
    assert flops == pytest.approx(5.709e9, rel=1e-3)    # the issue's 5.71
    # q, k at 30 heads of 96 and v, o at 30 of 192 in bfloat16, g and beta
    # float32
    token = 3 * (2 * 30 * 96 * 2 + 30 * 192 * 2 + 2 * 30 * 4) + 2 * (
        30 * 192 * 2)
    assert family.gdn_scan_bytes(sizes) == token * 16384 * 3
    # q, k, v, o forward; q, k, v, o, do, dq, dk, dv backward: 12 x [T, d]
    assert family.attention_bytes(sizes) == 2 * 12 * 16384 * 3840


def test_grouped_attention_is_refused():
    with pytest.raises(SystemExit, match="grouped"):
        family.config_of(dict(TINY, num_key_value_heads=2))


# -------------------------------------------------------------- the control
def test_bfloat16_fails_a_float32_olmo_hybrid():
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
            lambda p: olmo_hybrid.loss_fn(p, toks, tgts, cfg)))(params))
    sound, _ = compare.norm_gap(grads, reference["grad_norms"])
    low, _ = compare.norm_gap(
        ref.follow(TINY, KEY, 1, 1, "bfloat16")["grad_norms"],
        reference["grad_norms"])
    assert sound <= SOUND["grad_norm_gap"] < low
    assert low >= 3 * sound


def test_both_controls_are_far_off_where_the_program_is_not():
    """bfloat16 and float8 operands each move the worst matrix's gradient
    norm by hundreds of times what reassociation does.  (Which of the two
    is further off depends on the leaf here: a recurrence that keeps its
    state over hundreds of tokens with beta up to 2 is moved several per
    cent by either.)"""
    reference = ref.follow(TINY, KEY, 1, 1)
    gaps = [compare.norm_gap(
        ref.follow(TINY, KEY, 1, 1, p)["grad_norms"],
        reference["grad_norms"])[0] for p in ("bfloat16", "float8")]
    assert min(gaps) > 100 * SOUND["grad_norm_gap"]
