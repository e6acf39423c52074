"""The plain float32 Laguna reference and its configuration at tiny sizes
on the CPU: the configuration against the catalog row, the scalars the
file states a second time against its published lists and
``rope_parameters``, the share's parameter count, the counts the family
makes from the shapes, and the control: a run below the configuration's
precision has to fail the comparison that a sound run passes.
(``tests/test_laguna.py`` holds the program against this reference.)"""

import json
import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import compare                                   # noqa: E402
from benchmark.families import laguna as family                 # noqa: E402
from benchmark.reference import laguna as ref                   # noqa: E402
from horovod_tpu.models import laguna                           # noqa: E402

with open(os.path.join(ROOT, "benchmark", "configs",
                       "laguna-s-2_1-5l.json")) as fh:
    CONFIG = json.load(fh)
PUBLISHED = {k: v for k, v in CONFIG.items()
             if not isinstance(v, (dict, list))}
TINY = dict(PUBLISHED, **CONFIG["tiny"], batch_per_chip=1, seq_len=96)
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
    for the three that are reduced; the per-layer lists and
    ``rope_parameters`` whole; every width the published one."""
    published = {
        "model_type": "laguna", "hidden_size": 3072,
        "intermediate_size": 12288, "num_attention_heads": 48,
        "num_key_value_heads": 8, "head_dim": 128,
        "max_position_embeddings": 1048576, "attention_bias": False,
        "rms_norm_eps": 1e-06, "num_experts_per_tok": 10,
        "moe_intermediate_size": 1024,
        "shared_expert_intermediate_size": 1024, "norm_topk_prob": True,
        "decoder_sparse_step": 1, "mlp_only_layers": [0],
        "tie_word_embeddings": False, "gating": "per-head",
        "sliding_window": 512, "moe_apply_router_weight_on_input": False,
        "moe_routed_scaling_factor": 2.5,
        "moe_router_logit_softcapping": 0,
        "rope_parameters": {
            "full_attention": {
                "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
                "original_max_position_embeddings": 8192, "beta_slow": 1,
                "beta_fast": 32, "attention_factor": 1.4852030263919618,
                "partial_rotary_factor": 0.5},
            "sliding_attention": {
                "rope_type": "default", "rope_theta": 10000,
                "partial_rotary_factor": 1}},
        "layer_types": ["full_attention"] + ["sliding_attention"] * 3,
        "num_attention_heads_per_layer": [48, 72, 72, 72],
        "mlp_layer_types": ["dense"] + ["sparse"] * 47,
        "gating_types": ["per_head"] * 48}
    for key, value in published.items():
        if key in ("layer_types", "num_attention_heads_per_layer"):
            value = value * 12
        assert CONFIG[key] == value, key
    assert CONFIG["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert (CONFIG["num_hidden_layers"], CONFIG["num_experts"],
            CONFIG["vocab_size"]) == (5, 16, 12544)
    assert (CONFIG["num_hidden_layers_published"],
            CONFIG["num_experts_published"],
            CONFIG["vocab_size_published"]) == (48, 256, 100352)
    assert CONFIG["vocab_size"] * 8 == CONFIG["vocab_size_published"]
    assert set(CONFIG["reduced_note"]) == set(CONFIG["reduced"])
    for key in ("scoring", "shared_expert", "gate", "qk_norm", "blocks",
                "auxiliary_loss", "rotary", "yarn", "per_expert_load"):
        assert CONFIG["assumed"][key]
    for key in ("deployment", "memory_analysis", "limits", "tiny"):
        assert CONFIG[key]


def test_the_files_scalars_are_its_published_lists_and_rotaries():
    """The harness hands a family the file's scalars, so the layers run and
    both rotaries are stated a second time: ``published_as_run`` derives
    them from the lists and ``rope_parameters``, and ``build`` refuses a
    file in which the two differ."""
    stated = family.published_as_run(CONFIG)
    assert stated == {k: CONFIG[k] for k in stated}
    assert stated["layer_types_run"].split() == [
        "full_attention"] + ["sliding_attention"] * 3 + ["full_attention"]
    assert stated["num_attention_heads_per_layer_run"] == "48 72 72 72 48"
    assert stated["mlp_layer_types_run"] == "dense sparse sparse sparse sparse"
    assert stated["full_rope_type"] == "yarn"
    assert stated["full_partial_rotary_factor"] == 0.5
    assert stated["sliding_rope_theta"] == 10000
    assert ref.layer_table(PUBLISHED) == [
        ("full_attention", 48, "dense"), ("sliding_attention", 72, "sparse"),
        ("sliding_attention", 72, "sparse"),
        ("sliding_attention", 72, "sparse"), ("full_attention", 48, "sparse")]
    with pytest.raises(ValueError, match="name 5 layers"):
        ref.layer_table(dict(PUBLISHED, num_hidden_layers=4))
    drifted = dict(CONFIG, sliding_rope_theta=500000)
    assert family.published_as_run(drifted) != {
        k: drifted[k] for k in family.published_as_run(drifted)}


def test_the_program_is_told_the_published_rotaries_and_table():
    cfg = family.config_of(PUBLISHED)
    assert cfg.rope_full == laguna.ROPE_FULL
    assert cfg.rope_sliding == laguna.ROPE_SLIDING
    assert cfg.layer_types == laguna.laguna_s_2_1().layer_types[:5]
    assert cfg.heads_per_layer == (48, 72, 72, 72, 48)
    assert cfg.mlp_layer_types == ("dense",) + ("sparse",) * 4
    assert (cfg.sliding_window, cfg.d_ff, cfg.routed_scale) == (
        512, 12288, 2.5)
    tiny = family.config_of(TINY)
    assert tiny.heads_per_layer == (4, 6, 6, 6, 4)
    assert tiny.rope_full.attention_factor is None
    assert (tiny.rope_full.width, tiny.rope_sliding.width) == (8, 16)
    assert tiny.sliding_window < 96


def test_the_share_holds_1_113_007_104_parameters():
    shapes = jax.eval_shape(lambda k: ref.init_weights(k, PUBLISHED), KEY)
    count = lambda t: sum(int(np.prod(x.shape))
                          for x in jax.tree_util.tree_leaves(t))
    layers = shapes["layers"]
    assert count(layers[0]["attn"]) == 44_187_648
    assert count(layers[1]["attn"]) == 63_135_744
    assert count(layers[0]) == 157_440_000
    expert = 3 * 3072 * 1024
    assert count(layers[1]) - 256 == 73_365_504 + 16 * expert
    assert count(layers[4]) - 256 == 54_417_408 + 16 * expert
    outside = sum(count(p) for p in layers) - 4 * (256 + 16 * expert)
    assert outside == 431_953_920
    assert count(shapes) - 4 * 256 == 1_113_007_104
    assert count(shapes) - 4 * 256 == 431_953_920 + 77_073_408 + 603_979_776


def test_flops_and_bytes_from_the_shapes():
    sizes = dict(PUBLISHED, seq_len=16384, batch_per_chip=1)
    band = sum(min(t + 1, 512) for t in range(16384))
    assert band == 8_257_792 == family.pairs(sizes, "sliding")
    assert family.pairs(sizes, "full") == 134_225_920
    assert family.attention_flops(sizes, "sliding") == (
        12.0 * band * 128 * 72 * 3)
    assert family.attention_flops(sizes, "full") == (
        12.0 * 134_225_920 * 128 * 48 * 2)
    item = 2
    assert family.attention_bytes(sizes, "sliding") == item * 6 * 16384 * (
        128 * 72 * 3 + 128 * 8 * 3)
    assert family.attention_bytes(sizes, "full") == item * 6 * 16384 * (
        128 * 48 * 2 + 128 * 8 * 2)
    assert family.sparse_layers(sizes) == 4
    assert family.expert_bytes(sizes) == 3 * 4 * 16 * 9_437_184 * item
    # what every token meets outside the routed experts: the matrices of
    # the five layers (no norms, no selection bias) and the head
    dense = (431_953_920 - 10 * 3072) + 12544 * 3072
    assert family.dense_matmul_params(sizes) == dense
    # even routing: 0.625 of a token's 10 assignments in each of 4 layers
    per_token = family.model_flops_per_item(sizes, 4 * 0.625)
    matrices = dense + 2.5 * 9_437_184
    assert 493e6 < matrices < 495e6
    step = per_token * 16384
    assert abs(step - (6.0 * matrices * 16384
                       + family.attention_flops(sizes, "full")
                       + family.attention_flops(sizes, "sliding"))) < 1e6
    # 48.6 TFLOP in matrices, 19.8 in the two full layers' pairs, 2.7 in
    # the three bands' (12 a pair and head dimension, as the other families
    # count: the scores recomputed in the backward pass are left out)
    assert 71.0e12 < step < 71.2e12


# -------------------------------------------------------------- the control
def test_bfloat16_fails_a_float32_laguna():
    """The control at test size: the reference put in the program's place
    and computed in bfloat16 under a float32 configuration comes out as
    not correct, on two seeds, by the gradient norms; the reference
    itself passes."""
    for seed in (1, 2):
        key = jax.random.PRNGKey(seed)
        reference = ref.follow(TINY, key, 1, 3)
        assert compare.decide([as_record(reference)], reference, SOUND)[0]
        low = ref.follow(TINY, key, 1, 3, "bfloat16")
        correct, rows = compare.decide([as_record(low)], reference, SOUND)
        assert not correct
        failed = [name for name, _, _, ok in rows if not ok]
        assert any(n.startswith("grad_norm_gap") for n in failed), rows


def test_both_controls_are_far_off_where_the_program_is_not():
    """bfloat16 and float8 operands each move the worst matrix's gradient
    norm by many times what reassociation does, and the program in
    float32 stays inside it."""
    from benchmark.reference.common import leaf_norms
    reference = ref.follow(TINY, KEY, 1, 1)
    gaps = [compare.norm_gap(
        ref.follow(TINY, KEY, 1, 1, p)["grad_norms"],
        reference["grad_norms"])[0] for p in ("bfloat16", "float8")]
    assert min(gaps) > 10 * SOUND["grad_norm_gap"]
    assert gaps[1] > gaps[0]
    params = ref.init_weights(KEY, TINY)
    toks, tgts = ref.make_batch(KEY, TINY, 0)
    cfg = family.config_of(dict(TINY, use_flash=False))
    with jax.default_matmul_precision("highest"):
        grads = leaf_norms(jax.jit(jax.grad(
            lambda p: laguna.loss_fn(p, toks, tgts, cfg)))(params))
    sound, _ = compare.norm_gap(grads, reference["grad_norms"])
    assert sound <= SOUND["grad_norm_gap"] < gaps[0] and gaps[0] >= 3 * sound
