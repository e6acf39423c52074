"""The plain float32 JoyAI-LLM-Flash reference and its configuration at
tiny sizes on the CPU: the configuration against the catalog row, the
share's parameter count, the counts the family makes from the shapes, the
gradient a layer a jitted call against the whole one, the bias it moves,
and the control: a run below the configuration's precision has to fail the
comparison that a sound run passes.  (``tests/test_joyai.py`` holds the
program against this reference.)"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import compare                                   # noqa: E402
from benchmark.families import joyai as family                  # noqa: E402
from benchmark.reference import joyai as ref                    # noqa: E402
from benchmark.reference.resnet import scalars                  # noqa: E402

with open(os.path.join(ROOT, "benchmark", "configs",
                       "joyai-llm-flash-5l.json")) as fh:
    CONFIG = json.load(fh)
PUBLISHED = {k: v for k, v in CONFIG.items()
             if not isinstance(v, (dict, list))}
TINY = dict(PUBLISHED, **CONFIG["tiny"], batch_per_chip=1, seq_len=64)
# float32 against float32 at these sizes differs by reassociation only.
SOUND = {"loss_rel": 1e-5, "grad_norm_gap": 2e-4, "delta_norm_gap": 2e-3,
         "vector_delta_norm_gap": 1e-3}
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
    for the three that are reduced; every width the published one."""
    published = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
        "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 7168, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
        "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 8,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 1,
        "q_lora_rank": 1536, "qk_head_dim": 192, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_interleave": True, "rope_scaling": None,
        "rope_theta": 32000000, "routed_scaling_factor": 2.5,
        "scoring_func": "sigmoid", "tie_word_embeddings": False,
        "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128}
    for key, value in published.items():
        assert CONFIG[key] == value, key
    assert CONFIG["source"] == ("https://huggingface.co/jdopensource/"
                                "JoyAI-LLM-Flash/blob/main/config.json")
    assert CONFIG["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert (CONFIG["num_hidden_layers"], CONFIG["n_routed_experts"],
            CONFIG["vocab_size"]) == (5, 32, 16256)
    assert (CONFIG["num_hidden_layers_published"],
            CONFIG["n_routed_experts_published"],
            CONFIG["vocab_size_published"]) == (40, 256, 129280)
    # an eighth of the vocabulary as a vocabulary-parallel head pads it
    assert CONFIG["vocab_size"] * 8 == 130048 >= 129280
    assert CONFIG["vocab_size"] % 128 == 0 and (129280 // 8) % 128
    assert CONFIG["n_routed_experts"] * 8 == 256
    assert set(CONFIG["reduced_note"]) == set(CONFIG["reduced"]) | {"total"}
    assert (CONFIG["mtp_loss_weight"], CONFIG["bias_update_speed"]) == (
        0.3, 0.001)
    for key in ("mtp_loss_weight", "bias_update_speed", "router_bias_init",
                "mtp_input", "mtp_block", "aux_loss", "blocks", "rotary",
                "shared_expert", "k_rope_layout"):
        assert CONFIG["assumed"][key]
    for key in ("deployment", "memory_analysis", "limits", "tiny"):
        assert CONFIG[key]
    for name in ("loss_rel", "grad_norm_gap", "delta_norm_gap",
                 "vector_delta_norm_gap", "why"):
        assert name in CONFIG["limits"]
    # tiny still has two widths and a module
    assert TINY["qk_head_dim"] != TINY["v_head_dim"]
    assert TINY["num_nextn_predict_layers"] == 1


def test_the_share_holds_1_058_320_384_parameters():
    shapes = jax.eval_shape(lambda k: ref.init_weights(k, PUBLISHED), KEY)
    count = lambda t: sum(int(np.prod(x.shape))
                          for x in jax.tree_util.tree_leaves(t))
    attn = count(shapes["layers"][1]["attn"])
    assert attn == 26_347_520
    assert count(shapes["layers"][0]) == 70_391_808
    assert count(shapes["layers"][1]) - 256 == 182_589_440
    assert count(shapes["mtp"]) - 256 == 190_984_192
    assert count({k: shapes[k] for k in ("embed", "lm_head", "final_norm")}
                 ) == 66_586_624
    assert count(shapes) - 5 * 256 == 1_058_320_384
    assert shapes["layers"][1]["moe"]["router_bias"].dtype == jnp.float32
    assert shapes["layers"][1]["moe"]["w1"].shape == (32, 2048, 768)
    assert shapes["layers"][1]["moe"]["router"].shape == (2048, 256)


def test_flops_and_bytes_from_the_shapes():
    sizes = dict(PUBLISHED, seq_len=16384, batch_per_chip=1)
    pairs = 134_225_920
    # per pair and head 4 x (192 + 128) / 2 forward, twice that backward
    assert family.attention_flops(sizes, 1) == 12.0 * pairs * 32 * 160
    assert 8.2e12 < family.attention_flops(sizes, 1) < 8.3e12
    assert family.attention_bytes(sizes, 5) == 2 * 6 * 16384 * 32 * 5 * (
        192 + 128)
    assert (family.attention_calls(sizes), family.expert_layers(sizes)) == (
        6, 5)
    assert family.expert_params(sizes) == 4_718_592
    assert family.expert_bytes(sizes) == 3 * 5 * 32 * 4_718_592 * 2
    # what every token meets outside the routed experts: six attention
    # blocks, layer 0's SwiGLU, five routers and shared experts, the head
    # twice, W_eh (no norms, no bias)
    dense = (6 * (26_347_520 - 2048) + 44_040_192
             + 5 * (524_288 + 4_718_592) + 2 * 2048 * 16256 + 8_388_608)
    assert family.dense_matmul_params(sizes) == dense
    # even routing: an eighth of a token's 8 assignments in each of 5
    step = family.model_flops_per_item(sizes, 5 * 1.0) * 16384
    matrices = 6.0 * (dense + 5 * 4_718_592) * 16384
    assert abs(step - matrices - family.attention_flops(sizes, 6)) < 1e6
    # 49.5 TFLOP in the six calls' pairs of about 81: 61 %
    assert 0.60 < family.attention_flops(sizes, 6) / step < 0.62
    assert 80e12 < step < 82e12


def test_the_gradient_a_layer_a_call_is_the_whole_gradient():
    """``gradient`` (what ``follow`` takes: a layer a jitted call, the
    module between the stack and the heads) gives both loss terms, every
    leaf's gradient and the counts of ``loss_terms`` differentiated
    whole."""
    params = ref.init_weights(KEY, TINY)
    toks, tgts = ref.make_batch(KEY, TINY, 0)
    with jax.default_matmul_precision("highest"):
        terms, grads, counts = ref.gradient(
            ref._pieces(scalars(TINY), "float32"), params, toks, tgts, TINY)
        (want_terms, want_counts), want = jax.jit(
            lambda p: (ref.loss_terms(p, toks, tgts, TINY),
                       jax.grad(lambda p: ref.loss_fn(p, toks, tgts, TINY))(
                           p)))(params)
    for got, w in zip(terms, want_terms):
        assert abs(got - float(w)) <= 1e-5 * float(w)
    for a, b in zip(counts, want_counts):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert jax.tree_util.tree_structure(grads) == (
        jax.tree_util.tree_structure(want))
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree_util.tree_leaves(want)):
        scale = float(jnp.max(jnp.abs(b))) or 1.0
        assert float(jnp.max(jnp.abs(a - b))) <= 5e-4 * scale, path


def test_follow_moves_the_bias_by_the_load_and_adam_leaves_it():
    """After one step every selection bias lies ``bias_update_speed`` from
    its seeded value wherever an expert's count is off the mean, and
    nowhere further; its gradient's norm is 0."""
    followed = ref.follow(TINY, KEY, 1, 1)
    bias = {k: v for k, v in followed["delta_norms"].items()
            if "router_bias" in k}
    assert len(bias) == 2                   # the expert layer, the module
    for leaf, norm in bias.items():
        assert 0.001 < norm <= 0.001 * np.sqrt(16) + 1e-9, leaf
        assert followed["grad_norms"][leaf] == 0.0
    assert len(followed["loss_terms"]) == 1
    main, mtp = followed["loss_terms"][0]
    assert abs(followed["losses"][0][0] - main - 0.3 * mtp) <= 1e-6


def test_both_controls_fail_a_float32_run_that_the_reference_passes():
    """The control at test size: the reference put in the program's place
    and computed in bfloat16, and in float8, under a float32 configuration
    comes out as not correct by the gradient norms; the reference itself
    passes."""
    reference = ref.follow(TINY, KEY, 1, 3)
    assert compare.decide([as_record(reference)], reference, SOUND)[0]
    gaps = []
    for precision in ("bfloat16", "float8"):
        low = ref.follow(TINY, KEY, 1, 3, precision)
        correct, rows = compare.decide([as_record(low)], reference, SOUND)
        assert not correct
        failed = [name for name, _, _, ok in rows if not ok]
        assert any(n.startswith("grad_norm_gap") for n in failed), rows
        gaps.append(compare.norm_gap(low["grad_norms"],
                                     reference["grad_norms"])[0])
    assert gaps[1] > gaps[0] > 10 * SOUND["grad_norm_gap"]
