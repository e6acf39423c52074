"""The plain float32 Nemotron-H reference against ``horovod_tpu/models`` at
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
from benchmark.families import nemotron_h as family             # noqa: E402
from benchmark.reference import nemotron_h as ref               # noqa: E402
from horovod_tpu.models import nemotron_h                       # noqa: E402

with open(os.path.join(ROOT, "benchmark", "configs",
                       "nemotron3-super-120b-a12b-11l.json")) as fh:
    CONFIG = json.load(fh)
PUBLISHED = {k: v for k, v in CONFIG.items()
             if not isinstance(v, (dict, list))}
TINY = dict(PUBLISHED, **CONFIG["tiny"], batch_per_chip=1, seq_len=200)
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
    for the four that are reduced; the pattern the program runs is the
    published one's first eleven characters."""
    published = {
        "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
        "expand": 2, "head_dim": 128, "hidden_size": 4096,
        "intermediate_size": 2688, "layer_norm_epsilon": 1e-05,
        "mamba_head_dim": 64, "mamba_hidden_act": "silu",
        "mamba_num_heads": 128, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "mlp_bias": False,
        "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
        "moe_intermediate_size": 2688, "moe_latent_size": 1024,
        "moe_shared_expert_intermediate_size": 5376,
        "moe_shared_expert_overlap": False,
        "mtp_hybrid_override_pattern": "*E", "n_group": 1, "n_groups": 8,
        "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 22,
        "num_key_value_heads": 2, "num_logits_to_keep": 1,
        "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
        "residual_in_fp32": False, "rope_theta": 10000,
        "routed_scaling_factor": 5, "sliding_window": None,
        "ssm_state_size": 128, "tie_word_embeddings": False,
        "time_step_floor": 0.0001, "time_step_max": 0.1,
        "time_step_min": 0.001, "topk_group": 1, "use_bias": False,
        "use_conv_bias": True, "use_mamba_kernels": True}
    assert {k: CONFIG[k] for k in published} == published
    assert CONFIG["hybrid_override_pattern"] == nemotron_h.PUBLISHED_PATTERN
    assert len(CONFIG["hybrid_override_pattern"]) == 88
    assert CONFIG["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size", "num_nextn_predict_layers"]
    assert [(CONFIG[k], CONFIG[p]) for k, p in (
        ("num_hidden_layers", "num_hidden_layers_published"),
        ("n_routed_experts", "num_experts_published"),
        ("vocab_size", "vocab_size_published"),
        ("num_nextn_predict_layers", "num_nextn_predict_layers_published"),
    )] == [(11, 88), (16, 512), (16384, 131072), (0, 1)]
    assert set(CONFIG["reduced_note"]) == set(CONFIG["reduced"])
    cfg = family.config_of(PUBLISHED)
    assert cfg.pattern == "MEMEMEM*EME" and cfg.experts_held == 16
    assert dict(d_model=4096, ssm_heads=128, ssm_head_dim=64, ssm_groups=8,
                ssm_state=128, chunk=128, n_heads=32, n_kv_heads=2,
                head_dim=128, n_experts=512, top_k=22, routed_scale=5.0,
                d_latent=1024, d_expert=2688, d_shared=5376).items() <= {
        k: getattr(cfg, k) for k in cfg.__dataclass_fields__}.items()
    assert set(CONFIG["assumed"]) >= {
        "router_input", "auxiliary_loss", "selection_bias", "mamba_vectors",
        "no_rotary", "column_order"}
    assert set(CONFIG["limits"]) >= {"loss_rel", "grad_norm_gap",
                                     "delta_norm_gap", "why"}


def test_the_drawn_weights_are_the_published_initialisation():
    """``A`` in (1, 16), steps in 0.001..0.1, ``D`` one, norm weights away
    from one, a selection bias small beside the scores' spread; and on the
    drawn batch some heads carry their state across a chunk and some
    forget inside it."""
    sizes = dict(TINY, mamba_num_heads=32, n_groups=4, hidden_size=128)
    params = ref.init_weights(KEY, sizes)
    for layer in (p for p in params["layers"] if "ssm" in p):
        p = layer["ssm"]
        a, step = np.exp(p["A_log"]), np.log1p(np.exp(p["dt_bias"]))
        assert a.shape == step.shape == (32,)
        assert (a > 1).all() and (a < 16).all() and a.max() - a.min() > 8
        assert (step > 0.00099).all() and (step < 0.101).all()
        assert step.max() > 20 * step.min()
        assert (np.asarray(p["D"]) == 1).all()
        assert float(jnp.mean(jnp.abs(p["norm"] - 1.0))) > 0.2
        assert float(jnp.mean(jnp.abs(layer["norm"] - 1.0))) > 0.2
        assert 0.2 < float(jnp.std(p["conv_bias"])) < 0.35
    bias = params["layers"][1]["moe"]["router_bias"]
    assert bias.shape == (16,) and 0.003 < float(jnp.std(bias)) < 0.03
    toks, _ = ref.make_batch(KEY, sizes, 0)
    counted = family.decay_stats(*nemotron_h.decay_stats(
        params, toks, family.config_of(sizes)), dict(sizes, seq_len=200))
    assert 0.1 < counted["least_share_carried"] < 0.9
    assert counted["chunks_per_sequence"] == 7
    assert max(counted["decay_least"]) < 0.5
    assert min(counted["decay_most"]) > 0.99


def test_a_missing_norm_is_far_off():
    """The program handed norm weights of one (the same as leaving the
    weights out) is caught by the loss."""
    params = ref.init_weights(KEY, TINY)
    toks, tgts = ref.make_batch(KEY, TINY, 0)
    cfg = family.config_of(dict(TINY, use_flash=False))
    ones = jax.tree_util.tree_map_with_path(
        lambda path, w: jnp.ones_like(w) if jax.tree_util.keystr(
            path).endswith(("['norm']", "['final_norm']")) else w, params)
    loss = jax.jit(lambda p: nemotron_h.loss_fn(p, toks, tgts, cfg))
    sound, plain = float(loss(params)), float(loss(ones))
    want = float(jax.jit(lambda p: ref.loss_fn(p, toks, tgts, TINY))(params))
    assert abs(sound - want) <= 1e-5 * want
    assert abs(plain - want) > 0.01 * want


# ------------------------------------------------- counts from the shapes
def test_the_share_holds_1_431_132_544_parameters():
    shapes = jax.eval_shape(lambda k: ref.init_weights(k, PUBLISHED), KEY)
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert n == 1_431_132_544                       # the issue's 1,431 M
    ssm = 4096 * 18560 + 8192 * 4096
    attn = 4096 * (32 + 2 * 2) * 128 + 4096 * 4096
    beside = 4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376
    assert family.dense_matmul_params(PUBLISHED) == (
        5 * ssm + attn + 5 * beside + 4096 * 16384) == 923_271_168
    assert family.expert_params(PUBLISHED) == 2 * 1024 * 2688
    assert family.layer_kinds(PUBLISHED) == (5, 5, 1)


def test_flops_and_bytes_from_the_shapes():
    sizes = dict(PUBLISHED, seq_len=8192, batch_per_chip=1)
    # a chunk of 128 tokens, forward: a head's 2 C^2 P + 4 C N P, a
    # group's 2 C^2 N once for its 16 heads
    chunk = 128 * (2 * 128 * 128 * 64 + 4 * 128 * 128 * 64) + 8 * (
        2 * 128 * 128 * 128)
    assert family.chunks_per_sequence(sizes) == 64
    assert family.ssm_scan_flops(sizes) == 3.0 * chunk * 64 * 5
    assert family.attention_flops(sizes) == (
        12.0 * (8192 * 8193 // 2) * 128 * 32)
    held = 5 * 22 * 16 / 512        # even routing: assignments held a token
    flops = family.model_flops_per_item(sizes, held)
    assert flops == pytest.approx(
        6.0 * (923_271_168 + held * 5_505_024)
        + (family.attention_flops(sizes)
           + family.ssm_scan_flops(sizes)) / 8192)
    assert flops == pytest.approx(5.95e9, rel=2e-3)   # the issue's about 6.0
    # x and y at 128 heads of 64 and B, C at 8 groups of 128 in bfloat16,
    # the step and the log decay float32
    x, bc, gates = 128 * 64 * 2, 2 * 8 * 128 * 2, 2 * 128 * 4
    assert family.ssm_scan_bytes(sizes) == (
        3 * (x + bc + gates) + 2 * x) * 8192 * 5
    # q, o, do, dq at 32 heads and k, v, dk, dv at 2, of 128: 6 q + 6 k
    assert family.attention_bytes(sizes) == 2 * 8192 * 128 * (
        6 * 32 + 6 * 2)
    assert family.expert_bytes(sizes) == 3 * 5 * 16 * 5_505_024 * 2


def test_the_expert_load_counter():
    counts = np.array([[300, 352, 410, 352]] * 5)
    load = family.expert_load(counts, 8192, dict(PUBLISHED))
    assert load["assignments"] == 5 * 8192 * 22
    assert load["assignments_held"] == 5 * 1414
    assert load["tokens_per_held_expert"] == {"least": 300, "mean": 353.5,
                                              "most": 410}
    assert load["assignments_dropped"] == 0


# -------------------------------------------------------------- the control
def test_bfloat16_fails_a_float32_nemotron_h():
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
            lambda p: nemotron_h.loss_fn(p, toks, tgts, cfg)))(params))
    sound, _ = compare.norm_gap(grads, reference["grad_norms"])
    low, _ = compare.norm_gap(
        ref.follow(TINY, KEY, 1, 1, "bfloat16")["grad_norms"],
        reference["grad_norms"])
    assert sound <= SOUND["grad_norm_gap"] < low
    assert low >= 3 * sound


def test_both_controls_are_far_off_where_the_program_is_not():
    """bfloat16 and float8 operands each move the worst matrix's gradient
    norm by many times what reassociation does."""
    reference = ref.follow(TINY, KEY, 1, 1)
    gaps = [compare.norm_gap(
        ref.follow(TINY, KEY, 1, 1, p)["grad_norms"],
        reference["grad_norms"])[0] for p in ("bfloat16", "float8")]
    assert min(gaps) > 10 * SOUND["grad_norm_gap"]
    assert gaps[1] > gaps[0]
