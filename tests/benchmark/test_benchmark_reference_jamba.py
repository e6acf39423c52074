"""The plain float32 Jamba reference against ``horovod_tpu/models`` at tiny
sizes on the CPU, the configuration against the catalog row, the counts the
family makes from the shapes, and the control: a run below the
configuration's precision has to fail the comparison that a sound run
passes."""

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
from benchmark.families import jamba as family                  # noqa: E402
from benchmark.reference import jamba as ref                    # noqa: E402
from horovod_tpu.models import jamba                            # noqa: E402

with open(os.path.join(ROOT, "benchmark", "configs",
                       "jamba2-3b-14l.json")) as fh:
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
    for the one that is reduced; every width is the published one."""
    published = {
        "attn_layer_offset": 7, "attn_layer_period": 14,
        "expert_layer_offset": 1, "expert_layer_period": 2,
        "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 8192, "mamba_conv_bias": True,
        "mamba_d_conv": 4, "mamba_d_state": 16, "mamba_dt_rank": 160,
        "mamba_expand": 2, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "model_type": "jamba",
        "num_attention_heads": 20, "num_experts": 1,
        "num_experts_per_tok": 1, "num_key_value_heads": 1,
        "num_logits_to_keep": 1, "rms_norm_eps": 1e-06,
        "sliding_window": None, "tie_word_embeddings": True,
        "use_mamba_kernels": True, "vocab_size": 65536}
    assert {k: CONFIG[k] for k in published} == published
    assert CONFIG["reduced"] == ["num_hidden_layers"]
    assert (CONFIG["num_hidden_layers"],
            CONFIG["num_hidden_layers_published"]) == (14, 28)
    assert set(CONFIG["reduced_note"]) == set(CONFIG["reduced"])
    assert CONFIG["head_dim"] * CONFIG["num_attention_heads"] == CONFIG[
        "hidden_size"]
    cfg = family.config_of(PUBLISHED)
    assert cfg.pattern == "MMMMMMM*MMMMMM"
    assert dict(d_model=2560, d_ff=8192, vocab_size=65536, n_heads=20,
                n_kv_heads=1, head_dim=128, mamba_expand=2, mamba_d_state=16,
                mamba_d_conv=4, mamba_dt_rank=160, num_experts=1,
                norm_eps=1e-6).items() <= {
        k: getattr(cfg, k) for k in cfg.__dataclass_fields__}.items()
    assert cfg.ssm_dims().d_inner == 5120 and cfg.ssm_dims().dt_rank == 160
    assert set(CONFIG["assumed"]) >= {
        "layer_order", "inner_norms", "no_rotary", "head_dim",
        "mamba_vectors", "draws", "column_order", "tied_gradient"}
    assert set(CONFIG["limits"]) >= {"loss_rel", "grad_norm_gap",
                                     "delta_norm_gap", "why"}
    for word in ("two stages", "stage 0", "tied"):
        assert word in CONFIG["deployment"]
    # the tiny preset changes sizes, never the mechanism
    assert not set(CONFIG["tiny"]) & {"mamba_d_state", "mamba_d_conv",
                                      "mamba_expand", "num_experts",
                                      "tie_word_embeddings"}


def test_the_drawn_weights_are_the_published_initialisation():
    """``A = 1 .. 16`` for every channel, steps in 0.001..0.1, ``D`` one,
    norm weights away from one (the three inner norms' too), a convolution
    bias in ±0.5; and on the drawn batch a share of the (token, channel,
    state) triples forgets within a token and most do not."""
    sizes = dict(TINY, hidden_size=128)
    params = ref.init_weights(KEY, sizes)
    for layer in (p for p in params["layers"] if "ssm" in p):
        p = layer["ssm"]
        a, step = np.exp(p["A_log"]), np.log1p(np.exp(p["dt_bias"]))
        assert a.shape == (256, 16) and step.shape == (256,)
        np.testing.assert_allclose(a, np.broadcast_to(np.arange(1, 17),
                                                      a.shape), rtol=1e-6)
        assert (step > 0.00099).all() and (step < 0.101).all()
        assert step.max() > 20 * step.min()
        assert (np.asarray(p["D"]) == 1).all()
        for name in ("dt_norm", "b_norm", "c_norm"):
            assert float(jnp.mean(jnp.abs(p[name] - 1.0))) > 0.1
        assert float(jnp.mean(jnp.abs(layer["mixer_norm"] - 1.0))) > 0.2
        assert float(jnp.mean(jnp.abs(layer["mlp_norm"] - 1.0))) > 0.2
        assert 0.2 < float(jnp.std(p["conv_bias"])) < 0.35
    assert "lm_head" not in params
    assert 0.8 < float(jnp.std(params["embed"])) * np.sqrt(128) < 1.2
    toks, _ = ref.make_batch(KEY, sizes, 0)
    counted = family.decay_stats(*jamba.decay_stats(
        params, toks, family.config_of(sizes)))
    low, high = family.FORGETTING_SHARE
    assert low < counted["least_share"] <= counted["most_share"] < 0.3 < high
    assert len(counted["token_decay_under_0.5_share"]) == 3
    assert max(counted["delta_least"]) < 1e-3
    assert min(counted["delta_most"]) > 0.1


def test_a_missing_norm_is_far_off():
    """The program handed norm weights of one (the same as leaving the
    weights out) moves the loss of random weights by a hundred times what
    the sound program differs by; the three inner norms alone move the
    logits by a hundred times the models' tolerance."""
    params = ref.init_weights(KEY, TINY)
    toks, tgts = ref.make_batch(KEY, TINY, 0)
    cfg = family.config_of(dict(TINY, use_flash=False))

    def ones(suffixes):
        return jax.tree_util.tree_map_with_path(
            lambda path, w: jnp.ones_like(w) if jax.tree_util.keystr(
                path).endswith(suffixes) else w, params)

    loss = jax.jit(lambda p: jamba.loss_fn(p, toks, tgts, cfg))
    want = float(jax.jit(lambda p: ref.loss_fn(p, toks, tgts, TINY))(params))
    assert abs(float(loss(params)) - want) <= 1e-5 * want
    plain = float(loss(ones(("_norm']",))))
    assert abs(plain - want) > 100 * 1e-5 * want
    # the three inner norms alone: the loss of random weights hardly feels
    # them, the logits do
    logits = jax.jit(lambda p: jamba.forward(p, toks, cfg))
    sound = logits(params)
    inner = logits(ones(("['dt_norm']", "['b_norm']", "['c_norm']")))
    assert float(jnp.max(jnp.abs(inner - sound))) > 0.02 * float(
        jnp.max(jnp.abs(sound)))


# ------------------------------------------------- counts from the shapes
def test_the_stage_holds_1_598_556_096_parameters():
    shapes = jax.eval_shape(lambda k: ref.init_weights(k, PUBLISHED), KEY)
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    mamba_layer = (26_214_400 + 25_600 + 983_040 + 192 + 824_320 + 81_920
                   + 5_120 + 13_107_200)
    assert mamba_layer == 41_241_792
    assert n == (13 * (mamba_layer + 62_914_560 + 5_120)
                 + (13_762_560 + 62_914_560 + 5_120)
                 + 65536 * 2560 + 2560) == 1_598_556_096   # the issue's count
    ssm = 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560
    attn = 2560 * (20 + 2) * 128 + 2560 * 2560
    assert family.matmul_params(PUBLISHED) == (
        13 * ssm + attn + 14 * 3 * 2560 * 8192 + 2560 * 65536)
    assert family.layer_kinds(PUBLISHED) == (13, 1)


def test_flops_and_bytes_from_the_shapes():
    sizes = dict(PUBLISHED, seq_len=8192, batch_per_chip=1)
    assert family.attention_flops(sizes) == (
        12.0 * (8192 * 8193 // 2) * 128 * 20)
    flops = family.model_flops_per_item(sizes)
    assert flops == pytest.approx(
        6.0 * family.matmul_params(sizes)
        + family.attention_flops(sizes) / 8192)
    # 80 TFLOP of model work a step: the issue's count
    assert flops * 8192 == pytest.approx(79.5e12, rel=1e-2)
    # x, y, dy, dx at 5120 channels and B, C, dB, dC at 16 states in
    # bfloat16, the step and its gradient float32: forward, the forward
    # again, backward; 13 layers
    x, bc, step = 5120 * 2, 2 * 16 * 2, 5120 * 4
    forward = 2 * x + step + bc
    assert family.selective_scan_bytes(sizes) == (
        2 * forward + forward + x + step + bc) * 8192 * 13
    assert family.selective_scan_bytes(sizes) == pytest.approx(16.4e9,
                                                               rel=1e-2)
    # q, o, do, dq at 20 heads and k, v, dk, dv at one, of 128: 6 q + 6 k
    assert family.attention_bytes(sizes) == 2 * 8192 * 128 * (6 * 20 + 6 * 1)


def test_a_batch_whose_state_never_or_always_forgets_is_refused():
    counted = family.decay_stats([0.11, 0.02, 0.3], [1e-5] * 3, [3.0] * 3)
    assert counted["least_share"] == 0.02 and counted["most_share"] == 0.3
    low, high = family.FORGETTING_SHARE
    assert (low, high) == (0.05, 0.95)
    assert not low <= counted["least_share"]
    assert family.decay_stats([0.5, 0.99], [0.1] * 2, [9.0] * 2)[
        "most_share"] > high


# -------------------------------------------------------------- the control
def test_bfloat16_fails_a_float32_jamba():
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
            lambda p: jamba.loss_fn(p, toks, tgts, cfg)))(params))
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
