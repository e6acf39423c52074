"""The plain float32 decoder reference against ``horovod_tpu/models`` at tiny
sizes on the CPU, and the control: a run below the configuration's
precision has to fail the comparison that a sound run passes."""

import dataclasses
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

from benchmark import compare                           # noqa: E402
from benchmark.families import llama as llama_family    # noqa: E402
from benchmark.reference import llama as ref_llama      # noqa: E402
from benchmark.reference.common import leaf_norms  # noqa: E402
from horovod_tpu.models import llama                    # noqa: E402

LLAMA = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
             head_dim=16, intermediate_size=128, vocab_size=256,
             num_hidden_layers=2, sliding_window=24, rope_theta=10000.0,
             rms_norm_eps=1e-5, dtype="float32", batch_per_chip=2,
             seq_len=64)
# float32 against float32 at these sizes differs by reassociation only.
SOUND = {"loss_rel": 1e-5, "grad_norm_gap": 1e-4, "delta_norm_gap": 2e-3}
KEY = jax.random.PRNGKey(5)


def same_layout(a, b):
    assert jax.tree_util.tree_structure(a) == jax.tree_util.tree_structure(b)
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        assert x.shape == y.shape and x.dtype == y.dtype


def worst_rel(a, b):
    return max(float(jnp.max(jnp.abs(x - y)) / (jnp.max(jnp.abs(y)) + 1e-12))
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


def llama_cfg(**kw):
    return llama.LlamaConfig(
        vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq=64, rope_theta=10000.0, dtype=jnp.float32,
        dp_axis=None, tp_axis=None, sp_axis=None, use_flash=False,
        sliding_window=24, norm_eps=1e-5, **kw)


def as_record(followed, rank=0):
    return {"rank": rank, "first_losses": followed["losses"][rank],
            "grad_norms": followed["grad_norms"],
            "delta_norms": followed["delta_norms"], "digest": "",
            "last_loss": 1.0, "params_changed": True}


# -------------------------------------------------------------------- llama
def test_llama_weights_have_the_programs_layout():
    mine = jax.eval_shape(lambda k: ref_llama.init_weights(k, LLAMA), KEY)
    theirs = jax.eval_shape(lambda k: llama.init_params(llama_cfg(), k), KEY)
    same_layout(mine, theirs)


@pytest.mark.parametrize("use_flash", [False, True])
def test_llama_reference_agrees_with_the_model_in_float32(use_flash):
    params = ref_llama.init_weights(KEY, LLAMA)
    toks, tgts = ref_llama.make_batch(KEY, LLAMA, 0)
    cfg = llama_cfg()
    cfg = dataclasses.replace(cfg, use_flash=use_flash)  # True: interpreted
    with jax.default_matmul_precision("highest"):
        l1, g1 = jax.jit(jax.value_and_grad(
            lambda p: ref_llama.loss_fn(p, toks, tgts, LLAMA)))(params)
        l2, g2 = jax.jit(jax.value_and_grad(
            lambda p: llama.loss_fn(p, toks, tgts, cfg)))(params)
    assert abs(float(l1) - float(l2)) <= 1e-5 * abs(float(l2))
    assert worst_rel(g1, g2) <= 1e-4


def test_the_window_mask_matters_at_these_sizes():
    params = ref_llama.init_weights(KEY, LLAMA)
    toks, tgts = ref_llama.make_batch(KEY, LLAMA, 0)
    a = ref_llama.loss_fn(params, toks, tgts, LLAMA)
    b = ref_llama.loss_fn(params, toks, tgts, dict(LLAMA, sliding_window=64))
    assert abs(float(a) - float(b)) > 1e-4


@pytest.mark.parametrize("seq, window, pairs", [
    (4096, 4096, 4096 * 4097 // 2), (8, None, 36), (8, 3, 6 + 5 * 3),
    (16384, 4096, 4096 * 4097 // 2 + 12288 * 4096)])
def test_attended_pairs(seq, window, pairs):
    assert llama_family.attended_pairs(seq, window) == pairs
    if seq <= 8:
        i, j = np.arange(seq)[:, None], np.arange(seq)[None]
        mask = (j <= i) & ((i - j < window) if window else True)
        assert mask.sum() == pairs


def test_mistral_flops_per_token_from_the_shapes():
    sizes = dict(hidden_size=4096, num_attention_heads=32,
                 num_key_value_heads=8, head_dim=128, intermediate_size=14336,
                 vocab_size=32000, num_hidden_layers=4, sliding_window=4096,
                 seq_len=4096, dtype="bfloat16")
    layer = 4096 * 128 * (64 + 16) + 3 * 4096 * 14336
    assert layer == 218_103_808
    matmul = 6.0 * (4 * layer + 4096 * 32000)
    attention = 12.0 * (4096 * 4097 // 2) * 128 * 32 * 4 / 4096
    assert llama_family.model_flops_per_item(sizes) == pytest.approx(
        matmul + attention)
    assert llama_family.attention_bytes(sizes) == 2 * 4 * (
        6 * 4096 * 128 * 32 + 6 * 4096 * 128 * 8)


# -------------------------------------------------------------- the control
def test_bfloat16_fails_a_float32_decoder(family=ref_llama, sizes=LLAMA):
    """The control at test size: the reference put in the program's place
    and computed in bfloat16 under a float32 configuration comes out as
    not correct, on three seeds, by the gradient norms; the reference
    itself passes."""
    for seed in (1, 2, 3):
        key = jax.random.PRNGKey(seed)
        reference = family.follow(sizes, key, 1, 3)
        assert compare.decide([as_record(reference)], reference, SOUND)[0]
        low = family.follow(sizes, key, 1, 3, "bfloat16")
        correct, rows = compare.decide([as_record(low)], reference, SOUND)
        assert not correct
        failed = [name for name, _, _, ok in rows if not ok]
        assert any(n.startswith("grad_norm_gap") for n in failed), rows


def test_a_bfloat16_model_fails_a_float32_configuration():
    """The program's own lower-precision path as the control: the model in
    bfloat16 against the float32 reference of a float32 configuration."""
    reference = ref_llama.follow(LLAMA, KEY, 1, 1)
    params = ref_llama.init_weights(KEY, LLAMA)
    toks, tgts = ref_llama.make_batch(KEY, LLAMA, 0)

    def grads(dtype):
        cfg = dataclasses.replace(llama_cfg(), dtype=dtype)
        cast = jax.tree_util.tree_map(lambda x: x.astype(dtype), params)
        return leaf_norms(jax.jit(jax.grad(
            lambda p: llama.loss_fn(p, toks, tgts, cfg)))(cast))

    sound, _ = compare.norm_gap(grads(jnp.float32), reference["grad_norms"])
    low, _ = compare.norm_gap(grads(jnp.bfloat16), reference["grad_norms"])
    assert sound <= SOUND["grad_norm_gap"] < low
    assert low >= 3 * sound


def test_float8_is_further_off_than_bfloat16():
    reference = ref_llama.follow(LLAMA, KEY, 1, 1)
    gaps = [compare.norm_gap(
        ref_llama.follow(LLAMA, KEY, 1, 1, p)["grad_norms"],
        reference["grad_norms"])[0] for p in ("bfloat16", "float8")]
    assert gaps[1] > 3 * gaps[0] > 0


