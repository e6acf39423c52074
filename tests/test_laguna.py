"""The Laguna model path at test size on the CPU: the layer table from the
three per-layer lists, head counts that differ by layer kind in one model,
both rotaries (YaRN's frequencies against numbers written here), the window
against the full mask, the gate a head, the leading dense layer, the expert
layer's share test, the whole model's logits, loss and every leaf's
gradient against the benchmark's float32 reference (which shares no code
with the program; where bfloat16 in float32's place fails), the planted
faults, the counters, three optimizer steps, and the train step under
``shard_map`` with the in-graph ``DistributedOptimizer``."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import horovod_tpu as hvd                                   # noqa: E402
from benchmark.reference import laguna as ref               # noqa: E402
from family import (EXPERT_ROUTINGS, Seeded,                 # noqa: E402
                    expert_blocks_case, planted, worst_rel)
from horovod_tpu import trace                               # noqa: E402
from horovod_tpu.compat import shard_map                    # noqa: E402
from horovod_tpu.models import blocks, laguna, moe          # noqa: E402

# the first five layers (full + dense; sliding, sliding, sliding, full, all
# on expert layers) with the configuration file's ``tiny`` widths, a window
# a quarter of the sequence and a YaRN that starts from 32 positions
SIZES = dict(
    hidden_size=64, intermediate_size=96, num_hidden_layers=5,
    layer_types_run="full_attention sliding_attention sliding_attention "
                    "sliding_attention full_attention",
    num_attention_heads_per_layer_run="4 6 6 6 4",
    mlp_layer_types_run="dense sparse sparse sparse sparse",
    num_key_value_heads=2, head_dim=16, sliding_window=16,
    full_rope_type="yarn", full_rope_theta=500000, full_rope_factor=4,
    full_rope_original_max_position_embeddings=32, full_rope_beta_fast=32,
    full_rope_beta_slow=1, full_rope_attention_factor=None,
    full_partial_rotary_factor=0.5, sliding_rope_type="default",
    sliding_rope_theta=10000, sliding_partial_rotary_factor=1,
    num_experts=4, num_experts_published=16, first_expert=0,
    num_experts_per_tok=3, moe_routed_scaling_factor=2.5,
    moe_intermediate_size=32, shared_expert_intermediate_size=32,
    vocab_size=256, rms_norm_eps=1e-6, dtype="float32", batch_per_chip=2,
    seq_len=64)
KEY = jax.random.PRNGKey(47)
# float32 against float32 at 64 tokens: reassociation only (the program
# sums the sorted assignments and the head's blocks, the reference walks
# the held experts and blocks of queries).  bfloat16 in float32's place
# reads 100 times these: the last test of the file.
LOGITS_TOL, LOSS_TOL, GRAD_TOL = 2e-4, 1e-5, 5e-4


def config(sizes=SIZES, **kw):
    from benchmark.families import laguna as family
    return family.config_of({**sizes, "use_flash": False, **kw})


SEEDED = Seeded(ref, SIZES, KEY)
# Where depth is not what a test asserts (Adam's wiring leaf by leaf, the
# gradient exchange, the mean over ranks), the dense full layer and one
# sliding expert layer: a step's compile time follows its layers.
SHALLOW = dict(
    SIZES, num_hidden_layers=2,
    layer_types_run="full_attention sliding_attention",
    num_attention_heads_per_layer_run="4 6",
    mlp_layer_types_run="dense sparse")


# ------------------------------------------------------------ the layer table
def test_the_published_table_is_one_full_layer_in_four_and_a_dense_first():
    cfg = laguna.laguna_s_2_1()
    assert cfg.n_layers == 48
    assert [cfg.is_sliding(i) for i in range(8)] == [
        False, True, True, True] * 2
    assert cfg.heads_per_layer[:5] == (48, 72, 72, 72, 48)
    assert [cfg.is_sparse(i) for i in range(3)] == [False, True, True]
    assert (cfg.rope_full.kind, cfg.rope_full.width) == ("yarn", 64)
    assert (cfg.rope_sliding.kind, cfg.rope_sliding.width) == (
        "default", 128)


def test_the_published_sizes_count_118b_parameters():
    cfg = laguna.laguna_s_2_1()
    shapes = jax.eval_shape(lambda k: laguna.init_params(cfg, k), KEY)
    count = lambda t: sum(int(np.prod(x.shape))
                          for x in jax.tree_util.tree_leaves(t))
    full, sliding = shapes["layers"][0]["attn"], shapes["layers"][1]["attn"]
    assert count(full) == 44_187_648 and count(sliding) == 63_135_744
    assert shapes["layers"][0]["mlp"]["w_gate"].shape == (3072, 12288)
    assert "moe" not in shapes["layers"][0] and "mlp" not in shapes[
        "layers"][1]
    assert shapes["layers"][1]["moe"]["w1"].shape == (256, 3072, 1024)
    assert 117e9 < count(shapes) < 119e9


def test_the_cells_share_counts_what_the_configuration_file_says():
    from benchmark import cell as cells
    from benchmark.families import laguna as family
    cell = cells.load_cell("laguna_s2_1-5l-spmd-1c")
    assert family.published_as_run(cell.config) == {
        k: cell.config[k] for k in family.published_as_run(cell.config)}
    shapes = jax.eval_shape(lambda k: ref.init_weights(k, cell.sizes), KEY)
    # 1,113,007,104 and the four selection biases of 256 zeros
    assert sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(
        shapes)) == 1_113_007_104 + 4 * 256
    cfg = family.config_of(cell.sizes)
    assert cfg.heads_per_layer == (48, 72, 72, 72, 48)
    assert cfg.rope_full == laguna.ROPE_FULL
    assert cfg.rope_sliding == laguna.ROPE_SLIDING
    assert (cfg.first_expert, cfg.experts_held, cfg.n_experts) == (0, 16, 256)
    # a sixteenth of the experts: the sequence's sorted assignments in ten
    # blocks of 16384
    assert moe.dropless_blocks(cell.sizes["seq_len"] * 10,
                               cfg.moe_cfg()) == 10


@pytest.mark.parametrize("kw, match", [
    (dict(heads_per_layer=(4, 6, 6, 6)), "a layer an entry"),
    (dict(layer_types=("full_attention",) * 4 + ("chunked_attention",)),
     "full_attention or sliding_attention"),
    (dict(mlp_layer_types=("dense",) * 4 + ("moe",)), "dense or sparse"),
    (dict(heads_per_layer=(4, 6, 6, 6, 5)), "multiples of 2"),
    (dict(rope_sliding=blocks.Rotary(width=32)), "a rotary of 32"),
])
def test_a_config_the_family_cannot_run_is_refused(kw, match):
    with pytest.raises(ValueError, match=match):
        laguna.tiny(**kw)


def test_init_params_has_the_references_layout():
    cfg = config()
    mine = jax.eval_shape(lambda k: laguna.init_params(cfg, k), KEY)
    theirs = jax.eval_shape(lambda k: ref.init_weights(k, SIZES), KEY)
    assert jax.tree_util.tree_structure(mine) == \
        jax.tree_util.tree_structure(theirs)
    assert jax.tree_util.tree_leaves(mine) == jax.tree_util.tree_leaves(theirs)
    # head counts of two kinds in one model, a gate a head
    assert [p["attn"]["wq"].shape[1] for p in mine["layers"]] == [
        64, 96, 96, 96, 64]
    assert [p["attn"]["wg"].shape[1] for p in mine["layers"]] == [
        4, 6, 6, 6, 4]
    assert ["mlp" in p for p in mine["layers"]] == [True] + [False] * 4


# ---------------------------------------------------------------- the rotary
def test_yarns_frequencies_are_the_numbers_written_here():
    """Width 16, theta 10000, factor 8 from 64 positions, ``beta_fast`` 4,
    ``beta_slow`` 1, by hand: the correction dimensions are 16 ln(64 / (2
    pi beta)) / (2 ln 10000) = 0.812 and 2.016, so the ramp runs from index
    0 to 3: r = 0, 1/3, 2/3, 1, 1, ...; the frequency is f (1 - r) + f / 8
    r with f = 10000^(-j / 8); cos and sin times 0.1 ln 8 + 1."""
    want = [1.0, 0.22399466759526024, 0.04166666666666667,
            0.003952847075210474, 0.00125, 0.0003952847075210474, 0.000125,
            3.952847075210474e-05]
    rot = blocks.Rotary(width=16, theta=10000.0, kind="yarn", factor=8.0,
                        original_max=64, beta_fast=4.0, beta_slow=1.0)
    freqs, scale = blocks.rotary_frequencies(rot)
    np.testing.assert_allclose(freqs, want, rtol=1e-12)
    assert abs(scale - 1.2079441541679836) < 1e-12
    sizes = dict(head_dim=16, full_partial_rotary_factor=1,
                 full_rope_type="yarn", full_rope_theta=10000,
                 full_rope_factor=8,
                 full_rope_original_max_position_embeddings=64,
                 full_rope_beta_fast=4, full_rope_beta_slow=1)
    theirs, their_scale, width = ref.rotary_of(sizes, sliding=False)
    np.testing.assert_allclose(theirs, want, rtol=1e-12)
    assert width == 16 and abs(their_scale - scale) < 1e-12


def test_the_published_yarn_keeps_the_fast_and_divides_the_slow():
    """The published setting: indices up to 9 keep their frequency, from
    18 on it is divided by 128, between them the ramp; the scale is the
    config's ``attention_factor``, which is 0.1 ln 128 + 1."""
    freqs, scale = blocks.rotary_frequencies(laguna.ROPE_FULL)
    plain = 500000.0 ** (-np.arange(32) / 32)
    np.testing.assert_allclose(freqs[:10], plain[:10], rtol=1e-12)
    np.testing.assert_allclose(freqs[18:], plain[18:] / 128, rtol=1e-12)
    assert ((freqs[10:18] < plain[10:18])
            & (freqs[10:18] > plain[10:18] / 128)).all()
    assert scale == 1.4852030263919618
    assert abs(scale - (0.1 * np.log(128) + 1)) < 1e-12
    derived = dataclasses.replace(laguna.ROPE_FULL, attention_factor=None)
    assert abs(blocks.rotary_frequencies(derived)[1] - scale) < 1e-12


def test_the_plain_rotary_is_qwen3_nexts_partial_rope():
    """``blocks.rotary`` told ``default`` and a width is the rotary that
    ``qwen3_next`` applies: the same pairs, the same angles."""
    from horovod_tpu.models import qwen3_next
    x = jax.random.normal(KEY, (2, 24, 3, 16))
    for width in (16, 8, 4):
        got = blocks.rotary(x, blocks.Rotary(width=width, theta=1e7))
        want = qwen3_next._partial_rope(x, width, 1e7)
        assert float(jnp.max(jnp.abs(got - want))) == 0.0


def test_a_rotary_turns_its_width_and_passes_the_rest():
    x = jax.random.normal(KEY, (1, 12, 2, 16))
    y = blocks.rotary(x, laguna.tiny().rope_full)           # width 8
    assert float(jnp.max(jnp.abs(y[..., 8:] - x[..., 8:]))) == 0.0
    assert float(jnp.max(jnp.abs(y[:, 1:, :, :8] - x[:, 1:, :, :8]))) > 0.1
    # position 0 is not turned, only scaled by the attention factor
    scale = blocks.rotary_frequencies(laguna.tiny().rope_full)[1]
    np.testing.assert_allclose(y[:, 0, :, :8], scale * x[:, 0, :, :8],
                               rtol=1e-6)
    with pytest.raises(ValueError, match="default or yarn"):
        blocks.Rotary(width=8, kind="linear")
    with pytest.raises(ValueError, match="turns pairs"):
        blocks.Rotary(width=7)


# ------------------------------------------------------------- the attention
def attention_of(layer, x, cfg, params):
    """The attention block's own part of a layer: its output less its
    input."""
    return laguna._attention_block(params["layers"][layer], x, cfg,
                                   layer) - x


@pytest.mark.parametrize("use_flash", [False, True])
def test_a_sliding_layer_sees_its_window_and_a_full_layer_everything(
        use_flash):
    """A change to token 0 reaches query ``t`` of a sliding layer only
    where ``t < window``; it reaches every later query of a full layer.
    (A sliding layer that attends everything fails the first.)"""
    params, _, _ = SEEDED
    cfg = config(use_flash=use_flash)
    x = jax.random.normal(KEY, (1, 64, 64))
    moved = x.at[0, 0].add(1.0)
    both = jax.jit(lambda layer: jnp.max(jnp.abs(
        attention_of(layer, moved, cfg, params)
        - attention_of(layer, x, cfg, params)), axis=-1)[0],
        static_argnums=0)
    with jax.default_matmul_precision("highest"):
        for layer, reach in ((1, 16), (0, 64)):
            change = both(layer)
            assert float(jnp.min(change[:reach])) > 1e-6
            assert float(jnp.max(change[reach:], initial=0.0)) == 0.0


def test_the_window_is_the_references_band_not_the_full_mask():
    """The reference's attention over the slice of keys a block can see is
    its full masked softmax with the band's mask, and differs from the
    causal one."""
    q = jax.random.normal(KEY, (1, 50, 6, 16))
    k, v = (jax.random.normal(jax.random.PRNGKey(i), (1, 50, 2, 16))
            for i in (1, 2))

    def masked(window):
        qg = q.reshape(1, 50, 2, 3, 16)
        s = jnp.einsum("bqkrd,bskd->bkrqs", qg, k) / 4.0
        i, j = jnp.arange(50)[:, None], jnp.arange(50)[None]
        keep = (j <= i) & ((i - j < window) if window else True)
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        return jnp.einsum("bkrqs,bskd->bqkrd", p, v).reshape(1, 50, 6, 16)

    with jax.default_matmul_precision("highest"):
        for block in (8, 16, 64):           # banded twice, whole once
            ref.QUERY_BLOCK, sound = block, ref.QUERY_BLOCK
            try:
                got = ref.attention(q, k, v, jnp.einsum, window=7)
                whole = ref.attention(q, k, v, jnp.einsum)
            finally:
                ref.QUERY_BLOCK = sound
            assert float(jnp.max(jnp.abs(got - masked(7)))) <= 1e-5
            assert float(jnp.max(jnp.abs(whole - masked(None)))) <= 1e-5
        assert float(jnp.max(jnp.abs(masked(7) - masked(None)))) > 0.1


def test_the_gate_weighs_each_heads_output():
    """With ``Wg`` zero every gate is a half, so the block's output halves
    against gates of one (``Wg`` large and positive on a positive input is
    not needed: the ratio to the ungated reference is what is read)."""
    params, _, _ = SEEDED
    cfg = config()
    x = jax.random.normal(KEY, (1, 32, 64))
    p = params["layers"][1]
    halves = dict(p, attn=dict(p["attn"], wg=jnp.zeros_like(p["attn"]["wg"])))
    with jax.default_matmul_precision("highest"):
        got = laguna._attention_block(halves, x, cfg, 1) - x
        u = ref.rms_norm(x, p["attn_norm"], 1e-6)
        mm = jnp.einsum
        rot = ref.rotary_of(SIZES, True)
        o = ref.attention(
            ref.turned(mm("btd,de->bte", u, p["attn"]["wq"]).reshape(
                1, 32, 6, 16), *rot),
            ref.turned(mm("btd,de->bte", u, p["attn"]["wk"]).reshape(
                1, 32, 2, 16), *rot),
            mm("btd,de->bte", u, p["attn"]["wv"]).reshape(1, 32, 2, 16), mm,
            16)
        ungated = o.reshape(1, 32, 96) @ p["attn"]["wo"]
    assert float(jnp.max(jnp.abs(got - 0.5 * ungated))) <= 1e-5 * float(
        jnp.max(jnp.abs(ungated)))


# ---------------------------------------------------------- the expert layer
@pytest.mark.parametrize("held", [1, 4])
def test_all_shares_parts_add_up_to_the_whole_layer(held):
    """The share test: over all shares (16 of one expert, 4 of four) the
    routed parts, with what every chip computes alike (the router,
    the shared expert) counted once, add up to the uncut reference's
    layer."""
    sizes = dict(SIZES, num_experts=16)
    whole = ref.init_weights(KEY, sizes)["layers"][1]["moe"]
    x = jax.random.normal(KEY, (40, 64))
    cfg = config().moe_cfg()
    with jax.default_matmul_precision("highest"):
        routed, shared = ref.expert_layer(whole, x, sizes, jnp.einsum)
        total, assignments = shared, 0
        for first in range(0, 16, held):
            share = dataclasses.replace(cfg, first_expert=first,
                                        experts_held=held)
            part = dict(whole, **{k: whole[k][first:first + held]
                                  for k in ("w1", "w2", "w3")})
            y, counts = moe.dropless_moe_ffn(x, part, share)
            total = total + (y - shared)
            assignments += int(counts.sum())
            mine, _ = ref.expert_layer(part, x, sizes, jnp.einsum,
                                       first_expert=first, held=held)
            assert float(jnp.max(jnp.abs(y - shared - mine))) <= 2e-5 * float(
                jnp.max(jnp.abs(routed)))
    assert assignments == 40 * 3
    assert float(jnp.max(jnp.abs(total - (routed + shared)))) <= 2e-5 * float(
        jnp.max(jnp.abs(routed)))


@pytest.mark.parametrize("routing", list(EXPERT_ROUTINGS))
def test_the_blocks_are_a_plain_loop_over_the_held_experts(routing):
    """This family's form of the layer (sigmoid scoring, SwiGLU experts, an
    ungated shared expert, no latent, the weights scaled) block by block
    is a plain loop over the held experts, values and gradients, under
    the four routings of ``family.expert_blocks_case``."""
    expert_blocks_case(moe.DroplessMoEConfig(
        d_model=32, d_ff=24, n_experts=64, top_k=4, first_expert=8,
        experts_held=4, d_shared=16, scoring="sigmoid", routed_scale=2.5,
        shared_gate=False), routing)


def test_the_chosen_weigh_by_their_score_over_its_sum_times_the_scale():
    params, _, _ = SEEDED
    x = jax.random.normal(KEY, (24, 64))
    p = params["layers"][2]["moe"]
    with jax.default_matmul_precision("highest"):
        ids, weights = moe.dropless_route(x, p["router"], config().moe_cfg(),
                                          p["router_bias"])
        want_ids, want = ref.route(p, x, SIZES, jnp.einsum)
    assert (np.asarray(ids) == np.asarray(want_ids)).all()
    np.testing.assert_allclose(weights, want, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(weights).sum(axis=-1), 2.5,
                               rtol=1e-5)
    scores = np.asarray(jax.nn.sigmoid(x @ p["router"]))
    assert (np.sort(np.asarray(ids), axis=-1)
            == np.sort(np.argsort(-scores, axis=-1)[:, :3], axis=-1)).all()


# ------------------------------------------------------------ the whole model
@pytest.mark.parametrize("use_flash", [False, True])
def test_logits_loss_and_gradients_are_the_references(use_flash):
    """Five layers in float32 on seeded weights (the reference's own draw:
    norm weights away from one): logits, the loss and every leaf's
    gradient; with the Pallas flash kernels interpreted at 2 and 3 query
    heads a key head, the sliding layers' with their window."""
    params, toks, tgts = SEEDED
    want, (l1, g1) = SEEDED.logits, SEEDED.loss_and_grads
    cfg = config(use_flash=use_flash)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p: laguna.forward(p, toks, cfg))(params)
        l2, g2 = jax.jit(jax.value_and_grad(
            lambda p: laguna.loss_fn(p, toks, tgts, cfg)))(params)
    assert got.shape == (2, 64, 256) and got.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(got - want))) <= LOGITS_TOL * float(
        jnp.max(jnp.abs(want)))
    assert abs(float(l1) - float(l2)) <= LOSS_TOL * abs(float(l1))
    assert jax.tree_util.tree_structure(g1) == jax.tree_util.tree_structure(g2)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(g2)[0],
                            jax.tree_util.tree_leaves(g1)):
        if "router_bias" in jax.tree_util.keystr(path):
            assert float(jnp.max(jnp.abs(a))) == 0.0     # a buffer
            continue
        assert worst_rel([a], [b]) <= GRAD_TOL, jax.tree_util.keystr(path)


def test_the_references_gradient_a_layer_a_call_is_its_whole_gradient():
    """``follow`` takes the gradient by ``gradient`` (a layer a jitted
    call, a program a shape of layer): the same numbers as ``jax.grad`` of
    the loss in one traced function."""
    params, toks, tgts = SEEDED
    want_l, want = SEEDED.loss_and_grads
    with jax.default_matmul_precision("highest"):
        loss, got = ref.gradient(ref._pieces(ref.scalars(SIZES), "float32"),
                                 params, toks, tgts, SIZES)
    assert abs(loss - float(want_l)) <= 1e-6 * float(want_l)
    assert worst_rel(got, want) <= 1e-5


@pytest.mark.parametrize("block", [48, 2048])
def test_the_head_in_blocks_is_the_head(monkeypatch, block):
    params, toks, tgts = SEEDED
    cfg = config()
    with jax.default_matmul_precision("highest"):
        logits = laguna.forward(params, toks, cfg)
        want = -jnp.mean(jnp.take_along_axis(
            jax.nn.log_softmax(logits), tgts[..., None], axis=-1))
        monkeypatch.setattr(laguna, "HEAD_TOKENS", block)
        got = laguna.loss_fn(params, toks, tgts, cfg)
    assert abs(float(got) - float(want)) <= 1e-6 * float(want)


# ----------------------------------------------------------- planted faults
def no_window(attend):
    return lambda q, k, v, causal, window=None: attend(q, k, v, causal=causal)


def no_gate(gate):
    return lambda u, wg: jnp.ones_like(gate(u, wg))


def whole_head_turned(rotary):
    return lambda x, rot: rotary(x, dataclasses.replace(
        rot, width=x.shape[-1]) if rot.kind == "yarn" else rot)


def no_attention_factor(rotary):
    return lambda x, rot: rotary(x, dataclasses.replace(
        rot, attention_factor=1.0))


def no_routed_scale(route):
    return lambda x, w, cfg, bias=None: route(
        x, w, dataclasses.replace(cfg, routed_scale=1.0), bias)


def not_renormalised(route):
    def broken(x, w, cfg, bias=None):
        ids, weights = route(x, w, cfg, bias)
        scores = jax.nn.sigmoid(x.astype(jnp.float32) @ w.astype(jnp.float32))
        return ids, cfg.routed_scale * jnp.take_along_axis(scores, ids, -1)
    return broken


@pytest.mark.parametrize("module, name, broken", [
    (laguna, "local_flash_attention", no_window),
    (laguna, "_head_gate", no_gate),
    (blocks, "rotary", whole_head_turned),
    (blocks, "rotary", no_attention_factor),
    (moe, "dropless_route", no_routed_scale),
    (moe, "dropless_route", not_renormalised),
], ids=["no-window", "no-gate", "whole-head-turned", "no-attention-factor",
        "no-routed-scale", "not-renormalised"])
def test_the_layers_wiring_is_what_the_reference_has(module, name, broken):
    """The window left off the sliding layers, the gate a head left out, a
    full layer turned over its whole head or without ``attention_factor``,
    the routed scale left out, the chosen not renormalised: each moves the
    logits far beyond the tolerance that the sound model keeps."""
    params, toks, _ = SEEDED
    program = lambda: jax.jit(lambda p: laguna.forward(
        p, toks, config()))(params)
    want = SEEDED.logits
    sound = SEEDED.kept("the sound program's logits", lambda *_: program())
    with planted(module, name, broken(getattr(module, name))), \
            jax.default_matmul_precision("highest"):
        got = program()
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(sound - want))) <= LOGITS_TOL * scale
    assert float(jnp.max(jnp.abs(got - want))) > 100 * LOGITS_TOL * scale


def test_an_expert_layer_in_layer_0s_place_is_another_model():
    """``mlp_layer_types`` decides: with layer 0 ``sparse`` the parameters
    have another layout and the reference's weights do not fit it."""
    cfg = dataclasses.replace(config(), mlp_layer_types=("sparse",) * 5)
    mine = jax.eval_shape(lambda k: laguna.init_params(cfg, k), KEY)
    theirs = jax.eval_shape(lambda k: ref.init_weights(k, SIZES), KEY)
    assert "moe" in mine["layers"][0] and "mlp" in theirs["layers"][0]
    assert jax.tree_util.tree_structure(mine) != \
        jax.tree_util.tree_structure(theirs)


# --------------------------------------------------------------- the counters
def test_expert_load_counts_what_lands_on_the_held_experts():
    params, toks, _ = SEEDED
    cfg = config()
    counts = jax.jit(lambda p: laguna.expert_load(p, toks, cfg))(params)
    assert counts.shape == (4, 4) and counts.dtype == jnp.int32
    # a quarter of the experts is held: about a quarter of 2 x 64 x 3
    assert (np.asarray(counts.sum(axis=1)) > 40).all()
    assert (np.asarray(counts.sum(axis=1)) < 160).all()
    # layer 1's, by the reference's own routing of the layer's input
    x = params["embed"][toks]
    x = laguna._mlp_block(params["layers"][0], laguna._attention_block(
        params["layers"][0], x, cfg, 0), cfg)
    x = laguna._attention_block(params["layers"][1], x, cfg, 1)
    u = ref.rms_norm(x, params["layers"][1]["mlp_norm"], 1e-6)
    ids, _ = ref.route(params["layers"][1]["moe"], u.reshape(-1, 64), SIZES,
                       jnp.einsum)
    assert [int((ids == e).sum()) for e in range(4)] == [
        int(c) for c in counts[0]]


@pytest.mark.parametrize("use_flash, layer, taken", [
    (False, 0, "full_plain"), (False, 1, "window_plain"),
    (True, 4, "full_flash"), (True, 3, "window_flash"),
])
def test_the_counter_says_which_path_a_layer_kind_took(use_flash, layer,
                                                       taken):
    params, _, _ = SEEDED
    before = dict(trace.attention)
    laguna._attention_block(params["layers"][layer], jnp.ones((1, 16, 64)),
                            config(use_flash=use_flash), layer)
    assert {k: trace.attention[k] - n for k, n in before.items()} == {
        k: int(k == taken) for k in before}


def test_the_counter_is_a_registered_series():
    names = {f"hvd_attention_{k}_total" for k in trace.attention}
    assert names <= set(trace.core.SERIES)
    for name in names:
        kind, _, read, _ = trace.core.SERIES[name]
        assert kind == "counter" and read() >= 0


# ------------------------------------------------------------ optimizer steps
def test_three_optimizer_steps_are_the_references():
    """The system against the reference over three Adam steps from the
    seeded weights: each step's loss, the first gradient's norms and the
    parameters' change, leaf by leaf (what ``compare.py`` is given)."""
    from benchmark import compare
    from benchmark.reference.common import leaf_norms
    reference = ref.follow(SHALLOW, KEY, 1, 3)
    params = ref.init_weights(KEY, SHALLOW)
    toks, tgts = ref.make_batch(KEY, SHALLOW, 0)
    adam = ref.ADAM
    opt = optax.adam(adam["lr"], b1=adam["b1"], b2=adam["b2"],
                     eps=adam["eps"])
    step = jax.jit(laguna.make_train_step(config(SHALLOW), opt))
    state, p, losses = opt.init(params), params, []
    with jax.default_matmul_precision("highest"):
        for i in range(3):
            p, state, loss = step(p, state, toks, tgts)
            losses.append(float(loss))
            if i == 0:
                grads = leaf_norms(jax.tree_util.tree_map(
                    lambda m: m / (1 - adam["b1"]), state[0].mu))
    assert set(grads) == set(reference["grad_norms"])
    for got, want in zip(losses, reference["losses"][0]):
        assert abs(got - want) <= 1e-5 * want
    assert losses[2] < losses[0]
    assert compare.norm_gap(grads, reference["grad_norms"])[0] <= 2e-4
    assert compare.norm_gap(leaf_norms(p, minus=params),
                            reference["delta_norms"])[0] <= 2e-3


def test_two_ranks_and_two_sequences_are_averaged_by_the_reference():
    """``follow`` at world 2 with two sequences a rank: the first gradient
    is the mean over the four sequences' gradients."""
    from benchmark.reference.common import leaf_norms
    reference = ref.follow(SHALLOW, KEY, 2, 1)
    assert len(reference["losses"]) == 2
    params = ref.init_weights(KEY, SHALLOW)
    batches = [ref.make_batch(KEY, SHALLOW, r) for r in range(2)]
    toks, tgts = (jnp.concatenate(x) for x in zip(*batches))
    with jax.default_matmul_precision("highest"):
        want = leaf_norms(jax.jit(jax.grad(
            lambda p: ref.loss_fn(p, toks, tgts, SHALLOW)))(params))
    for leaf, norm in want.items():
        assert abs(reference["grad_norms"][leaf] - norm) <= 1e-4 * max(
            norm, 1e-6), leaf


def test_the_train_step_under_shard_map_is_the_unsharded_step():
    """``make_train_step`` under ``shard_map`` over ``hvd.mesh()`` (8 CPU
    ranks, a sequence each) with the in-graph ``DistributedOptimizer``
    gives the parameters and the mean loss of the plain optax step on the
    whole batch."""
    hvd.init()
    mesh = hvd.mesh()
    sizes = dict(SHALLOW, batch_per_chip=1, seq_len=48)
    cfg = config(SHALLOW)
    params = ref.init_weights(KEY, sizes)
    toks, tgts = (jnp.concatenate(x) for x in zip(*(
        ref.make_batch(KEY, sizes, r) for r in range(mesh.size))))
    inner = optax.sgd(0.1)
    dist = hvd.DistributedOptimizer(optax.sgd(0.1), op=hvd.Average,
                                    axis_name="hvd")
    step = laguna.make_train_step(cfg, dist)

    def with_every_loss(p, state, t, y):
        p, state, loss = step(p, state, t, y)
        return p, state, loss[None]

    sharded = jax.jit(shard_map(
        with_every_loss, mesh=mesh, in_specs=(P(), P(), P("hvd"), P("hvd")),
        out_specs=(P(), P(), P("hvd")), check_vma=False))
    whole = jax.jit(laguna.make_train_step(cfg, inner))
    with jax.default_matmul_precision("highest"):
        p1, _, losses = sharded(params, dist.init(params), toks, tgts)
        p2, _, loss = whole(params, inner.init(params), toks, tgts)
    assert losses.shape == (mesh.size,) and len(set(np.asarray(losses))) > 1
    assert abs(float(jnp.mean(losses)) - float(loss)) <= 1e-5 * float(loss)
    moved = jax.tree_util.tree_map(lambda a, b: a - b, p1, params)
    want = jax.tree_util.tree_map(lambda a, b: a - b, p2, params)
    assert worst_rel(moved, want) <= 1e-3


def test_bfloat16_in_float32s_place_fails_the_tolerances():
    """The tolerances above are tight enough to tell a precision: the
    program at bfloat16 weights and activations against the float32
    reference is far outside the loss's and the gradients'."""
    params, toks, tgts = SEEDED
    low = jax.tree_util.tree_map(lambda w: w.astype(jnp.bfloat16), params)
    cfg = config(dtype="bfloat16")
    l1, g1 = SEEDED.loss_and_grads
    with jax.default_matmul_precision("highest"):
        l2, g2 = jax.jit(jax.value_and_grad(
            lambda p: laguna.loss_fn(p, toks, tgts, cfg)))(low)
    g2 = jax.tree_util.tree_map(lambda g: g.astype(jnp.float32), g2)
    assert abs(float(l1) - float(l2)) > 10 * LOSS_TOL * abs(float(l1))
    assert worst_rel(g2, g1) > 10 * GRAD_TOL
