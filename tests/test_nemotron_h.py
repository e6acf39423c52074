"""The Nemotron-H model path at test size on the CPU: the chunked
state-space recurrence against the token-by-token recurrence (the
benchmark's float32 reference, which shares no code with the program), the
grouped ``B``/``C``, the gated group norm, the convolution's bias, sigmoid
routing with a selection bias, ``relu^2`` experts in a latent, the sorted
assignments taken in blocks, the share test, the whole model's logits, loss
and gradients against that reference, three optimizer steps, and the train
step under ``shard_map`` with the in-graph ``DistributedOptimizer``."""

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
from benchmark.reference import nemotron_h as ref           # noqa: E402
from family import (EXPERT_ROUTINGS, Seeded,                 # noqa: E402
                    expert_blocks_case, planted, worst_rel)
from horovod_tpu.compat import shard_map                    # noqa: E402
from horovod_tpu.models import (blocks, gated_delta, mamba2, moe,  # noqa: E402
                                nemotron_h)

# one period (MEMEMEM*EME); the configuration file's ``tiny`` preset
SIZES = dict(hidden_size=64, num_hidden_layers=11,
             hybrid_override_pattern=nemotron_h.PUBLISHED_PATTERN,
             mamba_num_heads=8, mamba_head_dim=8, n_groups=2,
             ssm_state_size=16, conv_kernel=4, chunk_size=32,
             time_step_min=0.001, time_step_max=0.1, time_step_floor=1e-4,
             num_attention_heads=4, num_key_value_heads=2, head_dim=16,
             n_routed_experts=4, num_experts_published=16, first_expert=0,
             num_experts_per_tok=3, routed_scaling_factor=5,
             moe_latent_size=32, moe_intermediate_size=48,
             moe_shared_expert_intermediate_size=96, vocab_size=256,
             norm_eps=1e-5, dtype="float32", batch_per_chip=2, seq_len=100)
KEY = jax.random.PRNGKey(5)
# float32 against float32: reassociation only
LOGITS_TOL, LOSS_TOL, GRAD_TOL = 2e-4, 1e-5, 5e-4


SEEDED = Seeded(ref, SIZES, KEY)


def with_a_large_selection_bias(params):
    """A selection bias large enough that weighing by it shows."""
    for p in params["layers"]:
        if "moe" in p:
            p["moe"]["router_bias"] = 30.0 * p["moe"]["router_bias"]
    return params


BIASED = Seeded(ref, SIZES, KEY, change=with_a_large_selection_bias)
# Where depth is not what a test asserts (Adam's wiring leaf by leaf, the
# gradient exchange, the mean over ranks), one layer of each kind:
# a step's compile time follows its layers.
SHALLOW = dict(SIZES, hybrid_override_pattern="ME*", num_hidden_layers=3)


# -------------------------------------------------- the chunked recurrence
def scan_inputs(t, heads=4, groups=2, p=8, n=16, seed=0):
    """Head 0 keeps 0.999 a token (its state crosses every chunk), head 1
    keeps 0.5 (it forgets inside a chunk); the others are drawn between."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (2, t, heads, p))
    B = jax.random.normal(ks[1], (2, t, groups, n)) / np.sqrt(n)
    C = jax.random.normal(ks[2], (2, t, groups, n))
    delta = jnp.exp(jax.random.uniform(ks[3], (2, t, heads), jnp.float32,
                                       np.log(0.01), np.log(1.0)))
    keep = jnp.concatenate([
        jnp.asarray([0.999, 0.5]),
        jax.random.uniform(ks[4], (heads - 2,), jnp.float32, 0.8, 0.99)])
    log_a = jnp.log(keep) * jnp.ones((2, t, heads)) * (
        0.5 + jax.random.uniform(ks[4], (2, t, heads)))
    return x, delta, log_a, B, C


def token_by_token(x, delta, log_a, B, C):
    """The reference's recurrence, a group of heads at a time."""
    groups, rep = B.shape[2], x.shape[2] // B.shape[2]
    return jnp.concatenate([ref.recurrence(
        x[:, :, g * rep:(g + 1) * rep], delta[:, :, g * rep:(g + 1) * rep],
        log_a[:, :, g * rep:(g + 1) * rep], B[:, :, g], C[:, :, g])
        for g in range(groups)], axis=2)


@pytest.mark.parametrize("t", [128, 100, 32, 7])
def test_the_chunked_form_is_the_token_by_token_recurrence(t):
    """Values, at lengths that are (128, 32) and are not (100, 7) a
    multiple of the chunk of 32, with a head whose decay crosses every
    chunk and one that forgets inside a chunk."""
    inputs = scan_inputs(t)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(token_by_token)(*inputs)
        got = jax.jit(lambda *a: mamba2.chunked_ssd(*a, chunk=32))(*inputs)
    assert got.shape == want.shape == (2, t, 4, 8)
    assert float(jnp.max(jnp.abs(got - want))) <= 2e-5 * float(
        jnp.max(jnp.abs(want)))
    # the slow head's state is alive at the end, the fast head's is not
    decay = jnp.exp(jnp.sum(inputs[2][0], axis=0))
    if t >= 100:
        assert float(decay[0]) > 0.8 and float(decay[1]) < 1e-12


@pytest.mark.parametrize("t", [128, 100])
def test_the_chunked_forms_gradients_are_the_recurrences(t):
    inputs = scan_inputs(t, seed=1)
    weigh = jax.random.normal(jax.random.PRNGKey(9), (2, t, 4, 8))
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.grad(
            lambda *a: jnp.sum(token_by_token(*a) * weigh),
            argnums=range(5)))(*inputs)
        got = jax.jit(jax.grad(lambda *a: jnp.sum(
            mamba2.chunked_ssd(*a, chunk=32) * weigh),
            argnums=range(5)))(*inputs)
    assert worst_rel(got, want) <= 1e-4


def test_a_head_reads_its_own_groups_b_and_c():
    """Head ``h`` reads group ``h // (H / G)``: another group's ``B`` and
    ``C`` changed, a head's output stays."""
    x, delta, log_a, B, C = scan_inputs(64)
    base = mamba2.chunked_ssd(x, delta, log_a, B, C, 32)
    other = mamba2.chunked_ssd(x, delta, log_a, B.at[:, :, 1].mul(2.0),
                               C.at[:, :, 1].add(1.0), 32)
    assert jnp.array_equal(base[:, :, :2], other[:, :, :2])
    assert float(jnp.max(jnp.abs(base[:, :, 2:] - other[:, :, 2:]))) > 0.1


@pytest.mark.parametrize("token_heads, parts", [(2 * 100 * 2, 2),
                                                (2 * 100 * 4, 1), (1, 2)])
def test_a_group_at_a_time_is_all_groups_at_once(token_heads, parts):
    """``by_state_groups``: values and gradients of the recurrence run one
    group (two heads) at a time are those of both at once; a budget under
    one group still takes one."""
    inputs = scan_inputs(100, seed=2)
    grouped = mamba2.by_state_groups(mamba2.chunked_ssd, token_heads)
    loss = lambda scan: lambda *a: jnp.sum(jnp.sin(scan(*a, 32)))
    with jax.default_matmul_precision("highest"):
        want, g_want = jax.jit(jax.value_and_grad(
            loss(mamba2.chunked_ssd), argnums=range(5)))(*inputs)
        got, g_got = jax.jit(jax.value_and_grad(
            loss(grouped), argnums=range(5)))(*inputs)
    text = jax.make_jaxpr(lambda *a: grouped(*a, 32))(*inputs).pretty_print()
    assert ("scan" in text.split("cumsum")[0]) == (parts > 1)
    assert abs(float(got) - float(want)) <= 1e-5 * abs(float(want))
    assert worst_rel(g_got, g_want) <= 1e-5


def test_the_gated_norm_takes_its_mean_square_a_group():
    """Gate first, then the norm over each of 2 groups of 32 channels:
    against a loop over the groups, and far from the norm over all 64."""
    ks = jax.random.split(KEY, 3)
    y = jax.random.normal(ks[0], (2, 5, 64)) * jnp.repeat(
        jnp.asarray([1.0, 10.0]), 32)
    z, w = jax.random.normal(ks[1], (2, 5, 64)), 0.5 + jax.random.uniform(
        ks[2], (64,))
    got = mamba2.gated_group_norm(y, z, w, 2, 1e-5)
    gated = y * jax.nn.silu(z)
    want = jnp.concatenate([
        gated[..., g * 32:(g + 1) * 32] / jnp.sqrt(jnp.mean(jnp.square(
            gated[..., g * 32:(g + 1) * 32]), axis=-1, keepdims=True) + 1e-5)
        for g in range(2)], axis=-1) * w
    assert float(jnp.max(jnp.abs(got - want))) <= 1e-5
    whole = mamba2.gated_group_norm(y, z, w, 1, 1e-5)
    assert float(jnp.max(jnp.abs(whole - want))) > 0.5


def test_the_convolution_is_causal_depthwise_and_takes_a_bias():
    ks = jax.random.split(KEY, 3)
    x = jax.random.normal(ks[0], (2, 9, 6))
    kernel, bias = jax.random.normal(ks[1], (4, 6)), jax.random.normal(
        ks[2], (6,))
    want = np.zeros((2, 9, 6), np.float32)
    for t in range(9):
        for j in range(4):
            if t - 3 + j >= 0:
                want[:, t] += np.asarray(kernel[j]) * np.asarray(
                    x[:, t - 3 + j])
    got = gated_delta.causal_conv_silu(x, kernel, bias)
    assert np.allclose(got, jax.nn.silu(want + np.asarray(bias)), atol=1e-5)
    assert np.allclose(gated_delta.causal_conv_silu(x, kernel),
                       jax.nn.silu(want), atol=1e-5)


def test_the_mixer_is_the_references_layer():
    """``mamba2.mamba2`` against the reference's layer (groups of columns,
    the token-by-token recurrence) on the reference's own draw."""
    params, _, _ = SEEDED
    p = params["layers"][0]["ssm"]
    u = jax.random.normal(KEY, (2, 100, 64))
    cfg = nemotron_h.tiny()
    q = ref.quantizer("float32")
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, u: ref.mamba2(
            p, u, SIZES, ref._matmul(q), q))(p, u)
        got = jax.jit(lambda u, p: mamba2.mamba2(u, p, cfg.ssm_dims()))(u, p)
    assert float(jnp.max(jnp.abs(got - want))) <= 1e-4 * float(
        jnp.max(jnp.abs(want)))


# ------------------------------------------------------- the expert layer
LATENT = moe.DroplessMoEConfig(
    d_model=32, d_ff=24, n_experts=16, top_k=3, d_shared=40,
    scoring="sigmoid", routed_scale=5.0, expert_form="relu2", d_latent=16,
    shared_gate=False)
LATENT_SIZES = dict(num_experts_published=16, n_routed_experts=16,
                    first_expert=0, num_experts_per_tok=3,
                    routed_scaling_factor=5.0)


def latent_layer(cfg=LATENT, tokens=40, bias=0.3):
    params = moe.dropless_init_params(cfg, KEY)
    params["router_bias"] = bias * jax.random.normal(
        jax.random.PRNGKey(3), (cfg.n_experts,))
    return params, jax.random.normal(jax.random.PRNGKey(4),
                                     (tokens, cfg.d_model))


def test_sigmoid_routing_chooses_by_score_plus_bias_and_weighs_by_score():
    params, x = latent_layer(bias=1.0)
    ids, weights = moe.dropless_route(x, params["router"], LATENT,
                                      params["router_bias"])
    scores = jax.nn.sigmoid(x @ params["router"])
    by_biased = jnp.argsort(-(scores + params["router_bias"]), axis=-1)[:, :3]
    by_score = jnp.argsort(-scores, axis=-1)[:, :3]
    assert jnp.array_equal(jnp.sort(ids, -1), jnp.sort(by_biased, -1))
    assert not jnp.array_equal(jnp.sort(ids, -1), jnp.sort(by_score, -1))
    chosen = jnp.take_along_axis(scores, ids, axis=-1)
    assert np.allclose(weights, 5.0 * chosen / chosen.sum(-1, keepdims=True),
                       rtol=1e-5)
    assert np.allclose(weights.sum(-1), 5.0, rtol=1e-5)
    # no gradient reaches the bias: it chooses and does not weigh
    g = jax.grad(lambda b: jnp.sum(moe.dropless_route(
        x, params["router"], LATENT, b)[1] ** 2))(params["router_bias"])
    assert not np.asarray(g).any()


def test_softmax_routing_is_what_it_was():
    cfg = moe.DroplessMoEConfig(d_model=32, n_experts=16, top_k=3)
    params, x = latent_layer()
    ids, weights = moe.dropless_route(x, params["router"], cfg)
    probs = jax.nn.softmax(x @ params["router"], axis=-1)
    top, want = jax.lax.top_k(probs, 3)
    assert jnp.array_equal(ids, want)
    assert np.allclose(weights, top / top.sum(-1, keepdims=True), rtol=1e-5)


@pytest.mark.parametrize("field, value", [("scoring", "tanh"),
                                          ("expert_form", "gelu")])
def test_an_unknown_form_is_refused(field, value):
    with pytest.raises(ValueError, match=field):
        moe.DroplessMoEConfig(**{field: value})


def test_the_latent_layers_parameters():
    """Two matrices an expert, in the latent; a selection bias; the two
    projections; a shared expert of two matrices and no gate."""
    params, _ = latent_layer()
    assert {k: v.shape for k, v in params.items()} == {
        "router": (32, 16), "router_bias": (16,), "w_down": (32, 16),
        "w_up": (16, 32), "w1": (16, 16, 24), "w2": (16, 24, 16),
        "shared_w1": (32, 40), "shared_w2": (40, 32)}


def test_the_whole_latent_layer_is_the_references():
    params, x = latent_layer()
    with jax.default_matmul_precision("highest"):
        y, counts = moe.dropless_moe_ffn(x, params, LATENT)
        routed, shared = ref.expert_layer(params, x, LATENT_SIZES, jnp.einsum)
    assert int(counts.sum()) == 40 * 3
    assert float(jnp.max(jnp.abs(y - (routed + shared)))) <= 1e-5 * float(
        jnp.max(jnp.abs(y)))


@pytest.mark.parametrize("held", [1, 2, 4, 16])
def test_all_shares_parts_add_up_to_the_whole_layer(held):
    """The share test: over all shares the routed parts — each share's sum
    in the latent through ``W_up``, which is linear — with what every chip
    computes alike (the router, the two projections, the shared expert)
    counted once, add up to the uncut reference's layer."""
    params, x = latent_layer()
    with jax.default_matmul_precision("highest"):
        routed, shared = ref.expert_layer(params, x, LATENT_SIZES, jnp.einsum)
        total, assignments = shared, 0
        for first in range(0, 16, held):
            cfg = dataclasses.replace(LATENT, first_expert=first,
                                      experts_held=held)
            part = dict(params, w1=params["w1"][first:first + held],
                        w2=params["w2"][first:first + held])
            y, counts = moe.dropless_moe_ffn(x, part, cfg)
            total = total + (y - shared)
            assignments += int(counts.sum())
            # the share's own part is the reference's for that share
            mine, _ = ref.expert_layer(part, x, LATENT_SIZES, jnp.einsum,
                                       first_expert=first, held=held)
            assert float(jnp.max(jnp.abs(y - shared - mine))) <= 2e-5 * float(
                jnp.max(jnp.abs(routed)))
    assert assignments == 40 * 3
    assert float(jnp.max(jnp.abs(total - (routed + shared)))) <= 2e-5 * float(
        jnp.max(jnp.abs(routed)))


def test_blocks_are_taken_where_the_share_is_small():
    """A block is one and a half times what even routing sends here, among
    the divisors of the rows: one block where every expert is held, five
    at an eighth (``qwen3next-80b-a3b-4l``: 64 of 512, blocks of 32768),
    ten at a sixteenth (``laguna-s-2_1-5l``: 16 of 256), sixteen at a
    thirty-second (16 of 512 over 8192 x 22 rows: blocks of 11264)."""
    of = lambda held, rows: moe.dropless_blocks(rows, moe.DroplessMoEConfig(
        n_experts=512, top_k=2, experts_held=held))
    assert of(64, 16384 * 10) == 5 and of(512, 16384 * 10) == 1
    assert of(32, 16384 * 10) == 10
    sixteenth = moe.DroplessMoEConfig(n_experts=256, top_k=10,
                                      experts_held=16)
    assert moe.dropless_blocks(4096 * 10, sixteenth) == 10
    assert moe.dropless_blocks(16384 * 10, sixteenth) == 10
    assert of(16, 8192 * 22) == 16 and of(8, 8192 * 22) == 32
    assert of(16, 5 * 7 * 11) == 11       # the largest divisor up to 21


@pytest.mark.parametrize("bias_on_held, held_rows", [
    (0.7, (15, 10)),    # a row over one block: two live, the second all but
                        # empty
    (-50.0, (0, 0)),    # nothing routed here: no block walked
    (1.0, (65, 50)),    # five blocks live; both groups cross blocks' edges
    (50.0, (96, 96)),   # every token chooses both: eight of the sixteen
])
def test_blocks_give_what_one_block_gives(monkeypatch, bias_on_held,
                                          held_rows):
    """Values and gradients of the layer with the sorted assignments in
    sixteen blocks of 24 rows are those of one block, whatever the routing;
    a block past the last held row is not walked, in either pass."""
    cfg = dataclasses.replace(LATENT, n_experts=64, top_k=4, first_expert=8,
                              experts_held=2)
    params, x = latent_layer(cfg, tokens=96)
    params["router_bias"] = params["router_bias"].at[8:10].add(bias_on_held)
    assert moe.dropless_blocks(96 * 4, cfg) == 16

    def loss(p, x):
        y, counts = moe.dropless_moe_ffn(x, p, cfg)
        return jnp.sum(y * jnp.cos(y)), counts

    with jax.default_matmul_precision("highest"):
        (got, counts), g_got = jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True)(params, x)
        monkeypatch.setattr(moe, "BLOCK_OVER_EXPECTED", 10 ** 6)
        assert moe.dropless_blocks(96 * 4, cfg) == 1
        (want, _), g_want = jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True)(params, x)
    assert tuple(int(c) for c in counts) == held_rows
    assert abs(float(got) - float(want)) <= 1e-5 * abs(float(want))
    assert worst_rel(g_got, g_want) <= 1e-5
    if sum(held_rows):  # the held experts' matrices take a gradient
        assert float(jnp.max(jnp.abs(g_got[0]["w1"]))) > 0


@pytest.mark.parametrize("routing", list(EXPERT_ROUTINGS))
def test_the_blocks_are_a_plain_loop_over_the_held_experts(routing):
    """This family's form of the layer (sigmoid scoring with a selection
    bias, ``relu^2`` experts in a latent, an ungated shared expert) block
    by block is a plain loop over the held experts, values and gradients,
    under the four routings of ``family.expert_blocks_case``."""
    expert_blocks_case(dataclasses.replace(
        LATENT, n_experts=64, top_k=4, first_expert=8, experts_held=4),
        routing)


def test_the_gated_softmax_layer_in_blocks_is_one_blocks(monkeypatch):
    """``qwen3_next``'s form of expert (SwiGLU, a gated shared expert,
    softmax scoring, no latent) through the blocks too."""
    cfg = moe.DroplessMoEConfig(d_model=32, d_ff=24, n_experts=64, top_k=4,
                                first_expert=4, experts_held=2, d_shared=16)
    params = moe.dropless_init_params(cfg, KEY)
    x = jax.random.normal(jax.random.PRNGKey(4), (96, 32))
    loss = lambda p: jnp.sum(jnp.sin(moe.dropless_moe_ffn(x, p, cfg)[0]))
    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(loss)(params)
        monkeypatch.setattr(moe, "BLOCK_OVER_EXPECTED", 10 ** 6)
        want = jax.value_and_grad(loss)(params)
    assert worst_rel(got, want) <= 1e-5


# ----------------------------------------------------------- the whole model
def test_the_config_composes_its_blocks_from_the_pattern():
    cfg = nemotron_h.nemotron3_super_120b_a12b()
    assert cfg.n_layers == 88
    assert [cfg.count(k) for k in "ME*"] == [40, 40, 8]
    assert cfg.pattern[:11] == "MEMEMEM*EME" == nemotron_h.tiny().pattern
    with pytest.raises(ValueError, match="pattern"):
        nemotron_h.NemotronHConfig(pattern="MEX")
    params = nemotron_h.init_params(nemotron_h.tiny(), KEY)
    assert ["ssm" if "ssm" in p else "moe" if "moe" in p else "attn"
            for p in params["layers"]] == [
        nemotron_h.KINDS[c] for c in "MEMEMEM*EME"]


def test_init_params_has_the_references_layout():
    mine = nemotron_h.init_params(nemotron_h.tiny(), KEY)
    theirs = ref.init_weights(KEY, SIZES)
    assert jax.tree_util.tree_structure(mine) == \
        jax.tree_util.tree_structure(theirs)
    for x, y in zip(jax.tree_util.tree_leaves(mine),
                    jax.tree_util.tree_leaves(theirs)):
        assert x.shape == y.shape and x.dtype == y.dtype


def test_the_published_sizes_count_120b_parameters():
    shapes = jax.eval_shape(lambda k: nemotron_h.init_params(
        nemotron_h.nemotron3_super_120b_a12b(), k), KEY)
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    ssm = 4096 * 18560 + 5 * 10240 + 3 * 128 + 8192 + 8192 * 4096
    attn = 2 * 4096 * 4096 + 2 * 4096 * 256
    experts = (4096 * 512 + 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376
               + 512 * 2 * 1024 * 2688)
    assert n == (40 * ssm + 8 * attn + 40 * experts + 88 * 4096
                 + 2 * 131072 * 4096 + 4096)
    assert 1.19e11 < n < 1.22e11


@pytest.mark.parametrize("use_flash, token_heads", [
    (False, 1 << 17), (True, 1 << 17), (False, 2 * 100 * 4)])
def test_logits_loss_and_gradients_are_the_references(use_flash, token_heads):
    """One period in float32 on seeded weights (the reference's own draw:
    norm weights away from one, the published decays, a selection bias),
    3.1 chunks a sequence; with the Pallas flash kernel interpreted at 4
    query heads on 2 key heads, and with the recurrence one group at a
    time."""
    params, toks, tgts = SEEDED
    want, (l1, g1) = SEEDED.logits, SEEDED.loss_and_grads
    cfg = nemotron_h.tiny(use_flash=use_flash)
    with planted(nemotron_h, "SCAN_TOKEN_HEADS", token_heads), \
            jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p: nemotron_h.forward(p, toks, cfg))(params)
        l2, g2 = jax.jit(jax.value_and_grad(
            lambda p: nemotron_h.loss_fn(p, toks, tgts, cfg)))(params)
    assert got.shape == (2, 100, 256) and got.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(got - want))) <= LOGITS_TOL * float(
        jnp.max(jnp.abs(want)))
    assert abs(float(l1) - float(l2)) <= LOSS_TOL * abs(float(l1))
    assert jax.tree_util.tree_structure(g1) == jax.tree_util.tree_structure(g2)
    assert worst_rel(g2, g1) <= GRAD_TOL
    # the selection bias takes no gradient, in either
    assert not np.asarray(g2["layers"][1]["moe"]["router_bias"]).any()


def no_skip(mixer):
    def bad(u, p, dims, scan=None):
        return mixer(u, dict(p, D=jnp.zeros_like(p["D"])), dims, scan)
    return bad


def norm_over_all_channels(_norm):
    return lambda y, z, w, groups, eps: _norm(y, z, w, 1, eps)


def weights_with_the_bias(route):
    def bad(x, router_w, cfg, bias=None):
        ids, _ = route(x, router_w, cfg, bias)
        scores = jax.nn.sigmoid(x.astype(jnp.float32) @ router_w) + bias
        top = jnp.take_along_axis(scores, ids, axis=-1)
        return ids, cfg.routed_scale * top / top.sum(-1, keepdims=True)
    return bad


@pytest.mark.parametrize("module, name, broken", [
    (mamba2, "mamba2", no_skip),
    (mamba2, "gated_group_norm", norm_over_all_channels),
    (moe, "dropless_route", weights_with_the_bias),
    (blocks, "local_flash_attention", None),
])
def test_the_layers_wiring_is_what_the_reference_has(module, name, broken):
    """The skip ``D x`` left out, the gated norm over all channels instead
    of a group's, the router's weights taken from ``s + b``, a rotary
    applied: each moves the logits far beyond the tolerance that the sound
    model keeps."""
    from horovod_tpu.models import qwen3_next
    params, toks, _ = BIASED
    if broken is None:
        attend = module.local_flash_attention
        turn = lambda y: qwen3_next._partial_rope(y, y.shape[-1], 1e4)
        broken = lambda _: (lambda q, k, v, causal: attend(
            turn(q), turn(k), v, causal=causal))
    program = lambda: jax.jit(lambda p: nemotron_h.forward(
        p, toks, nemotron_h.tiny()))(params)
    want = BIASED.logits
    sound = BIASED.kept("the sound program's logits", lambda *_: program())
    with planted(module, name, broken(getattr(module, name))), \
            jax.default_matmul_precision("highest"):
        got = program()
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(sound - want))) <= LOGITS_TOL * scale
    assert float(jnp.max(jnp.abs(got - want))) > 100 * LOGITS_TOL * scale


def test_the_counters_read_the_held_load_and_the_decays():
    params, toks, _ = SEEDED
    cfg = nemotron_h.tiny()
    load = jax.jit(lambda p: nemotron_h.expert_load(p, toks, cfg))(params)
    assert load.shape == (5, 4) and load.dtype == jnp.int32
    assert (np.asarray(load.sum(axis=1)) <= 2 * 100 * 3).all()
    assert 0.15 < float(load.sum()) / (5 * 2 * 100 * 3) < 0.35     # 4 of 16
    share, least, most = jax.jit(
        lambda p: nemotron_h.decay_stats(p, toks, cfg))(params)
    assert share.shape == least.shape == most.shape == (5,)
    # the published draw: some heads keep their state across a chunk of 32,
    # some forget inside it
    assert (np.asarray(share) > 0.3).all() and (np.asarray(share) < 1).all()
    assert (np.asarray(least) < 0.5).all() and (np.asarray(most) > 0.99).all()
    # against the first layer's decays worked out by hand
    p = params["layers"][0]
    u = nemotron_h._rmsnorm(params["embed"][toks], p["norm"], 1e-5)
    dt = u @ p["ssm"]["w_in"][:, -8:]
    log_a = -jax.nn.softplus(dt + p["ssm"]["dt_bias"]) * jnp.exp(
        p["ssm"]["A_log"])
    whole = jnp.pad(log_a, ((0, 0), (0, 28), (0, 0))).reshape(
        2, 4, 32, 8).sum(axis=2)
    assert abs(float(share[0]) - float(jnp.mean(whole > np.log(0.01)))) < 1e-6
    assert abs(float(most[0]) - float(jnp.exp(log_a.max()))) < 1e-6


def test_three_optimizer_steps_are_the_references():
    """The system against the reference over three Adam steps from the
    seeded weights: each step's loss, the first gradient's norms and the
    parameters' change, leaf by leaf (what ``compare.py`` is given)."""
    from benchmark import compare
    from benchmark.reference.common import leaf_norms
    sizes = dict(SHALLOW, batch_per_chip=1)
    reference = ref.follow(sizes, KEY, 1, 3)
    params = ref.init_weights(KEY, sizes)
    toks, tgts = ref.make_batch(KEY, sizes, 0)
    adam = ref.ADAM
    opt = optax.adam(adam["lr"], b1=adam["b1"], b2=adam["b2"],
                     eps=adam["eps"])
    step = jax.jit(nemotron_h.make_train_step(
        nemotron_h.tiny(pattern="ME*"), opt))
    state, p, losses = opt.init(params), params, []
    with jax.default_matmul_precision("highest"):
        for i in range(3):
            p, state, loss = step(p, state, toks, tgts)
            losses.append(float(loss))
            if i == 0:
                grads = leaf_norms(jax.tree_util.tree_map(
                    lambda m: m / (1 - adam["b1"]), state[0].mu))
    for got, want in zip(losses, reference["losses"][0]):
        assert abs(got - want) <= 1e-5 * want
    assert losses[2] < losses[0]
    assert compare.norm_gap(grads, reference["grad_norms"])[0] <= 2e-4
    assert compare.norm_gap(leaf_norms(p, minus=params),
                            reference["delta_norms"])[0] <= 2e-3


def test_the_train_step_under_shard_map_is_the_unsharded_step():
    """``make_train_step`` under ``shard_map`` over ``hvd.mesh()`` (8 CPU
    ranks, a sequence each) with the in-graph ``DistributedOptimizer``
    gives the parameters and the mean loss of the plain optax step on the
    whole batch."""
    hvd.init()
    mesh = hvd.mesh()
    sizes = dict(SHALLOW, batch_per_chip=1, seq_len=64)
    cfg = nemotron_h.tiny(pattern="ME*")
    params = ref.init_weights(KEY, sizes)
    toks, tgts = (jnp.concatenate(x) for x in zip(*(
        ref.make_batch(KEY, sizes, r) for r in range(mesh.size))))
    inner = optax.sgd(0.1)
    dist = hvd.DistributedOptimizer(optax.sgd(0.1), op=hvd.Average,
                                    axis_name="hvd")
    step = nemotron_h.make_train_step(cfg, dist)

    def with_every_loss(p, state, t, y):
        p, state, loss = step(p, state, t, y)
        return p, state, loss[None]

    sharded = jax.jit(shard_map(
        with_every_loss, mesh=mesh, in_specs=(P(), P(), P("hvd"), P("hvd")),
        out_specs=(P(), P(), P("hvd")), check_vma=False))
    whole = jax.jit(nemotron_h.make_train_step(cfg, inner))
    with jax.default_matmul_precision("highest"):
        p1, _, losses = sharded(params, dist.init(params), toks, tgts)
        p2, _, loss = whole(params, inner.init(params), toks, tgts)
    assert losses.shape == (mesh.size,) and len(set(np.asarray(losses))) > 1
    assert abs(float(jnp.mean(losses)) - float(loss)) <= 1e-5 * float(loss)
    moved = jax.tree_util.tree_map(lambda a, b: a - b, p1, params)
    want = jax.tree_util.tree_map(lambda a, b: a - b, p2, params)
    assert worst_rel(moved, want) <= 1e-3
