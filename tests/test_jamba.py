"""The Jamba model path at test size on the CPU: the Mamba-1 mixer against
the benchmark's float32 reference (which shares no code with the program
and walks the recurrence token by token), the layer pattern from period and
offset, the tied head and its one gradient, attention at 4 query heads on
one key head without rotary, the whole model's logits, loss and every
leaf's gradient against that reference (where bfloat16 in float32's place
fails), the planted faults, the counter, three optimizer steps, and the
train step under ``shard_map`` with the in-graph ``DistributedOptimizer``."""

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
from benchmark.reference import jamba as ref                # noqa: E402
from family import Seeded, planted, worst_rel               # noqa: E402
from horovod_tpu.compat import shard_map                    # noqa: E402
from horovod_tpu.models import blocks, jamba, mamba         # noqa: E402

# one period of four (MM*M); the configuration file's ``tiny`` preset
SIZES = dict(hidden_size=64, intermediate_size=96, num_hidden_layers=4,
             attn_layer_period=4, attn_layer_offset=2, expert_layer_period=2,
             expert_layer_offset=1, num_experts=1, mamba_expand=2,
             mamba_d_state=16, mamba_d_conv=4, mamba_dt_rank=8,
             num_attention_heads=4, num_key_value_heads=1, head_dim=16,
             vocab_size=256, rms_norm_eps=1e-6, dtype="float32",
             batch_per_chip=2, seq_len=72)
KEY = jax.random.PRNGKey(43)
# float32 against float32 at 72 tokens: reassociation only (the program
# walks chunks of 64 tokens and sums the head's blocks, the reference neither).  bfloat16 in
# float32's place reads 100 times these: the last test of the file.
LOGITS_TOL, LOSS_TOL, GRAD_TOL = 2e-4, 1e-5, 5e-4


def config(sizes=SIZES, **kw):
    from benchmark.families import jamba as family
    return family.config_of({**sizes, "use_flash": False, **kw})


SEEDED = Seeded(ref, SIZES, KEY)
# Where depth is not what a test asserts (Adam's wiring leaf by leaf, the
# gradient exchange, the mean over ranks), a Mamba layer and an attention
# layer (M*): a step's compile time follows its layers.
SHALLOW = dict(SIZES, num_hidden_layers=2, attn_layer_period=2,
               attn_layer_offset=1)


# ------------------------------------------------------------------ the mixer
def test_the_mixer_is_the_references_layer():
    """``mamba`` on a seeded layer against ``reference/jamba.py``'s
    ``mamba``: the fused projection's column order, the convolution's bias,
    the three inner norms, the rank-8 step with its bias, a decay a
    (channel, state) pair, the skip and the gate."""
    from benchmark.reference.common import quantizer
    params, _, _ = SEEDED
    p = params["layers"][0]["ssm"]
    u = jax.random.normal(KEY, (2, 72, 64))
    q = quantizer("float32")
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, u: ref.mamba(
            p, u, SIZES, ref._matmul(q), q))(p, u)
        got = jax.jit(lambda u, p: mamba.mamba(u, p, config().ssm_dims()))(
            u, p)
    assert got.shape == (2, 72, 64)
    assert float(jnp.max(jnp.abs(got - want))) <= 1e-5 * float(
        jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("name", ["dt_norm", "b_norm", "c_norm"])
def test_each_inner_norms_weight_reaches_the_output(name):
    """The family's addition to Mamba-1 is always there: ``init_params``
    makes the three weights, and each scales what it norms."""
    dims = config().ssm_dims()
    p = mamba.init_params(dims, 64, jnp.float32,
                          iter(jax.random.split(KEY, 8)))
    assert {"dt_norm", "b_norm", "c_norm"} <= set(p)
    u = jax.random.normal(KEY, (1, 32, 64))
    scaled = dict(p, **{name: 2 * p[name]})
    assert float(jnp.max(jnp.abs(
        mamba.mamba(u, scaled, dims) - mamba.mamba(u, p, dims)))) > 1e-3


def test_init_params_is_the_published_draw():
    dims = config().ssm_dims()
    p = mamba.init_params(dims, 64, jnp.float32,
                          iter(jax.random.split(KEY, 8)))
    assert p["A_log"].shape == (128, 16)
    np.testing.assert_allclose(np.exp(p["A_log"][5]), np.arange(1, 17),
                               rtol=1e-6)
    assert (np.asarray(p["D"]) == 1).all()
    step = np.asarray(jax.nn.softplus(p["dt_bias"]))
    assert 1e-3 * 0.999 <= step.min() and step.max() <= 0.1 * 1.001


# ---------------------------------------------------------------- the pattern
@pytest.mark.parametrize("layers, period, offset, want", [
    (28, 14, 7, "MMMMMMM*MMMMMM" * 2),
    (14, 14, 7, "MMMMMMM*MMMMMM"),
    (8, 4, 2, "MM*MMM*M"),
    (6, 2, 0, "*M*M*M"),
    (5, 8, 3, "MMM*M"),
    (3, 1, 0, "***"),
])
def test_the_pattern_follows_period_and_offset(layers, period, offset, want):
    cfg = jamba.tiny(n_layers=layers, attn_layer_period=period,
                     attn_layer_offset=offset)
    assert cfg.pattern == want
    params = jax.eval_shape(lambda k: jamba.init_params(cfg, k), KEY)
    assert "".join("*" if "attn" in p else "M"
                   for p in params["layers"]) == want
    sizes = dict(SIZES, num_hidden_layers=layers, attn_layer_period=period,
                 attn_layer_offset=offset)
    assert "".join("*" if ref.is_attention(sizes, i) else "M"
                   for i in range(layers)) == want


@pytest.mark.parametrize("kw, match", [
    (dict(num_experts=16), "num_experts 16"),
    (dict(num_experts=2, expert_layer_period=4), "num_experts 2"),
    (dict(attn_layer_offset=4), "inside the period"),
])
def test_a_config_the_family_cannot_run_is_refused(kw, match):
    with pytest.raises(ValueError, match=match):
        jamba.tiny(**kw)


def test_the_expert_keys_are_read_and_one_expert_is_the_dense_mlp():
    cfg = config()
    assert (cfg.expert_layer_period, cfg.expert_layer_offset,
            cfg.num_experts) == (2, 1, 1)
    with pytest.raises(ValueError, match="num_experts 4"):
        config(num_experts=4)


def test_init_params_has_the_references_layout():
    cfg = config()
    mine = jax.eval_shape(lambda k: jamba.init_params(cfg, k), KEY)
    theirs = jax.eval_shape(lambda k: ref.init_weights(k, SIZES), KEY)
    assert jax.tree_util.tree_structure(mine) == \
        jax.tree_util.tree_structure(theirs)
    assert jax.tree_util.tree_leaves(mine) == jax.tree_util.tree_leaves(theirs)
    assert "lm_head" not in mine            # the head is the embedding


def test_the_published_sizes_count_3b_parameters():
    cfg = jamba.jamba2_3b()
    assert cfg.pattern.count("*") == 2 and cfg.pattern.index("*") == 7
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(
        jax.eval_shape(lambda k: jamba.init_params(cfg, k), KEY)))
    assert n == 26 * 104_161_472 + 2 * 76_682_240 + 167_772_160 + 2560
    assert 3.0e9 < n < 3.1e9


# ------------------------------------------------------------ the whole model
@pytest.mark.parametrize("use_flash", [False, True])
def test_logits_loss_and_gradients_are_the_references(use_flash):
    """One period in float32 on seeded weights (the reference's own draw:
    norm weights away from one, the published decays): logits, the loss and
    every leaf's gradient; with the Pallas flash kernel interpreted at 4
    query heads on one key head."""
    params, toks, tgts = SEEDED
    want, (l1, g1) = SEEDED.logits, SEEDED.loss_and_grads
    cfg = config(use_flash=use_flash)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p: jamba.forward(p, toks, cfg))(params)
        l2, g2 = jax.jit(jax.value_and_grad(
            lambda p: jamba.loss_fn(p, toks, tgts, cfg)))(params)
    assert got.shape == (2, 72, 256) and got.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(got - want))) <= LOGITS_TOL * float(
        jnp.max(jnp.abs(want)))
    assert abs(float(l1) - float(l2)) <= LOSS_TOL * abs(float(l1))
    assert jax.tree_util.tree_structure(g1) == jax.tree_util.tree_structure(g2)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(g2)[0],
                            jax.tree_util.tree_leaves(g1)):
        assert worst_rel([a], [b]) <= GRAD_TOL, jax.tree_util.keystr(path)


def test_the_references_gradient_a_layer_a_call_is_its_whole_gradient():
    """``follow`` takes the gradient by ``gradient`` (a layer a jitted
    call, the tied matrix's two parts summed in float32): the same numbers
    as ``jax.grad`` of the loss in one traced function."""
    params, toks, tgts = SEEDED
    want_l, want = SEEDED.loss_and_grads
    with jax.default_matmul_precision("highest"):
        loss, got = ref.gradient(ref._pieces(ref.scalars(SIZES), "float32"),
                                 params, toks, tgts)
    assert abs(loss - float(want_l)) <= 1e-6 * float(want_l)
    assert worst_rel(got, want) <= 1e-5


def test_the_tied_gradient_is_the_sum_of_an_untied_pairs():
    """With the head given a matrix of its own (the same numbers), the
    embedding's gradient is the lookup's scatter and the head's the
    product; tied, the one leaf's gradient is their sum."""
    params, toks, tgts = SEEDED
    cfg = config()

    def untied(embed, head):
        x = jamba.hidden(dict(params, embed=embed), toks, cfg)
        logits = jamba._logits(dict(params, embed=head), x, cfg)
        return -jnp.mean(jnp.take_along_axis(
            jax.nn.log_softmax(logits), tgts[..., None], axis=-1))

    with jax.default_matmul_precision("highest"):
        of_lookup, of_head = jax.jit(jax.grad(untied, argnums=(0, 1)))(
            params["embed"], params["embed"])
        tied = jax.jit(jax.grad(
            lambda p: jamba.loss_fn(p, toks, tgts, cfg)))(params)["embed"]
    # the lookup touches the rows of the tokens that occur, the head all
    assert (np.abs(np.asarray(of_lookup)).sum(axis=1) > 0).sum() <= 144
    assert (np.abs(np.asarray(of_head)).sum(axis=1) > 0).all()
    assert worst_rel([tied], [of_lookup + of_head]) <= 1e-5
    assert worst_rel([tied], [of_head]) > 1e-2


@pytest.mark.parametrize("block", [16, 64, 72, 1024])
def test_the_head_in_blocks_is_the_head(monkeypatch, block):
    params, toks, tgts = SEEDED
    cfg = config()
    with jax.default_matmul_precision("highest"):
        logits = jamba.forward(params, toks, cfg)
        want = -jnp.mean(jnp.take_along_axis(
            jax.nn.log_softmax(logits), tgts[..., None], axis=-1))
        monkeypatch.setattr(jamba, "HEAD_TOKENS", block)
        got = jamba.loss_fn(params, toks, tgts, cfg)
    assert abs(float(got) - float(want)) <= 1e-6 * float(want)


# ----------------------------------------------------------- planted faults
def no_skip(mixer):
    return lambda u, p, dims: mixer(
        u, dict(p, D=jnp.zeros_like(p["D"])), dims)


def no_inner_norms(norm):
    return lambda x, w, eps: x


def no_step_bias(mixer):
    return lambda u, p, dims: mixer(
        u, dict(p, dt_bias=jnp.zeros_like(p["dt_bias"])), dims)


def one_decay_a_channel(mixer):
    return lambda u, p, dims: mixer(
        u, dict(p, A_log=jnp.broadcast_to(p["A_log"][:, :1],
                                          p["A_log"].shape)), dims)


@pytest.mark.parametrize("module, name, broken", [
    (mamba, "mamba", no_skip),
    (mamba, "_rmsnorm", no_inner_norms),
    (mamba, "mamba", no_step_bias),
    (mamba, "mamba", one_decay_a_channel),
    (blocks, "local_flash_attention", None),
], ids=["no-skip", "no-inner-norms", "no-step-bias", "one-decay-a-channel",
        "a-rotary"])
def test_the_layers_wiring_is_what_the_reference_has(module, name, broken):
    """The skip ``D x`` left out, the norms on ``dt_r``, ``B`` and ``C``
    left out, ``b_dt`` left out, ``A`` taken as one decay a channel, a
    rotary applied: each moves the logits far beyond the tolerance that
    the sound model keeps."""
    from horovod_tpu.models import qwen3_next
    params, toks, _ = SEEDED
    if broken is None:
        attend = module.local_flash_attention
        turn = lambda y: qwen3_next._partial_rope(y, y.shape[-1], 1e4)
        broken = lambda _: (lambda q, k, v, causal: attend(
            turn(q), turn(k), v, causal=causal))
    program = lambda: jax.jit(lambda p: jamba.forward(
        p, toks, config()))(params)
    want = SEEDED.logits
    sound = SEEDED.kept("the sound program's logits", lambda *_: program())
    with planted(module, name, broken(getattr(module, name))), \
            jax.default_matmul_precision("highest"):
        got = program()
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(sound - want))) <= LOGITS_TOL * scale
    assert float(jnp.max(jnp.abs(got - want))) > 100 * LOGITS_TOL * scale


# ---------------------------------------------------------------- the counter
def test_the_counter_reads_the_decays():
    params, toks, _ = SEEDED
    cfg = config()
    share, least, most = jax.jit(
        lambda p: jamba.decay_stats(p, toks, cfg))(params)
    assert share.shape == least.shape == most.shape == (3,)
    # the published draw: a few triples forget inside a token, most do not
    assert (np.asarray(share) > 0.03).all() and (np.asarray(share) < 0.3).all()
    assert (np.asarray(least) < 1e-3).all() and (np.asarray(most) > 0.1).all()
    # against the first layer's steps worked out by hand
    p = params["layers"][0]
    u = jamba._rmsnorm(params["embed"][toks], p["mixer_norm"], 1e-6)
    w = p["ssm"]
    from horovod_tpu.models.gated_delta import causal_conv_silu
    x = causal_conv_silu(u @ w["w_in"][:, :128], w["conv"], w["conv_bias"])
    dbc = x @ w["w_x"]
    norm = lambda y, g: y * jax.lax.rsqrt(
        jnp.mean(y * y, -1, keepdims=True) + 1e-6) * g
    delta = jax.nn.softplus(norm(dbc[..., :8], w["dt_norm"]) @ w["w_dt"]
                            + w["dt_bias"])
    decay = jnp.exp(-delta[..., None] * jnp.exp(w["A_log"]))
    assert abs(float(share[0]) - float(jnp.mean(decay < 0.5))) < 1e-5
    assert abs(float(most[0]) - float(delta.max())) < 1e-5


# ------------------------------------------------------------ optimizer steps
def test_three_optimizer_steps_are_the_references():
    """The system against the reference over three Adam steps from the
    seeded weights: each step's loss, the first gradient's norms and the
    parameters' change, leaf by leaf (what ``compare.py`` is given); the
    tied matrix is one leaf on both sides."""
    from benchmark import compare
    from benchmark.reference.common import leaf_norms
    # SHALLOW as it is (two sequences a rank): ``follow`` keeps its compiled
    # pieces by the sizes, and the test below reads the same ones
    reference = ref.follow(SHALLOW, KEY, 1, 3)
    params = ref.init_weights(KEY, SHALLOW)
    toks, tgts = ref.make_batch(KEY, SHALLOW, 0)
    adam = ref.ADAM
    opt = optax.adam(adam["lr"], b1=adam["b1"], b2=adam["b2"],
                     eps=adam["eps"])
    step = jax.jit(jamba.make_train_step(config(SHALLOW), opt))
    state, p, losses = opt.init(params), params, []
    with jax.default_matmul_precision("highest"):
        for i in range(3):
            p, state, loss = step(p, state, toks, tgts)
            losses.append(float(loss))
            if i == 0:
                grads = leaf_norms(jax.tree_util.tree_map(
                    lambda m: m / (1 - adam["b1"]), state[0].mu))
    assert set(grads) == set(reference["grad_norms"])
    assert "['embed']:2d" in grads and not any("lm_head" in k for k in grads)
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
    sizes = dict(SHALLOW, batch_per_chip=1, seq_len=64)
    cfg = config(SHALLOW)
    params = ref.init_weights(KEY, sizes)
    toks, tgts = (jnp.concatenate(x) for x in zip(*(
        ref.make_batch(KEY, sizes, r) for r in range(mesh.size))))
    inner = optax.sgd(0.1)
    dist = hvd.DistributedOptimizer(optax.sgd(0.1), op=hvd.Average,
                                    axis_name="hvd")
    step = jamba.make_train_step(cfg, dist)

    def with_every_loss(p, state, t, y):
        p, state, loss = step(p, state, t, y)
        return p, state, loss[None]

    sharded = jax.jit(shard_map(
        with_every_loss, mesh=mesh, in_specs=(P(), P(), P("hvd"), P("hvd")),
        out_specs=(P(), P(), P("hvd")), check_vma=False))
    whole = jax.jit(jamba.make_train_step(cfg, inner))
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
            lambda p: jamba.loss_fn(p, toks, tgts, cfg)))(low)
    g2 = jax.tree_util.tree_map(lambda g: g.astype(jnp.float32), g2)
    assert abs(float(l1) - float(l2)) > 10 * LOSS_TOL * abs(float(l1))
    assert worst_rel(g2, g1) > 10 * GRAD_TOL
