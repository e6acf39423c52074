"""The Ouro looped-model path at test size on the CPU: the program against
the benchmark's plain float32 reference (which shares no code with it) on
seeded weights — loss, each pass's loss, every gradient leaf — the loop's
wiring (one pass is the plain stack, a shared weight's gradient is the sum
over four unshared copies, the exit distribution, the entropy's sign, the
head in blocks), and the train step under ``shard_map`` with the in-graph
``DistributedOptimizer``."""

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
from benchmark.reference import ouro as ref                 # noqa: E402
from family import Seeded, worst_rel                        # noqa: E402
from horovod_tpu.compat import shard_map                    # noqa: E402
from horovod_tpu.models import ouro                         # noqa: E402

# the configuration file's ``tiny`` preset, three layers deep
SIZES = dict(hidden_size=64, intermediate_size=96, num_hidden_layers=3,
             num_attention_heads=4, num_key_value_heads=4, head_dim=16,
             vocab_size=256, rms_norm_eps=1e-6, rope_theta=1e6,
             total_ut_steps=4, entropy_beta=0.05, dtype="float32",
             batch_per_chip=2, seq_len=70)
KEY = jax.random.PRNGKey(5)
# float32 against float32: reassociation only
LOSS_TOL, GRAD_TOL = 1e-5, 5e-4


SEEDED = Seeded(ref, SIZES, KEY)


def config(**kw):
    return ouro.tiny(n_layers=SIZES["num_hidden_layers"], **kw)


def test_the_weights_have_the_programs_layout():
    mine = jax.eval_shape(lambda k: ref.init_weights(k, SIZES), KEY)
    theirs = jax.eval_shape(lambda k: ouro.init_params(config(), k), KEY)
    assert jax.tree_util.tree_structure(mine) == \
        jax.tree_util.tree_structure(theirs)
    for x, y in zip(jax.tree_util.tree_leaves(mine),
                    jax.tree_util.tree_leaves(theirs)):
        assert x.shape == y.shape and x.dtype == y.dtype
    # one stack of weights, whatever the number of passes
    assert theirs["layers"]["wq"].shape == (3, 64, 64)
    assert theirs["gate"]["w"].shape == (64,) and theirs["gate"]["b"].shape \
        == ()


def test_the_published_sizes_count_2_7b_parameters():
    shapes = jax.eval_shape(lambda k: ouro.init_params(ouro.ouro_2_6b(), k),
                            KEY)
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert n == 48 * 51_388_416 + 2 * 49152 * 2048 + 2048 + 2049
    assert 2.6e9 < n < 2.7e9


@pytest.mark.parametrize("use_flash", [False, True])
def test_loss_each_passes_loss_and_gradients_are_the_references(use_flash):
    """Four passes over three layers in float32 on seeded weights (the
    reference's own draw: norm weights away from one, the gates spread
    round a half), with the Pallas flash kernel interpreted too."""
    params, toks, tgts = SEEDED
    cfg = config(use_flash=use_flash)
    nll, z = SEEDED.kept("exits", lambda w, toks, tgts: jax.jit(
        lambda w: ref.exits(w, toks, tgts, SIZES))(w))
    l1, g1 = SEEDED.loss_and_grads
    with jax.default_matmul_precision("highest"):
        logits, p = jax.jit(lambda w: ouro.forward(w, toks, cfg))(params)
        stats = jax.jit(lambda w: ouro.exit_stats(w, toks, tgts, cfg))(params)
        l2, g2 = jax.jit(jax.value_and_grad(
            lambda w: ouro.loss_fn(w, toks, tgts, cfg)))(params)
    assert logits.shape == (4, 2, 70, 256) and logits.dtype == jnp.float32
    assert np.allclose(p, ref.exit_distribution(z), atol=1e-6)
    assert np.allclose(stats["nll_mean"], jnp.mean(nll, axis=(1, 2)),
                       rtol=LOSS_TOL)
    # the four passes read different states
    assert len(set(np.round(np.asarray(stats["nll_mean"]), 4))) == 4
    assert abs(float(l1) - float(l2)) <= LOSS_TOL * abs(float(l1))
    assert jax.tree_util.tree_structure(g1) == jax.tree_util.tree_structure(g2)
    for (path, want), got in zip(
            jax.tree_util.tree_flatten_with_path(g1)[0],
            jax.tree_util.tree_leaves(g2)):
        assert float(jnp.max(jnp.abs(want))) > 0, path
        assert worst_rel(got, want) <= GRAD_TOL, jax.tree_util.keystr(path)


def plain_exits(per_pass_layers, params, toks, tgts, cfg):
    """``(nll, z)`` with a list of stacks, one a pass: no loop primitive,
    no custom backward pass."""
    x = params["embed"][toks]
    nll, z = [], []
    for layers in per_pass_layers:
        for l in range(cfg.n_layers):
            x = ouro._layer(jax.tree_util.tree_map(lambda w: w[l], layers),
                            x, cfg)
        x = ouro._close(params["final_norm"], x, cfg)
        logits = ouro._logits(params, x)
        nll.append(jax.scipy.special.logsumexp(logits, axis=-1)
                   - jnp.take_along_axis(logits, tgts[..., None],
                                         axis=-1)[..., 0])
        z.append(ouro._gate_logit(params, x))
    return jnp.stack(nll), jnp.stack(z)


def test_one_pass_is_the_plain_stack():
    """With ``total_ut_steps`` 1 the only exit takes all of the
    probability: the loss is the plain stack's mean cross-entropy, and the
    gate gets no gradient."""
    params, toks, tgts = SEEDED
    cfg = config(total_ut_steps=1)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(
            lambda w: ouro.loss_fn(w, toks, tgts, cfg)))(params)
        want, plain = jax.jit(jax.value_and_grad(lambda w: jnp.mean(
            plain_exits([w["layers"]], w, toks, tgts, cfg)[0])))(params)
    assert abs(float(loss) - float(want)) <= LOSS_TOL * float(want)
    assert not np.asarray(grads["gate"]["w"]).any()
    assert worst_rel(grads["layers"], plain["layers"]) <= GRAD_TOL
    assert worst_rel(grads["lm_head"], plain["lm_head"]) <= GRAD_TOL


def test_a_shared_weights_gradient_is_the_sum_over_four_unshared_copies():
    params, toks, tgts = SEEDED
    cfg = config()
    copies = [params["layers"]] * cfg.total_ut_steps

    def unshared(stacks):
        return ouro.expected_exit_loss(
            *plain_exits(stacks, params, toks, tgts, cfg), cfg.entropy_beta)

    with jax.default_matmul_precision("highest"):
        of_copy = jax.jit(jax.grad(unshared))(copies)
        shared = jax.jit(jax.grad(lambda w: ouro.loss_fn(
            dict(params, layers=w), toks, tgts, cfg)))(params["layers"])
    # every pass adds something of its own
    norms = [float(jnp.linalg.norm(g["w_up"])) for g in of_copy]
    assert min(norms) > 0 and len(set(np.round(norms, 6))) == 4
    summed = jax.tree_util.tree_map(lambda *g: sum(g), *of_copy)
    assert worst_rel(shared, summed) <= GRAD_TOL


def test_the_exit_distribution_sums_to_one_and_the_last_takes_the_rest():
    z = jax.random.normal(KEY, (4, 3, 11)) * 3.0
    p = np.asarray(jnp.exp(ouro.exit_log_probs(z)))
    lam = 1.0 / (1.0 + np.exp(-np.asarray(z)))
    assert np.allclose(p.sum(axis=0), 1.0, atol=1e-6)
    assert np.allclose(p[0], lam[0], atol=1e-6)
    assert np.allclose(p[2], lam[2] * (1 - lam[0]) * (1 - lam[1]), atol=1e-6)
    # the last pass's own gate is not read
    assert np.allclose(p[3], (1 - lam[0]) * (1 - lam[1]) * (1 - lam[2]),
                       atol=1e-6)
    assert np.allclose(p, ref.exit_distribution(z), atol=1e-6)
    moved = jnp.exp(ouro.exit_log_probs(z.at[3].add(5.0)))
    assert np.array_equal(np.asarray(moved), p)


def test_the_entropy_term_lowers_the_loss_by_beta_times_the_entropy():
    params, toks, tgts = SEEDED
    cfg = config()
    with jax.default_matmul_precision("highest"):
        stats = jax.jit(lambda w: ouro.exit_stats(w, toks, tgts, cfg))(params)
        loss = lambda cfg: jax.jit(
            lambda w: ouro.loss_fn(w, toks, tgts, cfg))(params)
        with_it = loss(cfg)
        without = loss(dataclasses.replace(cfg, entropy_beta=0.0))
    entropy = float(stats["entropy_mean"])
    assert 0 < entropy <= np.log(4) + 1e-6
    assert abs(float(without) - float(with_it) - 0.05 * entropy) <= 1e-5
    assert np.isclose(float(jnp.sum(stats["p_mean"])), 1.0, atol=1e-5)
    # a spread gate: every pass keeps a share
    assert float(jnp.min(stats["p_mean"])) > 0.05


@pytest.mark.parametrize("block", [16, 32, 70, 4096])
def test_the_head_in_blocks_is_the_head(monkeypatch, block):
    params, toks, tgts = SEEDED
    x = params["embed"][toks]
    monkeypatch.setattr(ouro, "HEAD_TOKENS", 4096)
    want, g1 = jax.value_and_grad(lambda w: jnp.sum(ouro._token_nll(
        w, x, tgts) ** 2))(params)
    monkeypatch.setattr(ouro, "HEAD_TOKENS", block)
    got, g2 = jax.value_and_grad(lambda w: jnp.sum(ouro._token_nll(
        w, x, tgts) ** 2))(params)
    assert abs(float(got) - float(want)) <= 1e-5 * float(want)
    assert worst_rel(g2["lm_head"], g1["lm_head"]) <= 1e-4


def test_the_train_step_under_shard_map_is_the_unsharded_step():
    """``make_train_step`` under ``shard_map`` over ``hvd.mesh()`` (8 CPU
    ranks, a sequence each) with the in-graph ``DistributedOptimizer``
    gives the parameters and the mean loss of the plain optax step on the
    whole batch."""
    hvd.init()
    mesh = hvd.mesh()
    sizes = dict(SIZES, batch_per_chip=1, seq_len=48)
    cfg = config()
    params, _, _ = SEEDED       # the draw does not read the batch's shape
    toks, tgts = (jnp.concatenate(x) for x in zip(*(
        ref.make_batch(KEY, sizes, r) for r in range(mesh.size))))
    inner = optax.sgd(0.1)
    dist = hvd.DistributedOptimizer(optax.sgd(0.1), op=hvd.Average,
                                    axis_name="hvd")
    step = ouro.make_train_step(cfg, dist)

    def with_every_loss(p, state, t, y):
        p, state, loss = step(p, state, t, y)
        return p, state, loss[None]

    sharded = jax.jit(shard_map(
        with_every_loss, mesh=mesh, in_specs=(P(), P(), P("hvd"), P("hvd")),
        out_specs=(P(), P(), P("hvd")), check_vma=False))
    whole = jax.jit(ouro.make_train_step(cfg, inner))
    with jax.default_matmul_precision("highest"):
        p1, _, losses = sharded(params, dist.init(params), toks, tgts)
        p2, _, loss = whole(params, inner.init(params), toks, tgts)
    assert losses.shape == (mesh.size,) and len(set(np.asarray(losses))) > 1
    assert abs(float(jnp.mean(losses)) - float(loss)) <= 1e-5 * float(loss)
    moved = jax.tree_util.tree_map(lambda a, b: a - b, p1, params)
    want = jax.tree_util.tree_map(lambda a, b: a - b, p2, params)
    assert worst_rel(moved, want) <= 1e-3
