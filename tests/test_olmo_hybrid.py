"""The Olmo-Hybrid model path at test size on the CPU: the chunked gated
delta rule with beta over (0, 2) and unequal key and value widths against
the token-by-token recurrence (the benchmark's float32 reference, which
shares no code with the program), the delta rule by head groups, the block's
wiring, the whole model's logits, loss and gradients against that
reference, and the train step under ``shard_map`` with the in-graph
``DistributedOptimizer``."""

import contextlib
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
from benchmark.reference import olmo_hybrid as ref          # noqa: E402
from benchmark.reference import qwen3_next as qwen_ref      # noqa: E402
from family import Seeded, planted, worst_rel               # noqa: E402
from horovod_tpu.compat import shard_map                    # noqa: E402
from horovod_tpu.models import gated_delta, olmo_hybrid, qwen3_next  # noqa: E402

# one period; key width != value width, as many key heads as value heads:
# the configuration file's ``tiny`` preset
SIZES = dict(hidden_size=64, intermediate_size=96, num_hidden_layers=4,
             full_attention_interval=4, num_attention_heads=4,
             num_key_value_heads=4, linear_num_key_heads=4,
             linear_num_value_heads=4, linear_key_head_dim=12,
             linear_value_head_dim=24, linear_conv_kernel_dim=4,
             linear_allow_neg_eigval=True, vocab_size=256, rms_norm_eps=1e-6,
             dtype="float32", chunk=64, batch_per_chip=2, seq_len=200)
KEY = jax.random.PRNGKey(5)
# float32 against float32: reassociation only
LOGITS_TOL, LOSS_TOL, GRAD_TOL = 2e-4, 1e-5, 5e-4


SEEDED = Seeded(ref, SIZES, KEY)


# ------------------------------------------------------- the chunked rule
def rule_inputs(t, beta, heads=3, dk=12, dv=24, decay=0.99, seed=0):
    """``beta``: "drawn" over (0, 2), or a number every beta takes."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (2, t, heads, dk)) / np.sqrt(dk)
    k = jax.random.normal(ks[1], (2, t, heads, dk))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (2, t, heads, dv))
    g = jnp.log(decay) * jax.random.uniform(ks[3], (2, t, heads))
    if beta == "drawn":
        beta = 2.0 * jax.nn.sigmoid(
            2.0 * jax.random.normal(ks[4], (2, t, heads)))
    else:
        beta = jnp.full((2, t, heads), beta, jnp.float32)
    return q, k, v, g, beta


@pytest.mark.parametrize("t", [64, 37, 200])
@pytest.mark.parametrize("beta", ["drawn", 1.999])
@pytest.mark.parametrize("dk, dv", [(12, 24), (24, 12)])
def test_chunked_rule_is_the_recurrence_with_beta_up_to_two(t, beta, dk, dv):
    """Beta over (0, 2) and every beta at 1.999 (each step's factor ``I -
    beta k k^T`` all but reflects), key width != value width either way, as
    many key heads as value heads, T a multiple of the chunk and not."""
    args = rule_inputs(t, beta, dk=dk, dv=dv)
    with jax.default_matmul_precision("highest"):
        want = qwen_ref.recurrence(*args)
        got = jax.jit(lambda *a: gated_delta.chunked_gated_delta_rule(
            *a, chunk=64))(*args)
    assert got.shape == want.shape == (2, t, 3, dv)
    assert float(jnp.max(jnp.abs(got - want))) <= 5e-5 * float(
        jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("beta", ["drawn", 1.999])
def test_chunked_rule_has_the_recurrences_gradients_with_beta_up_to_two(beta):
    args = rule_inputs(150, beta)
    weight = jax.random.normal(jax.random.PRNGKey(9), (2, 150, 3, 24))
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.grad(
            lambda *a: jnp.sum(qwen_ref.recurrence(*a) * weight),
            argnums=range(5)))(*args)
        got = jax.jit(jax.grad(lambda *a: jnp.sum(
            gated_delta.chunked_gated_delta_rule(*a, chunk=64) * weight),
            argnums=range(5)))(*args)
    assert worst_rel(got, want) <= 5e-4


def test_beta_past_one_flips_a_state_component():
    """What the negative eigenvalue does: the same key written twice with
    beta = 2 and a zero value turns the state's component along it round,
    where beta = 1 erases it."""
    k = jnp.zeros((1, 3, 1, 4)).at[..., 0].set(1.0)
    v = jnp.zeros((1, 3, 1, 2)).at[0, 0].set(1.0)
    g = jnp.zeros((1, 3, 1))
    read = lambda beta: np.asarray(gated_delta.chunked_gated_delta_rule(
        k, k, v, g, jnp.asarray(beta, jnp.float32).reshape(1, 3, 1), 64))
    np.testing.assert_allclose(read([1.0, 2.0, 2.0])[0, :, 0, 0],
                               [1.0, -1.0, 1.0], atol=1e-6)
    np.testing.assert_allclose(read([1.0, 1.0, 1.0])[0, :, 0, 0],
                               [1.0, 0.0, 0.0], atol=1e-6)


@pytest.mark.parametrize("token_heads, groups", [(2 * 100 * 2, 3),
                                                 (2 * 100 * 3, 2),
                                                 (2 * 100 * 6, 1)])
def test_the_rule_by_head_groups_is_the_rule(token_heads, groups):
    """Values and gradients; the group is the largest divisor of the six
    heads that fits, and all heads at once run the rule itself."""
    args = rule_inputs(100, "drawn", heads=6)
    calls = []

    def rule(*a):
        calls.append(a[0].shape[2])
        return gated_delta.chunked_gated_delta_rule(*a)

    grouped = gated_delta.by_head_groups(rule, token_heads)
    weight = jax.random.normal(jax.random.PRNGKey(9), (2, 100, 6, 24))
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.value_and_grad(lambda *a: jnp.sum(
            gated_delta.chunked_gated_delta_rule(*a, 64) * weight),
            argnums=range(5)))(*args)
        got = jax.jit(jax.value_and_grad(lambda *a: jnp.sum(
            grouped(*a, 64) * weight), argnums=range(5)))(*args)
    assert set(calls) == {6 // groups}
    assert abs(float(got[0]) - float(want[0])) <= 1e-5 * abs(float(want[0]))
    assert worst_rel(got[1], want[1]) <= 1e-4


# ------------------------------------------------------------- the model
def test_the_weights_have_the_programs_layout_and_pattern():
    cfg = olmo_hybrid.tiny(n_layers=8)
    assert cfg.layer_types == ("linear_attention",) * 3 + (
        "full_attention",) + ("linear_attention",) * 3 + ("full_attention",)
    mine = jax.eval_shape(lambda k: ref.init_weights(k, SIZES), KEY)
    theirs = jax.eval_shape(
        lambda k: olmo_hybrid.init_params(olmo_hybrid.tiny(), k), KEY)
    assert jax.tree_util.tree_structure(mine) == \
        jax.tree_util.tree_structure(theirs)
    for x, y in zip(jax.tree_util.tree_leaves(mine),
                    jax.tree_util.tree_leaves(theirs)):
        assert x.shape == y.shape and x.dtype == y.dtype
    assert ["attn" if "attn" in layer else "gdn"
            for layer in theirs["layers"]] == ["gdn", "gdn", "gdn", "attn"]


def test_the_published_sizes_count_7_4b_parameters():
    shapes = jax.eval_shape(lambda k: olmo_hybrid.init_params(
        olmo_hybrid.olmo_hybrid_7b(), k), KEY)
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert n == 8 * 832_520_436 + 2 * 100352 * 3840 + 3840
    assert 7.4e9 < n < 7.5e9


@pytest.mark.parametrize("use_flash, token_heads", [
    (False, 1 << 17), (True, 1 << 17), (False, 2 * 200 * 2)])
def test_logits_loss_and_gradients_are_the_references(use_flash, token_heads):
    """One period in float32 on seeded weights (the reference's own draw:
    norm weights away from one, decays up to 0.999, half of the betas past
    1), 3.1 chunks a sequence; with the Pallas flash kernel interpreted,
    and with the delta rule two heads at a time (its result saved by
    name)."""
    params, toks, tgts = SEEDED
    want, (l1, g1) = SEEDED.logits, SEEDED.loss_and_grads
    cfg = olmo_hybrid.tiny(use_flash=use_flash)
    with planted(olmo_hybrid, "RULE_TOKEN_HEADS", token_heads), \
            jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p: olmo_hybrid.forward(p, toks, cfg))(params)
        l2, g2 = jax.jit(jax.value_and_grad(
            lambda p: olmo_hybrid.loss_fn(p, toks, tgts, cfg)))(params)
    assert got.shape == (2, 200, 256) and got.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(got - want))) <= LOGITS_TOL * float(
        jnp.max(jnp.abs(want)))
    assert abs(float(l1) - float(l2)) <= LOSS_TOL * abs(float(l1))
    assert jax.tree_util.tree_structure(g1) == jax.tree_util.tree_structure(g2)
    assert worst_rel(g2, g1) <= GRAD_TOL


def norm_first(p, x, cfg):
    """A block with the norm BEFORE the mixer (the usual place)."""
    h = olmo_hybrid._rmsnorm(x, p["mixer_norm"], cfg.norm_eps)
    return x + (olmo_hybrid._full_attention(h, p["attn"], cfg) if "attn" in p
                else gated_delta.gated_delta_net(h, p["gdn"], cfg.gdn_dims()))


def with_rotary(attend):
    """Attention with a rotary applied to q and k, as every other decoder
    here has."""
    def rotated(q, k, v, causal):
        turn = lambda y: qwen3_next._partial_rope(y, y.shape[-1], 1e4)
        return attend(turn(q), turn(k), v, causal=causal)
    return rotated


@pytest.mark.parametrize("fault", ["norm_first", "rotary", "beta_in_0_1"])
def test_the_blocks_wiring_is_what_the_reference_has(fault):
    """A norm moved before the sublayer, a rotary applied, beta left in
    (0, 1): each moves the logits far beyond the tolerance that the sound
    model keeps."""
    params, toks, _ = SEEDED
    want = SEEDED.logits
    cfg, broken = olmo_hybrid.tiny(), contextlib.nullcontext()
    if fault == "norm_first":
        broken = planted(olmo_hybrid, "_mixer_block", norm_first)
    elif fault == "rotary":
        broken = planted(olmo_hybrid, "local_flash_attention", with_rotary(
            olmo_hybrid.local_flash_attention))
    else:       # another config is another trace
        cfg = olmo_hybrid.tiny(allow_neg_eigval=False)
    with broken, jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p: olmo_hybrid.forward(p, toks, cfg))(params)
    assert float(jnp.max(jnp.abs(got - want))) > 100 * LOGITS_TOL * float(
        jnp.max(jnp.abs(want)))


def test_beta_stats_reads_the_share_past_one_and_the_largest():
    params, toks, _ = SEEDED
    stats = lambda cfg: jax.jit(
        lambda p: olmo_hybrid.beta_stats(p, toks, cfg))(params)
    share, largest = stats(olmo_hybrid.tiny())
    assert share.shape == largest.shape == (3,)
    assert (np.asarray(share) > 0.35).all() and (np.asarray(share) < 0.65).all()
    assert (np.asarray(largest) > 1.8).all() and (np.asarray(largest) < 2).all()
    share, largest = stats(olmo_hybrid.tiny(allow_neg_eigval=False))
    assert not np.asarray(share).any() and (np.asarray(largest) < 1).all()


def test_the_train_step_under_shard_map_is_the_unsharded_step():
    """``make_train_step`` under ``shard_map`` over ``hvd.mesh()`` (8 CPU
    ranks, a sequence each) with the in-graph ``DistributedOptimizer``
    gives the parameters and the mean loss of the plain optax step on the
    whole batch."""
    hvd.init()
    mesh = hvd.mesh()
    # the gradient exchange is not a matter of depth: a Gated DeltaNet
    # layer and an attention layer, and a step that compiles in half the time
    sizes = dict(SIZES, num_hidden_layers=2, full_attention_interval=2,
                 batch_per_chip=1, seq_len=96)
    cfg = olmo_hybrid.tiny(n_layers=2, full_attention_interval=2)
    params = ref.init_weights(KEY, sizes)
    toks, tgts = (jnp.concatenate(x) for x in zip(*(
        ref.make_batch(KEY, sizes, r) for r in range(mesh.size))))
    inner = optax.sgd(0.1)
    dist = hvd.DistributedOptimizer(optax.sgd(0.1), op=hvd.Average,
                                    axis_name="hvd")
    step = olmo_hybrid.make_train_step(cfg, dist)

    def with_every_loss(p, state, t, y):
        p, state, loss = step(p, state, t, y)
        return p, state, loss[None]

    sharded = jax.jit(shard_map(
        with_every_loss, mesh=mesh, in_specs=(P(), P(), P("hvd"), P("hvd")),
        out_specs=(P(), P(), P("hvd")), check_vma=False))
    whole = jax.jit(olmo_hybrid.make_train_step(cfg, inner))
    with jax.default_matmul_precision("highest"):
        p1, _, losses = sharded(params, dist.init(params), toks, tgts)
        p2, _, loss = whole(params, inner.init(params), toks, tgts)
    assert losses.shape == (mesh.size,) and len(set(np.asarray(losses))) > 1
    assert abs(float(jnp.mean(losses)) - float(loss)) <= 1e-5 * float(loss)
    moved = jax.tree_util.tree_map(lambda a, b: a - b, p1, params)
    want = jax.tree_util.tree_map(lambda a, b: a - b, p2, params)
    assert worst_rel(moved, want) <= 1e-3
