"""The JoyAI-LLM-Flash model path at test size on the CPU: the whole
model's two loss terms and every leaf's gradient against the benchmark's
float32 reference (which shares no code with the program and holds the
attention's columns in the published order), the embedding's and the
head's gradients as the sums of their two uses, the prediction module's
masked last position, the expert layer's share test, the selection bias (no
gradient reaches it, Adam leaves it, the step moves it by the load of ALL
experts, the choice reads ``s + b`` and the weights ``s``), the counters,
and the train step under ``shard_map`` with the in-graph
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
from benchmark.reference import joyai as ref                # noqa: E402
from family import Seeded, worst_rel                        # noqa: E402
from horovod_tpu import trace                               # noqa: E402
from horovod_tpu.compat import shard_map                    # noqa: E402
from horovod_tpu.models import joyai, moe                   # noqa: E402

# the dense layer, one expert layer and the module at the configuration
# file's ``tiny`` widths: keys of 16 + 8 beside values of 16, ranks 24 and
# 16, 8 of 16 experts held at 3 a token
SIZES = dict(
    hidden_size=64, intermediate_size=96, num_hidden_layers=2,
    first_k_dense_replace=1, num_attention_heads=4, q_lora_rank=24,
    kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, qk_head_dim=24,
    v_head_dim=16, rope_theta=32000000, moe_intermediate_size=32,
    n_shared_experts=1, n_routed_experts=8, n_routed_experts_published=16,
    first_expert=0, num_experts_per_tok=3, routed_scaling_factor=2.5,
    scoring_func="sigmoid", n_group=1, topk_group=1, norm_topk_prob=True,
    num_nextn_predict_layers=1, mtp_loss_weight=0.3, bias_update_speed=0.001,
    vocab_size=256, rms_norm_eps=1e-6, dtype="float32", batch_per_chip=2,
    seq_len=48)
KEY = jax.random.PRNGKey(50)
# float32 against float32 at 48 tokens: reassociation only
LOSS_TOL, GRAD_TOL = 1e-5, 5e-4


def config(sizes=SIZES, **kw):
    from benchmark.families import joyai as family
    return family.config_of({**sizes, "use_flash": False, **kw})


SEEDED = Seeded(ref, SIZES, KEY)


def programs(params, cfg=None):
    """The reference's draw in the program's column order."""
    return joyai.from_published(params, cfg or config())


def reference_terms():
    """``((L_main, L_mtp), counts)`` of the reference on the draw, kept."""
    return SEEDED.kept("terms", lambda p, t, y: jax.jit(
        lambda p: ref.loss_terms(p, t, y, SIZES))(p))


def program_loss_and_grads():
    """``((L, counts), gradients)`` of the program's ``loss_fn`` on the
    draw (the plain attention path), kept: three tests read it."""
    return SEEDED.kept("program", lambda p, t, y: jax.jit(jax.value_and_grad(
        lambda p: joyai.loss_fn(p, t, y, config()), has_aux=True))(
            programs(p)))


def test_the_published_sizes_count_48b_parameters():
    cfg = joyai.joyai_llm_flash()
    shapes = jax.eval_shape(lambda k: joyai.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    count = lambda t: sum(int(np.prod(x.shape))
                          for x in jax.tree_util.tree_leaves(t))
    # "48B": the 40 layers, embedding and head; the module is 1.25 B more
    module = count(shapes["mtp"])
    assert 48.5e9 < count(shapes) - module < 49.5e9 and 1.2e9 < module < 1.3e9
    assert shapes["layers"][0]["mlp"]["w_gate"].shape == (2048, 7168)
    assert shapes["layers"][1]["moe"]["w1"].shape == (256, 2048, 768)
    assert shapes["layers"][1]["attn"]["wq_b"].shape == (1536, 32 * 192)
    assert shapes["layers"][1]["attn"]["wkv_b"].shape == (512, 32 * 256)
    assert shapes["mtp"]["proj"].shape == (4096, 2048)


def test_the_cells_share_counts_what_the_configuration_file_says():
    from benchmark import cell as cells
    cell = cells.load_cell("joyai_flash-5l-spmd-1c")
    shapes = jax.eval_shape(lambda k: ref.init_weights(k, cell.sizes),
                            jax.random.PRNGKey(0))
    count = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(
        shapes))
    assert count == 1_058_320_384 + 5 * 256     # and five selection biases
    assert "1,058,320,384" in cell.config["reduced_note"]["total"]
    assert cell.config["reduced"] == ["num_hidden_layers",
                                      "n_routed_experts", "vocab_size"]
    for key, published in (("num_hidden_layers", 40),
                           ("n_routed_experts", 256), ("vocab_size", 129280)):
        assert cell.config[key + "_published"] == published


@pytest.mark.parametrize("kw, match", [
    (dict(scoring_func="softmax"), "sigmoid"),
    (dict(n_group=8), "one group"),
    (dict(qk_head_dim=32), "qk_head_dim"),
    (dict(num_nextn_predict_layers=2), "prediction module"),
])
def test_a_config_the_family_cannot_run_is_refused(kw, match):
    with pytest.raises((SystemExit, ValueError), match=match):
        config(**kw)


def test_init_params_has_the_references_layout():
    want = jax.eval_shape(lambda k: ref.init_weights(k, SIZES), KEY)
    got = jax.eval_shape(lambda k: joyai.init_params(config(), k), KEY)
    assert jax.tree_util.tree_structure(got) == jax.tree_util.tree_structure(
        want)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype), path
    assert got["layers"][1]["moe"]["router_bias"].dtype == jnp.float32


# ------------------------------------------------------ loss and gradients
@pytest.mark.parametrize("use_flash", [False, True])
def test_both_loss_terms_and_every_gradient_are_the_references(use_flash):
    params, toks, tgts = SEEDED
    cfg = config(use_flash=use_flash)
    loss, grads = SEEDED.loss_and_grads
    (main, mtp), _ = reference_terms()
    with jax.default_matmul_precision("highest"):
        (got, _), g = program_loss_and_grads() if not use_flash else jax.jit(
            jax.value_and_grad(lambda p: joyai.loss_fn(p, toks, tgts, cfg),
                               has_aux=True))(programs(params))
        terms = jax.jit(lambda p: joyai.losses(p, toks, tgts, cfg)[0])(
            programs(params))
    assert abs(float(got) - float(loss)) <= LOSS_TOL * float(loss)
    assert abs(float(terms[0]) - float(main)) <= LOSS_TOL * float(main)
    assert abs(float(terms[1]) - float(mtp)) <= LOSS_TOL * float(mtp)
    assert abs(float(loss) - float(main) - 0.3 * float(mtp)) <= 1e-6
    assert jax.tree_util.tree_structure(g) == jax.tree_util.tree_structure(
        grads)
    assert worst_rel(g, programs(grads)) <= GRAD_TOL


def test_embedding_and_head_take_the_sum_of_their_two_uses():
    """Each use alone (the main term's gradient, the module's), then both:
    the whole loss's gradient of ``embed`` and of ``lm_head`` is the first
    plus ``mtp_weight`` times the second, and neither use is nothing."""
    params, toks, tgts = SEEDED
    cfg = config()
    p = programs(params)
    shared = lambda g: {k: g[k] for k in ("embed", "lm_head")}

    def each_term(p):
        _, back = jax.vjp(lambda p: jnp.stack(
            joyai.losses(p, toks, tgts, cfg)[0]), p)
        return [back(jnp.eye(2)[i])[0] for i in (0, 1)]

    with jax.default_matmul_precision("highest"):
        of_term = jax.jit(each_term)(p)
    both = program_loss_and_grads()[1]
    for g in of_term:
        for leaf in shared(g).values():
            assert float(jnp.max(jnp.abs(leaf))) > 0
    summed = jax.tree_util.tree_map(lambda a, b: a + cfg.mtp_weight * b,
                                    shared(of_term[0]), shared(of_term[1]))
    assert worst_rel(shared(both), summed) <= 1e-5
    assert worst_rel(shared(both), shared(of_term[0])) > 1e-2
    # the main term reaches nothing of the module
    assert all(float(jnp.max(jnp.abs(x))) == 0
               for x in jax.tree_util.tree_leaves(of_term[0]["mtp"]))


def test_the_last_position_takes_no_part_in_the_modules_term():
    """``L_mtp`` is the mean over positions ``0 .. T - 2`` of the module's
    logits at ``i`` held to the target of ``i + 1``; with the last position
    (whose rolled target is the sequence's first) it is another number."""
    params, toks, tgts = SEEDED
    cfg = config()
    with jax.default_matmul_precision("highest"):
        logits, mtp = jax.jit(lambda p: (
            joyai.mtp_forward(p, toks, tgts, cfg),
            joyai.losses(p, toks, tgts, cfg)[0][1]))(programs(params))
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, jnp.roll(tgts, -1, axis=1)[..., None],
                               axis=-1)[..., 0]
    assert abs(float(mtp) - float(jnp.mean(nll[:, :-1]))) <= 1e-5 * float(mtp)
    assert abs(float(mtp) - float(jnp.mean(nll))) > 1e-4 * float(mtp)


# ---------------------------------------------------------- the expert layer
def test_all_shares_parts_add_up_to_the_whole_layer():
    """The share test: over the 4 shares of 4 of 16 experts the routed
    parts, with what every chip computes alike (the router, the shared
    expert; the attention before it is every chip's whole) counted once,
    add up to the uncut reference's layer, and the shares' counts over ALL
    experts are one and the same."""
    sizes = dict(SIZES, n_routed_experts=16)
    whole = ref.init_weights(KEY, sizes)["layers"][1]["moe"]
    x = jax.random.normal(KEY, (40, 64))
    cfg = config().moe_cfg()
    with jax.default_matmul_precision("highest"):
        routed, shared, counts = ref.expert_layer(whole, x, sizes, jnp.einsum)
        total, assignments = shared, 0
        for first in range(0, 16, 4):
            share = dataclasses.replace(cfg, first_expert=first,
                                        experts_held=4)
            part = dict(whole, **{k: whole[k][first:first + 4]
                                  for k in ("w1", "w2", "w3")})
            y, held = moe.dropless_moe_ffn(x, part, share)
            total = total + (y - shared)
            assignments += int(held.sum())
            assert np.array_equal(np.asarray(held),
                                  np.asarray(counts[first:first + 4]))
    assert assignments == 40 * 3 == int(counts.sum())
    assert float(jnp.max(jnp.abs(total - (routed + shared)))) <= 2e-5 * float(
        jnp.max(jnp.abs(routed)))


def test_expert_load_counts_what_lands_on_the_held_experts():
    params, toks, tgts = SEEDED
    cfg = config()
    (_, counts, held), load = jax.jit(lambda p: (
        joyai.losses(p, toks, tgts, cfg),
        joyai.expert_load(p, toks, tgts, cfg)))(programs(params))
    assert counts.shape == (2, 16) and held.shape == (2, 8)   # the module's
    assert np.array_equal(np.asarray(load), np.asarray(held))
    assert np.array_equal(np.asarray(counts[:, :8]), np.asarray(held))
    assert np.array_equal(np.asarray(counts.sum(axis=1)), [96 * 3] * 2)
    assert np.array_equal(np.asarray(counts),
                          np.asarray(jnp.stack(reference_terms()[1])))


# ------------------------------------------------------------------ the bias
def test_the_choice_reads_s_plus_b_and_the_weights_s():
    """At a bias that changes the choice (expert 5 lifted over every
    score, expert 2 sunk under every one): 5 is every token's, 2 no
    token's, and the chosen weigh by their sigmoid scores alone."""
    cfg = config().moe_cfg()
    p = programs(next(iter(SEEDED)))["layers"][1]["moe"]
    x = jax.random.normal(KEY, (64, 64))
    bias = jnp.zeros((16,)).at[5].set(2.0).at[2].set(-2.0)
    plain_ids, _ = moe.dropless_route(x, p["router"], cfg, jnp.zeros((16,)))
    ids, weights = moe.dropless_route(x, p["router"], cfg, bias)
    assert not bool(jnp.all(jnp.any(plain_ids == 5, axis=1)))
    assert bool(jnp.any(plain_ids == 2))
    assert bool(jnp.all(jnp.any(ids == 5, axis=1)))
    assert not bool(jnp.any(ids == 2))
    scores = jax.nn.sigmoid(jnp.dot(x, p["router"],
                                    precision=jax.lax.Precision.HIGHEST))
    top = jnp.take_along_axis(scores, ids, axis=-1)
    assert float(jnp.max(jnp.abs(
        weights - 2.5 * top / top.sum(-1, keepdims=True)))) <= 1e-6
    want_ids, want = ref.route(dict(p, router_bias=bias), x, SIZES,
                               jnp.einsum)
    assert np.array_equal(np.sort(np.asarray(ids)),
                          np.sort(np.asarray(want_ids)))
    assert abs(float(weights.sum()) - float(want.sum())) <= 1e-3


def biases(tree):
    return [leaf for path, leaf in jax.tree_util.tree_flatten_with_path(
        tree)[0] if "router_bias" in jax.tree_util.keystr(path)]


def test_no_gradient_reaches_the_bias_and_the_step_moves_it_by_the_load():
    """Adam sees zeros on the leaf and leaves it; the step then moves it by
    ``bias_speed * sign(mean - count)`` from the counts over all 16
    experts, in the stack's expert layer and in the module's."""
    params, toks, tgts = SEEDED
    cfg = config()
    p = programs(params)
    adam = optax.adam(1e-2)
    (_, counts), grads = program_loss_and_grads()
    with jax.default_matmul_precision("highest"):
        after, state, _ = jax.jit(joyai.make_train_step(cfg, adam))(
            p, adam.init(p), toks, tgts)
    assert len(biases(grads)) == 2
    assert all(float(jnp.max(jnp.abs(g))) == 0 for g in biases(grads))
    assert all(float(jnp.max(jnp.abs(m))) == 0 for m in biases(state[0].mu))
    for old, new, c in zip(biases(p), biases(after), np.asarray(counts)):
        move = 0.001 * np.sign(c.mean() - c)
        assert np.abs(move).sum() > 0.01          # most experts are off it
        assert np.allclose(np.asarray(new - old), move, atol=1e-7)
    # the other leaves moved by Adam
    assert float(jnp.max(jnp.abs(after["embed"] - p["embed"]))) > 1e-3


# ------------------------------------------------------------------ counters
@pytest.mark.parametrize("use_flash, taken", [(False, "latent_plain"),
                                              (True, "latent_flash")])
def test_the_counter_says_which_path_the_attention_took(use_flash, taken):
    params, toks, tgts = SEEDED
    cfg = config(use_flash=use_flash)
    before = dict(trace.attention)
    jax.jit(lambda p: joyai.loss_fn(p, toks, tgts, cfg)[0]).lower(
        programs(params))
    moved = {k: trace.attention[k] - v for k, v in before.items()}
    assert moved.pop(taken) == 3                # two layers and the module
    assert not any(moved.values())
    assert f"hvd_attention_{taken}_total" in trace.core.SERIES


# --------------------------------------------------------------- train step
# (three optimizer steps against ``follow``, the biases' change among the
# vectors: tests/benchmark/test_benchmark_broken_joyai.py, the sound case)
def test_the_train_step_under_shard_map_is_the_unsharded_step():
    """``make_train_step`` under ``shard_map`` over ``hvd.mesh()`` (8 CPU
    ranks, a sequence each) with the in-graph ``DistributedOptimizer`` and
    the counts summed over the axis gives the parameters, the biases and
    the mean loss of the plain optax step on the whole batch."""
    hvd.init()
    mesh = hvd.mesh()
    sizes = dict(SIZES, batch_per_chip=1)
    cfg = config()
    params = programs(ref.init_weights(KEY, sizes))
    toks, tgts = (jnp.concatenate(x) for x in zip(*(
        ref.make_batch(KEY, sizes, r) for r in range(mesh.size))))
    inner = optax.sgd(0.1)
    dist = hvd.DistributedOptimizer(optax.sgd(0.1), op=hvd.Average,
                                    axis_name="hvd")
    step = joyai.make_train_step(cfg, dist, axis_name="hvd")

    def with_every_loss(p, state, t, y):
        p, state, loss = step(p, state, t, y)
        return p, state, loss[None]

    sharded = jax.jit(shard_map(
        with_every_loss, mesh=mesh, in_specs=(P(), P(), P("hvd"), P("hvd")),
        out_specs=(P(), P(), P("hvd")), check_vma=False))
    whole = jax.jit(joyai.make_train_step(cfg, inner))
    with jax.default_matmul_precision("highest"):
        p1, _, losses = sharded(params, dist.init(params), toks, tgts)
        p2, _, loss = whole(params, inner.init(params), toks, tgts)
    assert losses.shape == (mesh.size,) and len(set(np.asarray(losses))) > 1
    assert abs(float(jnp.mean(losses)) - float(loss)) <= 1e-5 * float(loss)
    moved = jax.tree_util.tree_map(lambda a, b: a - b, p1, params)
    want = jax.tree_util.tree_map(lambda a, b: a - b, p2, params)
    assert worst_rel(moved, want) <= 1e-3
    for a, b in zip(biases(moved), biases(want)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
        assert float(jnp.max(jnp.abs(a))) > 0
