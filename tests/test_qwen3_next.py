"""The Qwen3-Next model path at test size on the CPU: the chunked gated
delta rule against the token-by-token recurrence, the dropless expert layer
that is told its share against a plain loop over experts (the benchmark's
float32 reference, which shares no code with the program), and the whole
model's loss and gradients against that reference."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import qwen3_next as ref      # noqa: E402
from family import (EXPERT_ROUTINGS, Seeded,            # noqa: E402
                    expert_blocks_case, worst_rel)
from horovod_tpu import trace                           # noqa: E402
from horovod_tpu.models import moe, qwen3_next          # noqa: E402

# one period, 16 experts of which 4 are held, top-2: the configuration
# file's ``tiny`` preset
SIZES = dict(hidden_size=64, num_hidden_layers=4, full_attention_interval=4,
             num_attention_heads=4, num_key_value_heads=2, head_dim=16,
             partial_rotary_factor=0.25, rope_theta=1e7,
             linear_num_key_heads=2, linear_num_value_heads=4,
             linear_key_head_dim=16, linear_value_head_dim=16,
             linear_conv_kernel_dim=4, moe_intermediate_size=32,
             shared_expert_intermediate_size=32, num_experts=4,
             num_experts_published=16, first_expert=0,
             num_experts_per_tok=2, vocab_size=256, rms_norm_eps=1e-6,
             dtype="float32", chunk=64, batch_per_chip=2, seq_len=160)
KEY = jax.random.PRNGKey(5)


def mm(spec, a, b):
    return jnp.einsum(spec, a, b)


SEEDED = Seeded(ref, SIZES, KEY)


# ------------------------------------------------------- the chunked rule
def rule_inputs(t, decay, heads=3, dk=16, dv=8, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (2, t, heads, dk)) / np.sqrt(dk)
    k = jax.random.normal(ks[1], (2, t, heads, dk))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (2, t, heads, dv))
    # per-token decay between ``decay`` and 1
    g = jnp.log(decay) * jax.random.uniform(ks[3], (2, t, heads))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (2, t, heads)))
    return q, k, v, g, beta


@pytest.mark.parametrize("t", [64, 128, 37, 100, 200])
@pytest.mark.parametrize("decay", [0.999, 0.9])
def test_chunked_delta_rule_is_the_token_by_token_recurrence(t, decay):
    """Lengths that are and are not multiples of the chunk (and of the
    reference's segment), with decay near 1: the state a chunk hands on is
    most of what later tokens read."""
    args = rule_inputs(t, decay)
    with jax.default_matmul_precision("highest"):
        want = ref.recurrence(*args)
        got = jax.jit(lambda *a: qwen3_next.chunked_gated_delta_rule(
            *a, chunk=64))(*args)
    assert got.shape == want.shape == (2, t, 3, 8)
    assert float(jnp.max(jnp.abs(got - want))) <= 2e-5 * float(
        jnp.max(jnp.abs(want)))


def test_chunked_delta_rule_has_the_recurrences_gradients():
    args = rule_inputs(150, 0.99)
    weight = jax.random.normal(jax.random.PRNGKey(9), (2, 150, 3, 8))
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.grad(
            lambda *a: jnp.sum(ref.recurrence(*a) * weight),
            argnums=range(5)))(*args)
        got = jax.jit(jax.grad(lambda *a: jnp.sum(
            qwen3_next.chunked_gated_delta_rule(*a, chunk=64) * weight),
            argnums=range(5)))(*args)
    assert worst_rel(got, want) <= 1e-4


def test_the_state_carried_between_chunks_matters():
    """With the state dropped at each chunk's edge the outputs past the
    first chunk are far off: the comparison is not vacuous."""
    q, k, v, g, beta = rule_inputs(128, 0.999)
    rule = jax.jit(lambda *a: qwen3_next.chunked_gated_delta_rule(
        *a, chunk=64))
    whole = rule(q, k, v, g, beta)
    alone = rule(q[:, 64:], k[:, 64:], v[:, 64:], g[:, 64:], beta[:, 64:])
    assert float(jnp.max(jnp.abs(whole[:, 64:] - alone))) > 0.1 * float(
        jnp.max(jnp.abs(whole)))


# ------------------------------------------------------- the expert layer
@functools.lru_cache(maxsize=None)
def all_experts(seed):
    """An expert layer's weights for ALL 16 experts: drawn once a seed."""
    return ref.init_weights(jax.random.PRNGKey(seed), dict(
        SIZES, num_experts=16, num_hidden_layers=1,
        full_attention_interval=1))["layers"][0]["moe"]


def layer_params(held, first=0, seed=1):
    """An expert layer's weights for ALL 16 experts, and the slice a share
    holds."""
    full = dict(all_experts(seed))
    share = {k: (v[first:first + held] if k in ("w1", "w2", "w3") else v)
             for k, v in full.items()}
    return full, share


def share_cfg(first, held, shared=32):
    return moe.DroplessMoEConfig(d_model=64, d_ff=32, n_experts=16, top_k=2,
                                 first_expert=first, experts_held=held,
                                 d_shared=shared)


def tokens(n=96, seed=2):
    return jax.random.normal(jax.random.PRNGKey(seed), (n, 64))


def test_the_shares_add_up_to_the_uncut_layer():
    """THE SHARE TEST: the routed parts of all shares (4 shares of 4
    experts), with the shared expert counted once, add up to what the
    uncut reference gives for the whole layer."""
    x = tokens()
    full, _ = layer_params(16)
    with jax.default_matmul_precision("highest"):
        routed, shared = ref.expert_layer(full, x, SIZES, mm,
                                          first_expert=0, held=16)
        total, held = jnp.zeros_like(x), 0
        for s in range(4):
            _, share = layer_params(4, first=4 * s)
            # every chip computes the shared expert alike: counted once
            y, counts = moe.dropless_moe_ffn(
                x, share, share_cfg(4 * s, 4, shared=32 if s == 0 else 0))
            total, held = total + y, held + int(counts.sum())
    assert held == x.shape[0] * 2            # every assignment, once
    assert float(jnp.max(jnp.abs(total - (routed + shared)))) <= 1e-5 * float(
        jnp.max(jnp.abs(routed + shared)))
    assert float(jnp.max(jnp.abs(routed))) > 0.01       # and it is not nil


@pytest.mark.parametrize("first, held", [(0, 4), (4, 4), (12, 4), (0, 16),
                                         (5, 1)])
def test_a_share_is_the_references_share_with_its_gradients(first, held):
    x = tokens()
    _, share = layer_params(held, first)
    cfg = share_cfg(first, held)

    def program(p, x):
        return jnp.sum(jnp.sin(moe.dropless_moe_ffn(x, p, cfg)[0]))

    def reference(p, x):
        return jnp.sum(jnp.sin(sum(ref.expert_layer(
            p, x, SIZES, mm, first_expert=first, held=held))))

    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(program, argnums=(0, 1)))(share, x)
        want = jax.jit(jax.value_and_grad(reference, argnums=(0, 1)))(
            share, x)
    assert abs(float(got[0]) - float(want[0])) <= 1e-5 * abs(float(want[0]))
    assert worst_rel(got[1], want[1]) <= 1e-4


def steer(share, experts):
    """A router that sends every token to ``experts`` (top-2)."""
    router = jnp.zeros((64, 16)).at[:, jnp.asarray(experts)].set(
        jnp.asarray([[1.0, 0.5]]))
    return dict(share, router=router)


@pytest.mark.parametrize("experts, here", [((1, 2), 192), ((2, 2 + 8), 96),
                                           ((8, 9), 0)])
def test_no_assignment_is_lost_at_either_end(experts, here):
    """Every token routes to the same two experts: both held here (the
    buffer of all S * top_k rows is full), one of them, or none: the held
    counts say so, and the result and its gradients are the reference's
    (with none here, the shared expert alone)."""
    x = jnp.abs(tokens())         # positive, so that the steering holds
    _, share = layer_params(4)
    share, cfg = steer(share, experts), share_cfg(0, 4)
    with jax.default_matmul_precision("highest"):
        y, counts = moe.dropless_moe_ffn(x, share, cfg)
        routed, shared = ref.expert_layer(share, x, SIZES, mm,
                                          first_expert=0, held=4)
        got = jax.jit(jax.grad(lambda p, x: jnp.sum(jnp.sin(
            moe.dropless_moe_ffn(x, p, cfg)[0])), argnums=(0, 1)))(share, x)
        want = jax.jit(jax.grad(lambda p, x: jnp.sum(jnp.sin(sum(
            ref.expert_layer(p, x, SIZES, mm, first_expert=0, held=4)))),
            argnums=(0, 1)))(share, x)
    assert int(counts.sum()) == here
    assert sorted(np.asarray(counts))[-2:] == sorted(
        [96 if e < 4 else 0 for e in experts])
    assert float(jnp.max(jnp.abs(y - (routed + shared)))) <= 1e-5 * float(
        jnp.max(jnp.abs(routed + shared)))
    if here == 0:
        assert float(jnp.max(jnp.abs(routed))) == 0.0
    assert worst_rel(got, want) <= 1e-4


@pytest.mark.parametrize("routing", list(EXPERT_ROUTINGS))
def test_the_blocks_are_a_plain_loop_over_the_held_experts(routing):
    """This family's form of the layer (softmax scoring, SwiGLU experts, a
    gated shared expert, no latent) block by block — the usual load in one
    block, every assignment here in all eight, none in none, three with
    the last part full — is a plain loop over the held experts, values and
    gradients, and ``live_blocks`` says how many blocks ran."""
    expert_blocks_case(moe.DroplessMoEConfig(
        d_model=32, d_ff=24, n_experts=64, top_k=4, first_expert=8,
        experts_held=4, d_shared=16), routing)


def test_the_counter_adds_up_a_sites_blocks():
    """``trace.expert_blocks``: a traced call site adds itself, the blocks
    its sorted assignments are cut into and a block's rows; a second call
    of the compiled function adds nothing."""
    cfg = moe.DroplessMoEConfig(d_model=32, d_ff=24, n_experts=64, top_k=4,
                                first_expert=8, experts_held=4)
    params = moe.dropless_init_params(cfg, KEY)
    x = jax.random.normal(KEY, (96, 32))
    before = dict(trace.expert_blocks)
    layer = jax.jit(lambda p, x: moe.dropless_moe_ffn(x, p, cfg))
    layer(params, x), layer(params, x)
    blocks = moe.dropless_blocks(96 * 4, cfg)
    assert {k: trace.expert_blocks[k] - n for k, n in before.items()} == {
        "sites": 1, "blocks": blocks, "block_rows": 96 * 4 // blocks}
    whole = moe.DroplessMoEConfig(d_model=32, d_ff=24, n_experts=64, top_k=4)
    moe.dropless_moe_ffn(x, moe.dropless_init_params(whole, KEY), whole)
    assert {k: trace.expert_blocks[k] - n for k, n in before.items()} == {
        "sites": 2, "blocks": blocks + 1,
        "block_rows": 96 * 4 // blocks + 96 * 4}


def test_monitor_agent_exports_the_three_counts():
    """The counts are registered series: ``/metrics`` serves them."""
    from horovod_tpu.monitor.agent import MonitorAgent

    class Engine:
        monitor = None

    cfg = moe.DroplessMoEConfig(d_model=32, d_ff=24, n_experts=64, top_k=4,
                                first_expert=8, experts_held=4)
    params = moe.dropless_init_params(cfg, KEY)
    blocks = moe.dropless_blocks(96 * 4, cfg)
    agent = MonitorAgent(engine=Engine())
    try:
        first = agent.registry.snapshot()
        moe.dropless_moe_ffn(jnp.ones((96, 32)), params, cfg)
        second = agent.registry.snapshot()
        text = agent.registry.to_prometheus('rank="0"')
    finally:
        agent.close()

    def value(snap, name):
        return snap[name]["value"] if isinstance(snap[name], dict) \
            else snap[name]

    for key, moved in (("sites", 1), ("blocks", blocks),
                       ("block_rows", 96 * 4 // blocks)):
        name = f"hvd_expert_blocks_{key}_total"
        assert value(second, name) - value(first, name) == moved
        assert name in text and trace.core.SERIES[name][0] == "counter"


def test_live_blocks_reads_a_call_a_row():
    """``live_blocks`` over ``expert_load``'s ``[layers, experts_held]``:
    a layer a call, a NumPy array in and out."""
    cfg = moe.DroplessMoEConfig(n_experts=512, top_k=10, experts_held=64)
    rows = 16384 * 10
    block = rows // moe.dropless_blocks(rows, cfg)
    counts = np.zeros((3, 64), np.int64)
    counts[1, :] = block // 64            # a block to the row
    counts[2, 0] = rows                   # every assignment on one expert
    assert moe.live_blocks(counts, rows, cfg).tolist() == [
        0, 1, moe.dropless_blocks(rows, cfg)]
    counts[1, 5] += 1
    assert moe.live_blocks(counts, rows, cfg).tolist()[1] == 2


@pytest.mark.parametrize("kw", [dict(first_expert=14, experts_held=4),
                                dict(first_expert=-1), dict(top_k=0),
                                dict(top_k=17), dict(experts_held=0)])
def test_a_share_outside_the_experts_is_refused(kw):
    with pytest.raises(ValueError):
        moe.DroplessMoEConfig(n_experts=16, **kw)


def test_dropless_init_params_has_the_shares_shapes():
    cfg = share_cfg(4, 4)
    p = moe.dropless_init_params(cfg, KEY)
    assert {k: v.shape for k, v in p.items()} == {
        "router": (64, 16), "w1": (4, 64, 32), "w3": (4, 64, 32),
        "w2": (4, 32, 64), "shared_w1": (64, 32), "shared_w3": (64, 32),
        "shared_w2": (32, 64), "shared_gate": (64,)}
    assert "shared_w1" not in moe.dropless_init_params(share_cfg(0, 4, 0),
                                                       KEY)


# ------------------------------------------------------------- the model
def test_the_layer_pattern_is_three_recurrent_layers_then_attention():
    cfg = qwen3_next.tiny(n_layers=8)
    assert [cfg.is_full_attention(i) for i in range(8)] == [
        False, False, False, True] * 2
    kinds = ["attn" if "attn" in layer else "gdn"
             for layer in jax.eval_shape(
                 lambda k: qwen3_next.init_params(cfg, k), KEY)["layers"]]
    assert kinds == ["gdn", "gdn", "gdn", "attn"] * 2


def test_the_published_sizes_count_80b_parameters():
    shapes = jax.eval_shape(lambda k: qwen3_next.init_params(
        qwen3_next.qwen3_next_80b_a3b(), k), KEY)
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert 79e9 < n < 81e9


@pytest.mark.parametrize("use_flash", [False, True])
def test_loss_and_gradients_are_the_references(use_flash):
    """One period in float32 on seeded weights (the reference's own draw:
    norm weights away from zero, decays up to 0.999), 2.5 chunks a
    sequence; with the Pallas flash kernel interpreted as well."""
    params, toks, tgts = SEEDED
    l1, g1 = SEEDED.loss_and_grads
    cfg = qwen3_next.tiny(use_flash=use_flash)
    with jax.default_matmul_precision("highest"):
        l2, g2 = jax.jit(jax.value_and_grad(
            lambda p: qwen3_next.loss_fn(p, toks, tgts, cfg)))(params)
    assert abs(float(l1) - float(l2)) <= 1e-5 * abs(float(l2))
    assert worst_rel(g2, g1) <= 2e-4


def test_expert_load_counts_what_lands_on_the_held_experts():
    params, toks, _ = SEEDED
    counts = np.asarray(jax.jit(lambda p: qwen3_next.expert_load(
        p, toks, qwen3_next.tiny()))(params))
    assert counts.shape == (4, 4) and counts.dtype == np.int32
    # 4 of 16 experts, top-2 of 320 tokens: about 160 a layer, never more
    # than every assignment
    assert (counts.sum(axis=1) <= 640).all() and counts.sum() > 0


def test_a_train_step_lowers_the_loss():
    import optax
    cfg = qwen3_next.tiny()
    params, toks, tgts = SEEDED
    opt = optax.adam(1e-2)
    step = jax.jit(qwen3_next.make_train_step(cfg, opt))
    state, losses = opt.init(params), []
    for _ in range(4):
        params, state, loss = step(params, state, toks, tgts)
        losses.append(float(loss))
    assert losses[-1] < losses[0]
