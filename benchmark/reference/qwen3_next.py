"""Plain float32 Qwen3-Next decoder for the benchmark's ``correct``: one
chip's share of a deployment in which several chips share each layer.

``jax.numpy`` only, nothing imported from ``horovod_tpu``.  The equations
are those of the published ``qwen3_next`` model (HF transformers):

- ``RMSNorm0(x; w) = x / rms(x) * (1 + w)`` (zero-centred weight) before
  each mixer and expert layer, on each query and key head of the full
  layers, and before the untied head.
- layer ``i`` is a gated full-attention layer when ``(i + 1) %
  full_attention_interval == 0`` and a Gated DeltaNet layer otherwise.
- **Gated DeltaNet**: ``[q|k|v|z] = h W_qkvz``, ``[b|a] = h W_ba``; a
  causal depthwise convolution (no bias) over ``[q|k|v]``, then SiLU; q and
  k repeated to the value heads, L2-normalised, q scaled by ``1/sqrt(dk)``;
  ``beta = sigmoid(b)``, ``g = -exp(A_log) * softplus(a + dt_bias)``; per
  head ``S <- exp(g_t) S; u_t = beta_t (v_t - S^T k_t); S <- S + k_t u_t^T;
  o_t = S^T q_t`` — computed HERE as that **token-by-token recurrence**
  (a ``lax.scan`` over t, recomputed in segments in the backward pass), so
  that the program's chunked algebra is checked against something that
  does not share it; then ``w_n * o / rms(o) * SiLU(z)`` (a plain weight)
  and ``W_o``.
- **Gated attention**: ``W_q`` gives each head a query and a gate;
  ``RMSNorm0`` on each q and k head; rotary (half-split) on the first
  ``partial_rotary_factor`` of the head; causal softmax attention; the
  result times ``sigmoid(gate)``; ``W_o``.
- **Expert layer**: ``softmax(h W_r)`` over ALL published experts, the
  ``num_experts_per_tok`` largest renormalised to sum to 1; the routed part
  is a plain loop over the experts HELD HERE (``first_expert`` ..
  ``first_expert + num_experts``) with a mask; what the absent experts
  would have added is left out.  The shared expert, gated by
  ``sigmoid(h w_g)``, is computed for every token.
- loss: mean next-token cross-entropy over the vocabulary slice; no
  auxiliary loss.

Departures from the published implementation: the columns of ``W_qkvz``
are ordered ``[q|k|v|z]`` over all heads (HF interleaves them per key
head; with seeded weights the order is free).

Parameters are a dict in the layout the system under test uses (a layout,
not code).  Weights and data of a run are made HERE from the seed, in the
configuration's storage type; every operation computes in float32
(``follow`` sets ``highest`` matmul precision).  Each mixer and each expert
layer is recomputed in the backward pass, attention is computed one
(sequence, key-value head, block of queries) at a time, an expert's
matrices become float32 when that expert is computed, and ``follow`` takes
a rank's sequences one at a time, adding up their gradients as it does the
ranks', so that the float32 activations of 8192 tokens fit beside the
state.  ``precision`` other than ``float32`` rounds
the operands of every matrix product and of the convolution in both passes
(``common.quantizer``): the control.  Of the recurrence's products q, k and
v are the rounded operands; its state stays float32, as an accumulator.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .common import leaf_norms, quantizer
from .llama import ADAM, adam_step    # noqa: F401  (ADAM: the family's too)
from .resnet import scalars

SEGMENT = 128       # tokens of the recurrence recomputed together
QUERY_BLOCK = 2048  # queries of one attention block


def is_full_attention(i, sizes):
    return (i + 1) % sizes["full_attention_interval"] == 0


# ------------------------------------------------------------ weights, data
def init_weights(key, sizes):
    """Normal(0, 1/fan_in) matrices.  Norm weights uniform in ±0.5 about
    zero (RMSNorm0's, so that ``w`` in place of ``1 + w`` is far off) or
    in 0.5..1.5 (the gated norm's plain weight).  ``A_log`` and
    ``dt_bias`` so that a head's per-token decay ``exp(g)`` at ``a = 0``
    is log-uniform over heads between 0.9 and 0.999: state that crosses
    many chunks matters."""
    d, v, n = sizes["hidden_size"], sizes["vocab_size"], sizes[
        "num_hidden_layers"]
    h, kv, hd = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                 sizes["head_dim"])
    hk, hv, dk, dv = (sizes["linear_num_key_heads"],
                      sizes["linear_num_value_heads"],
                      sizes["linear_key_head_dim"],
                      sizes["linear_value_head_dim"])
    f, fs = sizes["moe_intermediate_size"], sizes[
        "shared_expert_intermediate_size"]
    held, published = sizes["num_experts"], sizes["num_experts_published"]
    taps = sizes["linear_conv_kernel_dim"]
    dt = jnp.dtype(sizes["dtype"])
    keys = iter(jax.random.split(key, 2 + 24 * n))

    def dense(fan_in, shape):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                / np.sqrt(fan_in)).astype(dt)

    def about(centre, shape):
        return (centre + jax.random.uniform(next(keys), shape, jnp.float32,
                                            -0.5, 0.5)).astype(dt)

    def gdn():
        # decay at a = 0 is exp(-A * softplus(dt_bias)) = exp(-rate)
        rate = jnp.exp(jax.random.uniform(
            next(keys), (hv,), jnp.float32, np.log(0.001), np.log(0.1)))
        a = jax.random.uniform(next(keys), (hv,), jnp.float32, 0.5, 2.0)
        step = rate / a
        return {"w_qkvz": dense(d, (d, 2 * hk * dk + 2 * hv * dv)),
                "w_ba": dense(d, (d, 2 * hv)),
                "conv": dense(taps, (taps, 2 * hk * dk + hv * dv)),
                "A_log": jnp.log(a).astype(dt),
                "dt_bias": jnp.log(jnp.expm1(step)).astype(dt),
                "out_norm": about(1.0, (dv,)),
                "wo": dense(hv * dv, (hv * dv, d))}

    def attn():
        return {"wq": dense(d, (d, h * 2 * hd)), "wk": dense(d, (d, kv * hd)),
                "wv": dense(d, (d, kv * hd)), "q_norm": about(0.0, (hd,)),
                "k_norm": about(0.0, (hd,)), "wo": dense(h * hd, (h * hd, d))}

    def moe():
        return {"router": dense(d, (d, published)),
                "w1": dense(d, (held, d, f)), "w3": dense(d, (held, d, f)),
                "w2": dense(f, (held, f, d)),
                "shared_w1": dense(d, (d, fs)), "shared_w3": dense(d, (d, fs)),
                "shared_w2": dense(fs, (fs, d)),
                "shared_gate": dense(d, (d,))}

    layers = []
    for i in range(n):
        full = is_full_attention(i, sizes)
        layers.append({"mixer_norm": about(0.0, (d,)),
                       "attn" if full else "gdn": attn() if full else gdn(),
                       "moe_norm": about(0.0, (d,)), "moe": moe()})
    return {"embed": dense(d, (v, d)), "layers": layers,
            "final_norm": about(0.0, (d,)), "lm_head": dense(d, (d, v))}


def make_batch(key, sizes, rank):
    """Rank ``rank``'s fixed batch of token rows and their next tokens,
    drawn uniformly from the vocabulary slice."""
    b, t = sizes["batch_per_chip"], sizes["seq_len"]
    toks = jax.random.randint(jax.random.fold_in(key, rank), (b, t + 1), 0,
                              sizes["vocab_size"], jnp.int32)
    return toks[:, :-1], toks[:, 1:]


# ------------------------------------------------------------------ forward
def rms(x, eps):
    return jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)


def rms_norm0(x, w, eps):
    return x / rms(x, eps) * (1.0 + w)


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def partial_rope(x, theta, rotary):
    """x [B, T, heads, head_dim]; the first ``rotary`` of the head rotate,
    pairs (i, i + rotary / 2) together."""
    half = rotary // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs[None]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = x[..., :half], x[..., half:rotary]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., rotary:]], axis=-1)


def recurrence(q, k, v, g, beta):
    """The gated delta rule a token at a time.  q, k [B,T,H,dk], v
    [B,T,H,dv], g, beta [B,T,H] -> o [B,T,H,dv]; the state starts at zero.
    Padding steps (k = 0, g = 0) leave the state as it is."""
    b, t, h, dk = q.shape
    pad = (-t) % SEGMENT

    def segments(x):
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = jnp.moveaxis(x, 1, 0)                       # time leads
        return x.reshape((-1, SEGMENT) + x.shape[1:])

    def token(state, x):
        q_t, k_t, v_t, g_t, beta_t = x
        state = state * jnp.exp(g_t)[..., None, None]
        u = beta_t[..., None] * (
            v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t))
        state = state + k_t[..., :, None] * u[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    segment = jax.checkpoint(lambda state, xs: jax.lax.scan(token, state, xs))
    state = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(segment, state,
                        tuple(segments(x) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o.reshape((-1,) + o.shape[2:]), 0, 1)[:, :t]


def gated_delta_net(p, x, sizes, mm, q):
    b, t, _ = x.shape
    hk, hv, dk, dv = (sizes["linear_num_key_heads"],
                      sizes["linear_num_value_heads"],
                      sizes["linear_key_head_dim"],
                      sizes["linear_value_head_dim"])
    taps = sizes["linear_conv_kernel_dim"]
    qkvz = mm("btd,de->bte", x, p["w_qkvz"])
    ba = mm("btd,de->bte", x, p["w_ba"])
    qkv, z = qkvz[..., :2 * hk * dk + hv * dv], qkvz[..., -hv * dv:]
    # causal depthwise convolution: tap j weighs the input taps-1-j back
    padded = q.operand(jnp.pad(qkv, ((0, 0), (taps - 1, 0), (0, 0))))
    qkv = silu(q.result(sum(q.operand(p["conv"][j]) * padded[:, j:j + t]
                            for j in range(taps))))
    split = lambda y, heads, dim: y.reshape(b, t, heads, dim)
    qs = split(qkv[..., :hk * dk], hk, dk)
    ks = split(qkv[..., hk * dk:2 * hk * dk], hk, dk)
    vs = split(qkv[..., 2 * hk * dk:], hv, dv)
    unit = lambda y: y / jnp.sqrt(
        jnp.sum(jnp.square(y), axis=-1, keepdims=True) + 1e-6)
    qs = jnp.repeat(unit(qs), hv // hk, axis=2) / np.sqrt(dk)
    ks = jnp.repeat(unit(ks), hv // hk, axis=2)
    beta = sigmoid(ba[..., :hv])
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[..., hv:] + p["dt_bias"])
    # the rule's products (S^T k, k u^T, S^T q) take q, k and v as operands
    o = q.result(recurrence(q.operand(qs), q.operand(ks), q.operand(vs),
                            g, beta))
    o = p["out_norm"] * (o / rms(o, sizes["rms_norm_eps"])) * silu(
        split(z, hv, dv))
    return mm("bte,ed->btd", o.reshape(b, t, hv * dv), p["wo"])


def attention(qs, ks, vs, mm):
    """qs [B,T,H,hd], ks/vs [B,T,KV,hd] -> [B,T,H,hd], causal; one
    (sequence, key-value head, block of queries) at a time."""
    b, t, h, hd = qs.shape
    kv = ks.shape[2]
    blocks = -(-t // QUERY_BLOCK)
    pad = blocks * QUERY_BLOCK - t
    j = jnp.arange(t)[None, :]

    def block(qb, start, kg, vg):       # [rep,Q,hd], [], [T,hd], [T,hd]
        i = start + jnp.arange(QUERY_BLOCK)[:, None]
        s = mm("rqd,sd->rqs", qb, kg) / np.sqrt(hd)
        s = jnp.where((j <= i)[None], s, -jnp.inf)
        s = s - jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s)
        p = p / jnp.sum(p, axis=-1, keepdims=True)
        return mm("rqs,sd->rqd", p, vg)

    def group(args):                    # [rep,T,hd], [T,hd], [T,hd]
        qg, kg, vg = args
        qg = jnp.pad(qg, ((0, 0), (0, pad), (0, 0))).reshape(
            h // kv, blocks, QUERY_BLOCK, hd)
        out = jax.lax.map(
            lambda a: jax.checkpoint(block)(a[0], a[1], kg, vg),
            (jnp.moveaxis(qg, 1, 0), jnp.arange(blocks) * QUERY_BLOCK))
        return jnp.moveaxis(out, 0, 1).reshape(h // kv, -1, hd)[:, :t]

    qg = qs.reshape(b, t, kv, h // kv, hd).transpose(0, 2, 3, 1, 4)
    out = jax.lax.map(group, (qg.reshape(b * kv, h // kv, t, hd),
                              ks.transpose(0, 2, 1, 3).reshape(b * kv, t, hd),
                              vs.transpose(0, 2, 1, 3).reshape(b * kv, t, hd)))
    return out.reshape(b, kv, h // kv, t, hd).transpose(
        0, 3, 1, 2, 4).reshape(b, t, h, hd)


def gated_attention(p, x, sizes, mm):
    b, t, _ = x.shape
    h, kv, hd = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                 sizes["head_dim"])
    eps, theta = sizes["rms_norm_eps"], sizes["rope_theta"]
    rotary = int(hd * sizes["partial_rotary_factor"])
    qg = mm("btd,de->bte", x, p["wq"]).reshape(b, t, h, 2 * hd)
    qs, gate = qg[..., :hd], qg[..., hd:]
    ks = mm("btd,de->bte", x, p["wk"]).reshape(b, t, kv, hd)
    vs = mm("btd,de->bte", x, p["wv"]).reshape(b, t, kv, hd)
    qs = partial_rope(rms_norm0(qs, p["q_norm"], eps), theta, rotary)
    ks = partial_rope(rms_norm0(ks, p["k_norm"], eps), theta, rotary)
    o = attention(qs, ks, vs, mm) * sigmoid(gate)
    return mm("bte,ed->btd", o.reshape(b, t, h * hd), p["wo"])


def route(p, x, sizes, mm):
    """[S, top_k] expert ids over all published experts and their
    renormalised weights."""
    probs = jax.nn.softmax(mm("sd,de->se", x, p["router"]), axis=-1)
    top, ids = jax.lax.top_k(probs, sizes["num_experts_per_tok"])
    return ids, top / jnp.sum(top, axis=-1, keepdims=True)


def expert_layer(p, x, sizes, mm, first_expert=None, held=None):
    """x [S, d].  ``(routed, shared)`` parts: the routed part of the
    experts ``first_expert .. first_expert + held`` (the configuration's
    share by default), and the shared expert's, which every chip computes
    alike."""
    first = sizes["first_expert"] if first_expert is None else first_expert
    held = sizes["num_experts"] if held is None else held
    ids, weights = route(p, x, sizes, mm)

    def expert(e, w1, w3, w2):          # one expert's matrices, as stored
        w1, w3, w2 = (w.astype(jnp.float32) for w in (w1, w3, w2))
        w = jnp.sum(jnp.where(ids == first + e, weights, 0.0), axis=-1)
        hidden = silu(mm("sd,df->sf", x, w1)) * mm("sd,df->sf", x, w3)
        return w[:, None] * mm("sf,fd->sd", hidden, w2)

    # the sum is the carry, and no expert's backward pass needs it
    routed, _ = jax.lax.scan(
        lambda total, of: (total + jax.checkpoint(expert)(*of), None),
        jnp.zeros_like(x), (jnp.arange(held), p["w1"], p["w3"], p["w2"]))
    hidden = silu(mm("sd,df->sf", x, p["shared_w1"])) * mm(
        "sd,df->sf", x, p["shared_w3"])
    shared = sigmoid(mm("sd,d->s", x, p["shared_gate"]))[:, None] * mm(
        "sf,fd->sd", hidden, p["shared_w2"])
    return routed, shared


def _mixer(p, x, sizes, mm, q):
    p = jax.tree_util.tree_map(lambda w: w.astype(jnp.float32), p)
    h = rms_norm0(x, p["mixer_norm"], sizes["rms_norm_eps"])
    return x + (gated_attention(p["attn"], h, sizes, mm) if "attn" in p
                else gated_delta_net(p["gdn"], h, sizes, mm, q))


def _experts(p, x, sizes, mm):
    # the experts' stacks stay in their storage type until an expert is
    # computed: float32 copies of all of them are 0.8 GB a layer
    p = jax.tree_util.tree_map(
        lambda w: w if w.ndim == 3 else w.astype(jnp.float32), p)
    b, t, d = x.shape
    h = rms_norm0(x, p["moe_norm"], sizes["rms_norm_eps"]).reshape(b * t, d)
    return x + sum(expert_layer(p["moe"], h, sizes, mm)).reshape(b, t, d)


def loss_fn(params, tokens, targets, sizes, precision="float32"):
    q = quantizer(precision)

    def mm(spec, a, b):
        return q.result(jnp.einsum(spec, q.operand(a), q.operand(b)))

    x = params["embed"].astype(jnp.float32)[tokens]
    for p in params["layers"]:      # each half recomputed on its own
        moe = {"moe_norm": p["moe_norm"], "moe": p["moe"]}
        mixer = {k: v for k, v in p.items() if k not in moe}
        x = jax.checkpoint(functools.partial(
            _mixer, sizes=sizes, mm=mm, q=q))(mixer, x)
        x = jax.checkpoint(functools.partial(
            _experts, sizes=sizes, mm=mm))(moe, x)
    x = rms_norm0(x, params["final_norm"].astype(jnp.float32),
                  sizes["rms_norm_eps"])
    logits = mm("btd,dv->btv", x, params["lm_head"].astype(jnp.float32))
    logp = logits - jax.scipy.special.logsumexp(logits, axis=-1,
                                                keepdims=True)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))


# -------------------------------------------------------------- three steps
@functools.lru_cache(maxsize=None)
def _programs(sizes_items, precision):
    """The jitted pieces of ``follow``, compiled once for a set of sizes."""
    sizes = dict(sizes_items)
    grad = jax.value_and_grad(functools.partial(
        loss_fn, sizes=sizes, precision=precision))

    def add_gradient(total, params, tokens, targets):
        loss, g = grad(params, tokens, targets)
        return loss, jax.tree_util.tree_map(jnp.add, total, g)

    return (jax.jit(lambda k: init_weights(k, sizes)),
            jax.jit(lambda k, r: make_batch(k, sizes, r)),
            jax.jit(add_gradient, donate_argnums=(0,)),
            jax.jit(adam_step, donate_argnums=(0, 2, 3)))


def follow(sizes, key, world, steps, precision="float32"):
    """The first ``steps`` synchronous data-parallel steps at the seeded
    weights: per-rank losses, the norm of the first averaged gradient and
    of the parameters' change, leaf by leaf (``reference/llama.py``'s, with
    this model).  A rank's sequences are taken one at a time and their
    gradients added up, in the gradients' storage type, as the ranks' are:
    the float32 activations of 8192 tokens are what fits beside the
    state."""
    weights, batch, add_gradient, update = _programs(scalars(sizes),
                                                     precision)
    with jax.default_matmul_precision("highest"):
        params = weights(key)
        mu = jax.tree_util.tree_map(jnp.zeros_like, params)
        nu = jax.tree_util.tree_map(jnp.zeros_like, params)
        losses = [[] for _ in range(world)]
        first = None
        for step in range(1, steps + 1):
            mean = jax.tree_util.tree_map(jnp.zeros_like, params)
            for r in range(world):
                tokens, targets = batch(key, r)
                of_rank = []
                for b in range(tokens.shape[0]):    # equally long: the mean
                    loss, mean = add_gradient(mean, params, tokens[b:b + 1],
                                              targets[b:b + 1])
                    of_rank.append(float(loss))
                losses[r].append(sum(of_rank) / len(of_rank))
            parts = world * len(of_rank)
            if parts > 1:
                mean = jax.tree_util.tree_map(lambda x: x / parts, mean)
            if first is None:
                first = leaf_norms(mean)
            params, mu, nu = update(params, mean, mu, nu, step)
            del mean
        delta = leaf_norms(params, minus=weights(key))
    return {"losses": losses, "grad_norms": first, "delta_norms": delta}
