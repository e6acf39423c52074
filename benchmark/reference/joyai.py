"""Plain float32 JoyAI-LLM-Flash decoder for the benchmark's ``correct``:
one chip's share of a deployment in which 8 chips share each layer.

``jax.numpy`` only, nothing imported from ``horovod_tpu``.  The equations
are DeepSeek-V3's, which the published ``joyai_llm_flash`` ``config.json``
names key by key; what it does not settle is marked *assumed* (the
configuration file lists the same):

- ``RMSNorm(x; w) = x / rms(x) * w``, eps from the config.  Layer ``i``:
  ``x <- x + Attn(RMSNorm(x))``, then ``x <- x + MLP(RMSNorm(x))`` (pre-norm
  blocks: *assumed*), no bias anywhere.
- **attention**, ``h`` the normed input: ``c_q = RMSNorm(h W_qa; q_norm)``
  (``hidden -> q_lora_rank``); ``[q_nope | q_rope] = c_q W_qb`` a head
  (``qk_nope_head_dim + qk_rope_head_dim``); ``[c_kv | k_r] = h W_kva``
  (``hidden -> kv_lora_rank + qk_rope_head_dim``); ``c_kv = RMSNorm(c_kv;
  kv_norm)``; ``[k_nope | v] = c_kv W_kvb`` a head (``qk_nope_head_dim +
  v_head_dim``).  Every head's ``q_rope`` and the ONE ``k_r`` that all
  heads share are turned by the plain rotary, ``f_j = theta^(-2j /
  qk_rope_head_dim)``, **pairs (2j, 2j + 1) taken literally**
  (``rope_interleave``), no scaling (``rope_scaling`` null).  Head ``n``'s
  score of query ``t`` on key ``j <= t`` is ``(q_nope[t,n] . k_nope[j,n] +
  q_rope[t,n] . k_r[j]) / sqrt(qk_head_dim)``: two products summed, as
  written, a masked softmax a block of query rows at a time, so that the
  program's kernels, their two widths and the copy of ``k_r`` they read are
  checked against something that shares none of them.  ``o[t,n] = sum_j p
  v[j,n]``; ``Attn = concat_n(o) W_o``.
- **MLP**: the first ``first_k_dense_replace`` layers are ``(SiLU(u
  W_gate) * u W_up) W_down`` at ``intermediate_size``.  The others: ``s =
  sigmoid(u W_r)`` over ALL published experts, the ``num_experts_per_tok``
  largest ``s + b`` chosen (``b`` the layer's selection bias; ``n_group``
  1: no group limit) and weighed by ``routed_scaling_factor * s_e /
  sum_chosen s``; the routed part is a plain loop over the experts HELD
  HERE (``first_expert .. first_expert + n_routed_experts``) with a mask;
  what the absent experts would have added is left out.  The shared expert
  (``n_shared_experts`` x ``moe_intermediate_size`` wide), ungated, is
  computed for every token and added.
- ``g = RMSNorm(x; final_norm)`` and the untied head give the main logits;
  ``L_main`` is the mean next-token cross-entropy over the vocabulary
  slice.
- **the prediction module** (``num_nextn_predict_layers`` 1) at position
  ``i``: ``z_i = [RMSNorm(Emb[t_{i+1}]; embed_norm) ; RMSNorm(g_i;
  hidden_norm)] W_eh`` (the embedding's half first and ``g`` behind the
  final norm: *assumed*), ``u = Block(z)``, one more layer of the sparse
  kind with weights of its own (*assumed*), ``logits' = Head(RMSNorm(u;
  final_norm'))`` through the main model's head, ``Emb`` the main model's
  embedding; held to ``t_{i+2}``, the last position masked out of the
  mean.  ``L = L_main + mtp_loss_weight * L_mtp`` (0.3: *assumed*); no
  auxiliary loss (*assumed*).
- **the bias**, after each step's Adam update: ``b_e +=
  bias_update_speed * sign(mean_e'(c_e') - c_e)`` (0.001: *assumed*),
  ``c_e`` the assignments expert ``e`` got in this step's batch in that
  layer over all ranks, over ALL published experts.  No gradient reaches
  it, and Adam leaves it.

Parameters are a dict with the leaves the system under test has, the
attention's columns **in the published order** (a head of ``wq_b`` ``[nope
| rope]``, the rope's pairs interleaved there and in ``wkv_a``'s last
columns); the program holds them permuted
(``models/latent_attention.from_published``), which no norm of a leaf
sees.  Weights and data of a run are made HERE from the seed, in the
configuration's storage type (the bias float32); every operation computes
in float32 (``follow`` sets ``highest`` matmul precision).  ``follow``
takes the gradient a layer a jitted call, as ``reference/laguna.py``'s.
``precision`` other than ``float32`` rounds the operands of every matrix
product in both passes (``common.quantizer``): the control.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .common import leaf_norms, quantizer
from .jamba import _matmul, embedded
from .laguna import swiglu
from .llama import ADAM, adam_step    # noqa: F401  (ADAM: the family's too)
from .olmo_hybrid import blocks_of, rms_norm
from .qwen3_next import make_batch, sigmoid    # noqa: F401
from .resnet import scalars

QUERY_BLOCK = 512       # queries of one attention block
TOKEN_BLOCK = 2048      # tokens of one block of the head's logits


def sparse_layers(sizes):
    """Expert layers of the main stack (the module's block is one more)."""
    return sizes["num_hidden_layers"] - sizes["first_k_dense_replace"]


# ------------------------------------------------------------ weights, data
def init_weights(key, sizes):
    """Normal(0, 1/fan_in) matrices; norm weights uniform in 0.5..1.5, so
    that a missing norm is far off; the selection bias uniform in -0.01 ..
    0.01, so that the first step's choice already reads it."""
    d, v = sizes["hidden_size"], sizes["vocab_size"]
    h = sizes["num_attention_heads"]
    rq, rkv = sizes["q_lora_rank"], sizes["kv_lora_rank"]
    dn, dr, dv = (sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"],
                  sizes["v_head_dim"])
    f, fe = sizes["intermediate_size"], sizes["moe_intermediate_size"]
    fs = sizes["n_shared_experts"] * fe
    held, published = (sizes["n_routed_experts"],
                       sizes["n_routed_experts_published"])
    dt = jnp.dtype(sizes["dtype"])
    layers = sizes["num_hidden_layers"]
    keys = iter(jax.random.split(key, 8 + 20 * (layers + 1)))

    def dense(fan_in, shape):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                / np.sqrt(fan_in)).astype(dt)

    def about_one(shape):
        return (1.0 + jax.random.uniform(next(keys), shape, jnp.float32,
                                         -0.5, 0.5)).astype(dt)

    def layer(sparse):
        p = {"attn_norm": about_one((d,)),
             "attn": {"wq_a": dense(d, (d, rq)), "q_norm": about_one((rq,)),
                      "wq_b": dense(rq, (rq, h * (dn + dr))),
                      "wkv_a": dense(d, (d, rkv + dr)),
                      "kv_norm": about_one((rkv,)),
                      "wkv_b": dense(rkv, (rkv, h * (dn + dv))),
                      "wo": dense(h * dv, (h * dv, d))},
             "mlp_norm": about_one((d,))}
        if sparse:
            p["moe"] = {
                "router": dense(d, (d, published)),
                "router_bias": jax.random.uniform(
                    next(keys), (published,), jnp.float32, -0.01, 0.01),
                "w1": dense(d, (held, d, fe)), "w3": dense(d, (held, d, fe)),
                "w2": dense(fe, (held, fe, d)),
                "shared_w1": dense(d, (d, fs)),
                "shared_w3": dense(d, (d, fs)),
                "shared_w2": dense(fs, (fs, d))}
        else:
            p["mlp"] = {"w_gate": dense(d, (d, f)), "w_up": dense(d, (d, f)),
                        "w_down": dense(f, (f, d))}
        return p

    params = {"embed": dense(d, (v, d)),
              "layers": [layer(i >= sizes["first_k_dense_replace"])
                         for i in range(layers)],
              "final_norm": about_one((d,)), "lm_head": dense(d, (d, v))}
    if sizes["num_nextn_predict_layers"] != 1:
        raise ValueError("this reference has one prediction module")
    params["mtp"] = {"embed_norm": about_one((d,)),
                     "hidden_norm": about_one((d,)),
                     "proj": dense(2 * d, (2 * d, d)),
                     "block": layer(True), "final_norm": about_one((d,))}
    return params


# ---------------------------------------------------------------- attention
def turned(x, theta):
    """x [B, T, ..., width]: pairs (2j, 2j + 1) of the last axis turned by
    ``position * theta^(-2j / width)``."""
    width = x.shape[-1]
    freqs = float(theta) ** (-np.arange(0, width, 2, dtype=np.float64)
                             / width)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * jnp.asarray(
        freqs, jnp.float32)[None]
    ang = ang.reshape((1, x.shape[1]) + (1,) * (x.ndim - 3) + (width // 2,))
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                      b * jnp.cos(ang) + a * jnp.sin(ang)],
                     axis=-1).reshape(x.shape)


def attention(q_nope, q_rope, k_nope, k_rope, vs, mm):
    """q_nope, k_nope [B,T,H,dn], q_rope [B,T,H,dr], k_rope [B,T,dr] (one
    for all heads), vs [B,T,H,dv] -> [B,T,H,dv]: a masked softmax, one
    (sequence, head, block of queries) at a time."""
    b, t, h, dn = q_nope.shape
    scale = 1.0 / np.sqrt(dn + q_rope.shape[-1])
    block = min(QUERY_BLOCK, t)
    blocks = -(-t // block)
    pad = blocks * block - t
    j = jnp.arange(t)[None, :]

    def of_block(qn, qr, start, kn, kr, vh):    # [Q,dn] [Q,dr] [] [T,..] x3
        i = start + jnp.arange(block)[:, None]
        s = (mm("qd,sd->qs", qn, kn) + mm("qd,sd->qs", qr, kr)) * scale
        s = jnp.where(j <= i, s, -jnp.inf)
        s = s - jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s)
        return mm("qs,sd->qd", p / jnp.sum(p, axis=-1, keepdims=True), vh)

    def sequence(args):
        # [H,T,dn], [H,T,dr], [H,T,dn], [T,dr], [H,T,dv]
        qn, qr, kn, kr, vh = args

        def head(a):
            qn, qr, kn, vh = a
            split = lambda y: jnp.pad(y, ((0, pad), (0, 0))).reshape(
                blocks, block, -1)
            out = jax.lax.map(
                lambda c: jax.checkpoint(of_block)(c[0], c[1], c[2], kn, kr,
                                                   vh),
                (split(qn), split(qr), jnp.arange(blocks) * block))
            return out.reshape(blocks * block, -1)[:t]

        return jax.lax.map(head, (qn, qr, kn, vh))

    heads_first = lambda y: y.transpose(0, 2, 1, 3)
    out = jax.lax.map(sequence, (heads_first(q_nope), heads_first(q_rope),
                                 heads_first(k_nope), k_rope,
                                 heads_first(vs)))
    return out.transpose(0, 2, 1, 3)


def latent_attention(p, u, sizes, mm):
    """A layer's attention of the normed ``u``."""
    b, t, _ = u.shape
    h, eps = sizes["num_attention_heads"], sizes["rms_norm_eps"]
    rkv = sizes["kv_lora_rank"]
    dn, dr = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    theta = sizes["rope_theta"]
    c_q = rms_norm(mm("btd,dr->btr", u, p["wq_a"]), p["q_norm"], eps)
    q = mm("btr,re->bte", c_q, p["wq_b"]).reshape(b, t, h, dn + dr)
    kv_a = mm("btd,dr->btr", u, p["wkv_a"])
    c_kv = rms_norm(kv_a[..., :rkv], p["kv_norm"], eps)
    kv = mm("btr,re->bte", c_kv, p["wkv_b"]).reshape(b, t, h, -1)
    o = attention(q[..., :dn], turned(q[..., dn:], theta), kv[..., :dn],
                  turned(kv_a[..., rkv:], theta), kv[..., dn:], mm)
    return mm("bte,ed->btd", o.reshape(b, t, -1), p["wo"])


# ---------------------------------------------------------------------- MLP
def route(p, x, sizes, mm):
    """[S, top_k] expert ids over all published experts, chosen by ``s +
    b``, and their weights: ``s`` over the chosen's sum, times the scaling
    factor."""
    scores = sigmoid(mm("sd,de->se", x, p["router"]))
    _, ids = jax.lax.top_k(scores + p["router_bias"],
                           sizes["num_experts_per_tok"])
    top = jnp.take_along_axis(scores, ids, axis=-1)
    return ids, (sizes["routed_scaling_factor"] * top
                 / jnp.sum(top, axis=-1, keepdims=True))


def expert_layer(p, x, sizes, mm, first_expert=None, held=None):
    """x [S, d].  ``(routed, shared, counts)``: the routed part of the
    experts ``first_expert .. first_expert + held`` (the configuration's
    share by default), the shared expert's, which every chip computes
    alike, and the assignments each of ALL published experts got."""
    first = sizes["first_expert"] if first_expert is None else first_expert
    held = sizes["n_routed_experts"] if held is None else held
    ids, weights = route(p, x, sizes, mm)
    counts = jnp.sum(ids.reshape(-1, 1) == jnp.arange(
        sizes["n_routed_experts_published"]), axis=0, dtype=jnp.int32)

    def expert(e, w1, w3, w2):          # one expert's matrices, as stored
        w1, w3, w2 = (w.astype(jnp.float32) for w in (w1, w3, w2))
        w = jnp.sum(jnp.where(ids == first + e, weights, 0.0), axis=-1)
        return w[:, None] * swiglu(x, w1, w3, w2, mm)

    # the sum is the carry, and no expert's backward pass needs it
    routed, _ = jax.lax.scan(
        lambda total, of: (total + jax.checkpoint(expert)(*of), None),
        jnp.zeros_like(x), (jnp.arange(held), p["w1"][:held], p["w3"][:held],
                            p["w2"][:held]))
    return routed, swiglu(x, p["shared_w1"], p["shared_w3"], p["shared_w2"],
                          mm), counts


# -------------------------------------------------------------------- layer
def layer(p, x, sizes, q):
    """One layer: attention behind its norm, then the MLP behind its own,
    each recomputed on its own in the backward pass.  ``(x, counts)``: the
    assignments each published expert got, or none of a dense layer.  The
    experts' stacks stay in their storage type until an expert is
    computed."""
    mm, eps = _matmul(q), sizes["rms_norm_eps"]
    p = jax.tree_util.tree_map(
        lambda w: w if w.ndim == 3 else w.astype(jnp.float32), p)
    b, t, d = x.shape

    def mixer(p, x):
        return x + latent_attention(
            p["attn"], rms_norm(x, p["attn_norm"], eps), sizes, mm)

    def mlp(p, x):
        u = rms_norm(x, p["mlp_norm"], eps).reshape(b * t, d)
        if "moe" in p:
            routed, shared, counts = expert_layer(p["moe"], u, sizes, mm)
            return x + (routed + shared).reshape(b, t, d), counts
        w = p["mlp"]
        y = swiglu(u, w["w_gate"], w["w_up"], w["w_down"], mm)
        return x + y.reshape(b, t, d), jnp.zeros((0,), jnp.int32)

    return jax.checkpoint(mlp)(p, jax.checkpoint(mixer)(p, x))


def token_losses(lm_head, x, targets, q):
    """[B, T] ``-log softmax(x lm_head)[target]``, the logits a block of
    tokens at a time."""
    def block(args):
        xb, tb = args
        logits = _matmul(q)("btd,dv->btv", xb, lm_head.astype(jnp.float32))
        logp = logits - jax.scipy.special.logsumexp(logits, axis=-1,
                                                    keepdims=True)
        return -jnp.take_along_axis(logp, tb[..., None], axis=-1)[..., 0]

    nll = jax.lax.map(jax.checkpoint(block), (blocks_of(x, TOKEN_BLOCK),
                                              blocks_of(targets, TOKEN_BLOCK)))
    b, t = targets.shape
    return jnp.moveaxis(nll, 0, 1).reshape(b, -1)[:, :t]


def below_module(top, x, targets, sizes):
    """``(g, z)``: the main stack's output behind the final norm, and the
    module's input.  ``top`` holds ``embed``, ``final_norm`` and ``mtp``
    (without its block and its last norm)."""
    eps = sizes["rms_norm_eps"]
    f32 = lambda w: w.astype(jnp.float32)
    g = rms_norm(x, f32(top["final_norm"]), eps)
    m = top["mtp"]
    both = jnp.concatenate(
        [rms_norm(embedded(top["embed"], targets), f32(m["embed_norm"]), eps),
         rms_norm(g, f32(m["hidden_norm"]), eps)], axis=-1)
    return g, jnp.einsum("bte,ed->btd", both, f32(m["proj"]))


def both_losses(lm_head, mtp_norm, g, u, targets, sizes, q):
    """``(L_main, L_mtp)``: the module's position ``i`` is held to the next
    position's target, its last position to nothing."""
    main = jnp.mean(token_losses(lm_head, g, targets, q))
    nll = token_losses(
        lm_head, rms_norm(u, mtp_norm.astype(jnp.float32),
                          sizes["rms_norm_eps"]),
        jnp.roll(targets, -1, axis=1), q)
    return main, jnp.mean(nll[:, :-1])


def top_of(params):
    return {"embed": params["embed"], "final_norm": params["final_norm"],
            "mtp": {k: v for k, v in params["mtp"].items()
                    if k not in ("block", "final_norm")}}


def loss_terms(params, tokens, targets, sizes, precision="float32"):
    """``((L_main, L_mtp), counts)`` in one traced function: for the tests'
    sizes (``gradient`` is what ``follow`` takes, and gives the same).
    ``counts`` is a list, an expert layer an entry, the module's last."""
    q = quantizer(precision)
    x = embedded(params["embed"], tokens)
    counts = []
    for p in params["layers"]:
        x, c = layer(p, x, sizes, q)
        counts += [c] if "moe" in p else []
    g, z = below_module(top_of(params), x, targets, sizes)
    u, c = layer(params["mtp"]["block"], z, sizes, q)
    return both_losses(params["lm_head"], params["mtp"]["final_norm"], g, u,
                       targets, sizes, q), counts + [c]


def loss_fn(params, tokens, targets, sizes, precision="float32"):
    (main, mtp), _ = loss_terms(params, tokens, targets, sizes, precision)
    return main + sizes["mtp_loss_weight"] * mtp


def moved_bias(bias, counts, sizes):
    """The selection bias after a step in which the experts got
    ``counts``."""
    counts = counts.astype(jnp.float32)
    return bias + sizes["bias_update_speed"] * jnp.sign(
        jnp.mean(counts) - counts)


# ------------------------------------------- the same, a layer a jitted call
@functools.lru_cache(maxsize=None)
def _pieces(sizes_items, precision):
    """The jitted pieces of ``gradient``, compiled once for a set of sizes
    (a program a shape of layer: on a dense MLP or an expert layer): a
    layer forward, a layer transposed, what lies between the main stack and
    the module forward and transposed, and both heads with their
    gradients."""
    sizes = dict(sizes_items)
    q = quantizer(precision)
    stored = lambda g, like: jax.tree_util.tree_map(
        lambda y, w: y.astype(w.dtype), g, like)

    def one(p, x):
        return layer(p, x, sizes, q)

    def layer_back(p, x, ct):
        g, ct = jax.vjp(lambda p, x: one(p, x)[0], p, x)[1](ct)
        return stored(g, p), ct

    def below(top, x, targets):
        return below_module(top, x, targets, sizes)

    def below_back(top, x, targets, ct_g, ct_z):
        """The gradient of ``top`` in float32 (the embedding's second use,
        to be added to its first) and the cotangent of ``x``."""
        _, back = jax.vjp(lambda top, x: below(top, x, targets), top, x)
        return back((ct_g, ct_z))

    def heads(lm_head, mtp_norm, g, u, targets):
        """``((L_main, L_mtp), gradients of L)`` in (the head, the module's
        last norm, ``g``, ``u``)."""
        def total(lm_head, mtp_norm, g, u):
            main, mtp = both_losses(lm_head, mtp_norm, g, u, targets, sizes,
                                    q)
            return main + sizes["mtp_loss_weight"] * mtp, (main, mtp)

        (_, terms), grads = jax.value_and_grad(
            total, argnums=(0, 1, 2, 3), has_aux=True)(lm_head, mtp_norm, g,
                                                       u)
        return terms, grads

    def scatter(embed, tokens, ct, second):
        return (second + jnp.zeros(embed.shape, jnp.float32).at[tokens].add(
            ct)).astype(embed.dtype)

    return {"embed": jax.jit(embedded), "layer": jax.jit(one),
            "layer_back": jax.jit(layer_back), "below": jax.jit(below),
            "below_back": jax.jit(below_back), "heads": jax.jit(heads),
            "scatter": jax.jit(scatter)}


def gradient(pieces, params, tokens, targets, sizes):
    """``((L_main, L_mtp), gradient of L, counts)`` at ``params`` for one
    batch, the gradient in the weights' storage type, ``counts`` an array
    an expert layer (the module's last)."""
    x = pieces["embed"](params["embed"], tokens)
    entered, counts = [], []
    for p in params["layers"]:
        entered.append(x)
        x, c = pieces["layer"](p, x)
        counts += [c] if "moe" in p else []
    top = top_of(params)
    g, z = pieces["below"](top, x, targets)
    u, c = pieces["layer"](params["mtp"]["block"], z)
    counts.append(c)
    terms, (lm_head, mtp_norm, ct_g, ct_u) = pieces["heads"](
        params["lm_head"], params["mtp"]["final_norm"], g, u, targets)
    del g, u
    block, ct_z = pieces["layer_back"](params["mtp"]["block"], z, ct_u)
    g_top, ct = pieces["below_back"](top, x, targets, ct_g, ct_z)
    del x, z, ct_g, ct_u, ct_z
    layers = []
    for p in reversed(params["layers"]):
        g, ct = pieces["layer_back"](p, entered.pop(), ct)
        layers.append(g)
    as_stored = lambda y, w: y.astype(w.dtype)
    grads = {"embed": pieces["scatter"](params["embed"], tokens, ct,
                                        g_top["embed"]),
             "layers": layers[::-1],
             "final_norm": as_stored(g_top["final_norm"],
                                     params["final_norm"]),
             "lm_head": as_stored(lm_head, params["lm_head"]),
             "mtp": {**jax.tree_util.tree_map(as_stored, g_top["mtp"],
                                              top["mtp"]),
                     "block": block,
                     "final_norm": as_stored(mtp_norm,
                                             params["mtp"]["final_norm"])}}
    return tuple(float(v) for v in terms), grads, counts


# -------------------------------------------------------------- three steps
@functools.lru_cache(maxsize=None)
def _programs(sizes_items):
    sizes = dict(sizes_items)
    add = lambda a, b: jax.tree_util.tree_map(jnp.add, a, b)

    def move(params, counts):
        """Every expert layer's bias moved by its row of ``counts``."""
        rows = iter(counts)

        def moved(p):
            if "moe" not in p:
                return p
            return {**p, "moe": {**p["moe"], "router_bias": moved_bias(
                p["moe"]["router_bias"], next(rows), sizes)}}

        return {**params, "layers": [moved(p) for p in params["layers"]],
                "mtp": {**params["mtp"],
                        "block": moved(params["mtp"]["block"])}}

    return (jax.jit(lambda k: init_weights(k, sizes)),
            jax.jit(lambda k, r: make_batch(k, sizes, r)),
            jax.jit(add, donate_argnums=(0, 1)),
            jax.jit(adam_step, donate_argnums=(0, 2, 3)),
            jax.jit(move, donate_argnums=(0,)))


def follow(sizes, key, world, steps, precision="float32"):
    """The first ``steps`` synchronous data-parallel steps at the seeded
    weights: per-rank losses, the norm of the first averaged gradient and
    of the parameters' change, leaf by leaf (``reference/laguna.py``'s
    ``follow``, with this model): a rank's sequences one at a time, their
    gradients added up in the gradients' storage type, as the ranks' are,
    and their counts added up, which move the bias after Adam's update."""
    weights, batch, add, update, move = _programs(scalars(sizes))
    pieces = _pieces(scalars(sizes), precision)
    weight = sizes["mtp_loss_weight"]
    with jax.default_matmul_precision("highest"):
        params = weights(key)
        mu = jax.tree_util.tree_map(jnp.zeros_like, params)
        nu = jax.tree_util.tree_map(jnp.zeros_like, params)
        losses = [[] for _ in range(world)]
        terms = []
        first = None
        for step in range(1, steps + 1):
            mean, counts, parts = None, None, 0
            for r in range(world):
                tokens, targets = batch(key, r)
                of_rank = []
                for b in range(tokens.shape[0]):    # equally long: the mean
                    (main, mtp), g, c = gradient(
                        pieces, params, tokens[b:b + 1], targets[b:b + 1],
                        sizes)
                    mean = g if mean is None else add(mean, g)
                    counts = c if counts is None else [
                        x + y for x, y in zip(counts, c)]
                    of_rank.append(main + weight * mtp)
                    terms.append((main, mtp))
                    parts += 1
                losses[r].append(sum(of_rank) / len(of_rank))
            del g
            if parts > 1:
                mean = jax.tree_util.tree_map(lambda x: x / parts, mean)
            if first is None:
                first = leaf_norms(mean)
            params, mu, nu = update(params, mean, mu, nu, step)
            del mean
            params = move(params, counts)
        delta = leaf_norms(params, minus=weights(key))
    return {"losses": losses, "grad_norms": first, "delta_norms": delta,
            "loss_terms": terms}
