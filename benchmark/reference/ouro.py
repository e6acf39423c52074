"""Plain float32 Ouro looped decoder for the benchmark's ``correct``: one
stage of a pipeline that is run round ``total_ut_steps`` times.

``jax.numpy`` only, nothing imported from ``horovod_tpu``.  The equations
are written from the published ``ouro`` ``config.json`` and the paper
("Scaling Latent Reasoning via Looped Language Models"); what the config
does not settle is marked *assumed* (the configuration file lists the same).
With ``R = total_ut_steps``, ``L`` layers, positions ``0..T-1`` in every
pass:

- ``RMSNorm(x; w) = x / rms(x) * w`` (a plain weight), eps from the config.
- ``x(0) = E[tokens]``; pass ``r = 1..R`` runs **the same** ``L`` layers on
  ``x(r-1)``: ``a = RMSNorm(h; g1)``; ``q, k, v = a Wq, a Wk, a Wv`` (heads
  of ``head_dim`` with keys of their own); rotary on the whole head of q
  and k (half-split pairs, ``rope_theta``, no scaling); causal softmax
  attention over masked scores, scale ``head_dim ** -0.5``; ``h = h +
  RMSNorm(o Wo; g2)``; ``m = RMSNorm(h; g3)``; ``h = h + RMSNorm((silu(m
  Wg) * m Wu) Wd; g4)`` — four norms a layer, on the input and the output
  of both sublayers (*assumed*: the published modelling code's
  ``input_layernorm_2`` / ``post_attention_layernorm_2``).
- ``x(r) = RMSNorm(h; g_f)``: the final norm closes every pass and its
  output starts the next (*assumed*, as the modelling code has it).
- after every pass a head and a gate read ``x(r)``: ``nll_i(r) = -log
  softmax(x_i(r) W_head)[target_i]``, ``lam_i(r) = sigmoid(x_i(r) . w_gate
  + b_gate)`` (*assumed*: ``Linear(hidden, 1)`` with its bias, one gate for
  all passes).
- ``p_i(r) = lam_i(r) prod_{j<r} (1 - lam_i(j))`` for ``r < R``; the last
  pass takes the remainder ``prod_{j<R} (1 - lam_i(j))`` and its own gate
  is not read.
- ``loss = mean_i [sum_r p_i(r) nll_i(r) - beta H(p_i)]`` with ``H(p) =
  -sum_r p(r) log p(r)`` and ``beta`` = ``entropy_beta`` (*assumed* 0.05).

Parameters are a dict in the layout the system under test uses (a layout,
not code): ``embed``, ``layers`` (one dict of arrays stacked over the
layers), ``final_norm``, ``gate`` (``w``, ``b``), ``lm_head``.  Weights and
data of a run are made HERE from the seed, in the configuration's storage
type; every operation computes in float32 (``follow`` sets ``highest``
matmul precision).

``loss_fn`` is the whole of it in one traced function: a Python loop over
passes and layers, nothing recomputed, for the tests' sizes.  At the
cell's sizes 64 layer applications of 8192 float32 tokens do not fit in
one program beside the state, so ``follow`` takes the same functions a
piece at a time (``add_gradient``): one jitted call a layer application,
forward through all passes, then backward pass by pass from the last —
a pass's layer inputs are made again from the state that entered the pass,
each application's backward (``jax.vjp`` of the layer alone) adds its
weights' gradient to the running total at that layer's place, so that the
four contributions to a shared weight are four additions written out here,
into a float32 total (``follow``).
Attention is computed one (head, block of queries) at a time and the
head's logits a block of tokens at a time.

``precision`` other than ``float32`` rounds the operands of every matrix
product in both passes (``common.quantizer``): the control.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .common import leaf_norms, quantizer
from .llama import ADAM, adam_step, rope    # noqa: F401  (ADAM: the family's)
from .olmo_hybrid import blocks_of
from .qwen3_next import (attention, make_batch,      # noqa: F401
                         rms, sigmoid, silu)
from .resnet import scalars

TOKEN_BLOCK = 2048      # tokens of the head's logits taken together
GATE_SCALE = 0.25       # the gate's logit on a normed state spreads by this
GATE_BIAS = -0.6        # ... round this: lam round 0.35


# ------------------------------------------------------------ weights, data
def init_weights(key, sizes):
    """Normal(0, 1/fan_in) matrices stacked over the layers and a Normal(0,
    1) embedding, so that the residual stream starts at the size the normed
    sublayer outputs add to it.  Norm weights uniform in 0.5..1.5, so that
    a missing norm is far off.  The gate's weight Normal(0, GATE_SCALE^2 /
    hidden) and its bias ``GATE_BIAS``: on a normed state its logit spreads
    by about 0.15 from token to token round a mean that differs from pass
    to pass by about 0.3 (the part of ``x(r)`` that all tokens share, much
    the same in the later passes), so ``lam`` spreads round 0.35, where the
    four exits' shares are most even (0.35, 0.23, 0.15, 0.27), and every
    pass keeps its share on every seed.  (At Normal(0, 1/hidden) and no
    bias a pass's mean logit read -1.7 on one seed of two and its mean
    ``p(r)`` 0.07; at Normal(0, 0.16/hidden) the last pass's read 0.054 on
    one seed of nine: three gates above a half leave it little.)"""
    d, v, n = sizes["hidden_size"], sizes["vocab_size"], sizes[
        "num_hidden_layers"]
    f = sizes["intermediate_size"]
    e = sizes["num_attention_heads"] * sizes["head_dim"]
    dt = jnp.dtype(sizes["dtype"])
    keys = iter(jax.random.split(key, 16))

    def dense(fan_in, shape):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                / np.sqrt(fan_in)).astype(dt)

    def about_one(shape):
        return jax.random.uniform(next(keys), shape, jnp.float32,
                                  0.5, 1.5).astype(dt)

    layers = {"attn_norm": about_one((n, d)), "wq": dense(d, (n, d, e)),
              "wk": dense(d, (n, d, e)), "wv": dense(d, (n, d, e)),
              "wo": dense(e, (n, e, d)), "attn_out_norm": about_one((n, d)),
              "mlp_norm": about_one((n, d)), "w_gate": dense(d, (n, d, f)),
              "w_up": dense(d, (n, d, f)), "w_down": dense(f, (n, f, d)),
              "mlp_out_norm": about_one((n, d))}
    return {"embed": dense(1, (v, d)), "layers": layers,
            "final_norm": about_one((d,)),
            "gate": {"w": dense(d / GATE_SCALE ** 2, (d,)),
                     "b": jnp.full((), GATE_BIAS, dt)},
            "lm_head": dense(d, (d, v))}


# ------------------------------------------------------------------ forward
def rms_norm(x, w, eps):
    return x / rms(x, eps) * w


def _matmul(q):
    return lambda spec, a, b: q.result(
        jnp.einsum(spec, q.operand(a), q.operand(b)))


def f32(tree):
    return jax.tree_util.tree_map(lambda w: w.astype(jnp.float32), tree)


def layer(p, x, sizes, mm):
    """One application of one layer to ``x [B, T, hidden]``; ``p`` holds
    that layer's weights."""
    p = f32(p)
    b, t, _ = x.shape
    h, hd = sizes["num_attention_heads"], sizes["head_dim"]
    eps, theta = sizes["rms_norm_eps"], sizes["rope_theta"]
    heads = lambda y: y.reshape(b, t, h, hd)
    a = rms_norm(x, p["attn_norm"], eps)
    qs = rope(heads(mm("btd,de->bte", a, p["wq"])), theta)
    ks = rope(heads(mm("btd,de->bte", a, p["wk"])), theta)
    vs = heads(mm("btd,de->bte", a, p["wv"]))
    o = attention(qs, ks, vs, mm).reshape(b, t, h * hd)
    x = x + rms_norm(mm("bte,ed->btd", o, p["wo"]), p["attn_out_norm"], eps)
    m = rms_norm(x, p["mlp_norm"], eps)
    y = mm("btf,fd->btd", silu(mm("btd,df->btf", m, p["w_gate"]))
           * mm("btd,df->btf", m, p["w_up"]), p["w_down"])
    return x + rms_norm(y, p["mlp_out_norm"], eps)


def close(final_norm, h, sizes):
    """The norm that ends a pass."""
    return rms_norm(h, final_norm.astype(jnp.float32), sizes["rms_norm_eps"])


def read(head, x, targets, sizes, mm):
    """What the head and the gate read after a pass: each token's loss and
    the gate's logit, both ``[B, T]``; the logits a block of tokens at a
    time."""
    head = f32(head)

    def block(args):
        xb, tb = args
        logits = mm("btd,dv->btv", xb, head["lm_head"])
        logp = logits - jax.scipy.special.logsumexp(logits, axis=-1,
                                                    keepdims=True)
        return -jnp.take_along_axis(logp, tb[..., None], axis=-1)[..., 0]

    t = x.shape[1]
    nll = jax.lax.map(jax.checkpoint(block), (blocks_of(x, TOKEN_BLOCK),
                                              blocks_of(targets, TOKEN_BLOCK)))
    nll = jnp.moveaxis(nll, 0, 1).reshape(x.shape[0], -1)[:, :t]
    return nll, mm("btd,d->bt", x, head["gate"]["w"]) + head["gate"]["b"]


def exit_distribution(z):
    """``p [R, ...]`` from the gates' logits ``z [R, ...]``."""
    passes = z.shape[0]
    left, p = jnp.ones_like(z[0]), []
    for r in range(passes - 1):
        lam = sigmoid(z[r])
        p.append(lam * left)
        left = left * (1.0 - lam)
    return jnp.stack(p + [left])        # the last pass takes the remainder


def mixture(nll, z, beta):
    """The expected-exit loss from ``nll`` and ``z``, both ``[R, B, T]``."""
    p = exit_distribution(z)
    entropy = -jnp.sum(p * jnp.log(p), axis=0)
    return jnp.mean(jnp.sum(p * nll, axis=0) - beta * entropy)


def head_of(params):
    return {"lm_head": params["lm_head"], "gate": params["gate"]}


def layer_at(layers, l):
    return jax.tree_util.tree_map(lambda w: w[l], layers)


def exits(params, tokens, targets, sizes, precision="float32"):
    """``(nll, z)``, each ``[R, B, T]``: a Python loop over passes and
    layers in one traced function.  For the tests' sizes."""
    mm = _matmul(quantizer(precision))
    x = params["embed"].astype(jnp.float32)[tokens]
    nll, z = [], []
    for _ in range(sizes["total_ut_steps"]):        # the same weights
        for l in range(sizes["num_hidden_layers"]):
            x = layer(layer_at(params["layers"], l), x, sizes, mm)
        x = close(params["final_norm"], x, sizes)
        of_pass = read(head_of(params), x, targets, sizes, mm)
        nll.append(of_pass[0])
        z.append(of_pass[1])
    return jnp.stack(nll), jnp.stack(z)


def loss_fn(params, tokens, targets, sizes, precision="float32"):
    return mixture(*exits(params, tokens, targets, sizes, precision),
                   sizes["entropy_beta"])


# --------------------------------------- the same, a piece at a time
@functools.lru_cache(maxsize=None)
def _pieces(sizes_items, precision):
    """The jitted pieces of ``add_gradient``, compiled once for a set of
    sizes: a layer application forward, and backward into the running
    total; the norm that closes a pass; the head and gate; the mixture."""
    sizes = dict(sizes_items)
    mm = _matmul(quantizer(precision))
    one = functools.partial(layer, sizes=sizes, mm=mm)
    end = functools.partial(close, sizes=sizes)
    look = functools.partial(read, sizes=sizes, mm=mm)
    take = lambda layers, l: jax.tree_util.tree_map(
        lambda w: jax.lax.dynamic_index_in_dim(w, l, keepdims=False), layers)

    def added(total, g):
        return jax.tree_util.tree_map(
            lambda t, y: t + y.astype(t.dtype), total, g)

    def layer_back(layers, l, x, ct, total):
        g, ct = jax.vjp(one, take(layers, l), x)[1](ct)
        total = jax.tree_util.tree_map(
            lambda t, y: jax.lax.dynamic_update_index_in_dim(
                t, jax.lax.dynamic_index_in_dim(t, l, keepdims=False)
                + y.astype(t.dtype), l, 0), total, g)
        return ct, total

    def close_back(final_norm, h, ct, total):
        g, ct = jax.vjp(end, final_norm, h)[1](ct)
        return ct, added(total, g)

    def read_back(head, x, targets, ct_nll, ct_z, ct_next, total):
        g, ct = jax.vjp(lambda w, y: look(w, y, targets), head, x)[1](
            (ct_nll, ct_z))
        return ct + ct_next, added(total, g)

    def embed_back(tokens, ct, total):
        return total.at[tokens].add(ct.astype(total.dtype))

    return {
        "embed": jax.jit(lambda e, tokens: e.astype(jnp.float32)[tokens]),
        "layer": jax.jit(lambda layers, l, x: one(take(layers, l), x)),
        "layer_back": jax.jit(layer_back, donate_argnums=(4,)),
        "close": jax.jit(end),
        "close_back": jax.jit(close_back, donate_argnums=(3,)),
        "read": jax.jit(look),
        "read_back": jax.jit(read_back, donate_argnums=(6,)),
        "mixture": jax.jit(jax.value_and_grad(functools.partial(
            mixture, beta=sizes["entropy_beta"]), argnums=(0, 1))),
        "embed_back": jax.jit(embed_back, donate_argnums=(2,)),
    }


def add_gradient(pieces, total, params, tokens, targets, sizes):
    """``(loss, total + gradient)`` of ``loss_fn`` at ``params`` for one
    batch, by the pieces: ``total`` is a tree like ``params`` and is given
    up."""
    n, passes = sizes["num_hidden_layers"], sizes["total_ut_steps"]
    layers, head = params["layers"], head_of(params)
    sums = {"head": head_of(total), "layers": total["layers"],
            "final_norm": total["final_norm"], "embed": total["embed"]}

    def run(x):                         # a pass's layer inputs, and its h
        entered = []
        for l in range(n):
            entered.append(x)
            x = pieces["layer"](layers, l, x)
        return entered, x

    # forward: keep what enters each pass, its h and its closed state
    x = pieces["embed"](params["embed"], tokens)
    starts, hs, xs, nll, z = [], [], [], [], []
    for _ in range(passes):
        starts.append(x)
        h = run(x)[1]
        x = pieces["close"](params["final_norm"], h)
        of_pass = pieces["read"](head, x, targets)
        hs.append(h), xs.append(x)
        nll.append(of_pass[0]), z.append(of_pass[1])
    loss, (ct_nll, ct_z) = pieces["mixture"](jnp.stack(nll), jnp.stack(z))
    # backward: from the last pass; what reaches x(r) is what its own head
    # and gate send and what pass r + 1 sends back through its layers
    ct = jnp.zeros_like(x)
    for r in reversed(range(passes)):
        ct, sums["head"] = pieces["read_back"](
            head, xs[r], targets, ct_nll[r], ct_z[r], ct, sums["head"])
        ct, sums["final_norm"] = pieces["close_back"](
            params["final_norm"], hs[r], ct, sums["final_norm"])
        entered = run(starts[r])[0]
        for l in reversed(range(n)):    # a shared weight's four additions
            ct, sums["layers"] = pieces["layer_back"](
                layers, l, entered.pop(), ct, sums["layers"])
    sums["embed"] = pieces["embed_back"](tokens, ct, sums["embed"])
    return float(loss), {"embed": sums["embed"], "layers": sums["layers"],
                         "final_norm": sums["final_norm"],
                         "gate": sums["head"]["gate"],
                         "lm_head": sums["head"]["lm_head"]}


# -------------------------------------------------------------- three steps
@functools.lru_cache(maxsize=None)
def _programs(sizes_items):
    sizes = dict(sizes_items)
    return (jax.jit(lambda k: init_weights(k, sizes)),
            jax.jit(lambda k, r: make_batch(k, sizes, r)),
            jax.jit(adam_step, donate_argnums=(0, 2, 3)))


def follow(sizes, key, world, steps, precision="float32"):
    """The first ``steps`` synchronous data-parallel steps at the seeded
    weights: per-rank losses, the norm of the first averaged gradient and
    of the parameters' change, leaf by leaf (``reference/olmo_hybrid.py``'s
    ``follow``, with this model): a rank's sequences one at a time.  The
    running total of the gradient is float32 whatever the weights' type, so
    that the four contributions to a shared weight, and the ranks' and
    sequences' on top of them, are summed unrounded: the program sums them
    in the weights' type, and the gap says what that costs."""
    weights, batch, update = _programs(scalars(sizes))
    pieces = _pieces(scalars(sizes), precision)
    with jax.default_matmul_precision("highest"):
        params = weights(key)
        mu = jax.tree_util.tree_map(jnp.zeros_like, params)
        nu = jax.tree_util.tree_map(jnp.zeros_like, params)
        losses = [[] for _ in range(world)]
        first = None
        for step in range(1, steps + 1):
            mean = jax.tree_util.tree_map(
                lambda w: jnp.zeros(w.shape, jnp.float32), params)
            for r in range(world):
                tokens, targets = batch(key, r)
                of_rank = []
                for b in range(tokens.shape[0]):    # equally long: the mean
                    loss, mean = add_gradient(
                        pieces, mean, params, tokens[b:b + 1],
                        targets[b:b + 1], sizes)
                    of_rank.append(loss)
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
