"""Plain float32 Olmo-Hybrid decoder for the benchmark's ``correct``: one
stage of a pipeline (one period of the layer pattern) with its slice of the
vocabulary.

``jax.numpy`` only, nothing imported from ``horovod_tpu``.  The equations
are written from the published ``olmo_hybrid`` ``config.json``; what it
does not settle is marked *assumed* (the configuration file lists the same):

- ``RMSNorm(x; w) = x / rms(x) * w`` (a plain weight), eps from the config.
- block ``i``: ``h = x + RMSNorm(Mixer_i(x); w_mixer)``, ``y = h +
  RMSNorm(MLP(h); w_mlp)`` — the norm on the sublayer's **output**
  (*assumed*: the ``olmo2`` / ``olmo3`` convention); ``MLP(h) = (silu(h
  W_gate) * h W_up) W_down``; logits ``= RMSNorm(x_L; w_final) W_head``.
- layer ``i`` is a full-attention layer when ``(i + 1) %
  full_attention_interval == 0`` (the published ``layer_types``) and a
  Gated DeltaNet layer otherwise.
- **Full attention**: ``q = RMSNorm(x W_q; w_q)``, ``k = RMSNorm(x W_k;
  w_k)`` over all columns (*assumed*, the same convention), ``v = x W_v``;
  heads of ``hidden / heads``; **no rotary** (``rope_parameters.rope_theta``
  is null: *assumed* to mean none); causal softmax attention, scale
  ``head_dim ** -0.5``, a block of queries at a time; ``W_o``.
- **Gated DeltaNet** (*assumed*: the reference layer the ``linear_*`` keys
  name, with its output gate and norm): ``[q|k|v|z] = x W_qkvz``, ``[b|a] =
  x W_ba``; a causal depthwise convolution (no bias) over ``[q|k|v]``, then
  SiLU; q and k L2-normalised a head, q scaled by ``1/sqrt(dk)``; ``beta =
  2 sigmoid(b)`` where ``linear_allow_neg_eigval`` (else ``sigmoid(b)``),
  ``g = -exp(A_log) * softplus(a + dt_bias)``; per head ``S <- exp(g_t) S;
  u_t = beta_t (v_t - S^T k_t); S <- S + k_t u_t^T; o_t = S^T q_t`` with
  ``S`` ``dk x dv`` — computed HERE as that **token-by-token recurrence**
  (``reference/qwen3_next.py``'s ``recurrence``: a ``lax.scan`` over t,
  recomputed in segments in the backward pass), so that the program's
  chunked algebra is checked against something that does not share it;
  then ``w_n * o / rms(o) * SiLU(z)`` and ``W_o``.
- loss: mean next-token cross-entropy over the vocabulary slice.

Parameters are a dict in the layout the system under test uses (a layout,
not code).  Weights and data of a run are made HERE from the seed, in the
configuration's storage type; every operation computes in float32
(``follow`` sets ``highest`` matmul precision).  Each mixer and each MLP
is recomputed in the backward pass, attention is computed one (head, block
of queries) at a time, the MLP and the head's logits and loss a block of
tokens at a time, and ``follow`` takes a rank's sequences one at a time, so
that the float32 activations of 16384 tokens fit beside the state.
``precision`` other than ``float32`` rounds the operands of every matrix
product and of the convolution in both passes (``common.quantizer``): the
control.  Of the recurrence's products q, k and v are the rounded operands;
its state stays float32, as an accumulator.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .common import leaf_norms, quantizer
from .llama import ADAM, adam_step    # noqa: F401  (ADAM: the family's too)
from .qwen3_next import (attention, make_batch,      # noqa: F401
                         recurrence, rms, sigmoid, silu)
from .resnet import scalars

TOKEN_BLOCK = 2048      # tokens of the MLP, and of the head's logits
KEY_HEADS = 6           # key heads of a Gated DeltaNet layer taken together


def is_full_attention(i, sizes):
    return (i + 1) % sizes["full_attention_interval"] == 0


# ------------------------------------------------------------ weights, data
def init_weights(key, sizes):
    """Normal(0, 1/fan_in) matrices and a Normal(0, 1) embedding, so that
    every sublayer sees inputs of unit size (the block has no norm before a
    sublayer) and ``b = x W_ba`` spreads over a few units: half of all
    betas exceed 1 and the largest come close to 2.  Norm weights uniform
    in 0.5..1.5, so that a missing norm is far off.  ``A_log`` and
    ``dt_bias`` as ``reference/qwen3_next.py`` draws them: a head's decay
    ``exp(g)`` at ``a = 0`` is log-uniform over heads between 0.9 and
    0.999, so state crosses many chunks."""
    d, v, n = sizes["hidden_size"], sizes["vocab_size"], sizes[
        "num_hidden_layers"]
    f = sizes["intermediate_size"]
    hk, hv, dk, dv = (sizes["linear_num_key_heads"],
                      sizes["linear_num_value_heads"],
                      sizes["linear_key_head_dim"],
                      sizes["linear_value_head_dim"])
    taps = sizes["linear_conv_kernel_dim"]
    dt = jnp.dtype(sizes["dtype"])
    keys = iter(jax.random.split(key, 2 + 16 * n))

    def dense(fan_in, shape):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                / np.sqrt(fan_in)).astype(dt)

    def about_one(shape):
        return jax.random.uniform(next(keys), shape, jnp.float32,
                                  0.5, 1.5).astype(dt)

    def gdn():
        # decay at a = 0 is exp(-A * softplus(dt_bias)) = exp(-rate)
        rate = jnp.exp(jax.random.uniform(
            next(keys), (hv,), jnp.float32, np.log(0.001), np.log(0.1)))
        a = jax.random.uniform(next(keys), (hv,), jnp.float32, 0.5, 2.0)
        return {"w_qkvz": dense(d, (d, 2 * hk * dk + 2 * hv * dv)),
                "w_ba": dense(d, (d, 2 * hv)),
                "conv": dense(taps, (taps, 2 * hk * dk + hv * dv)),
                "A_log": jnp.log(a).astype(dt),
                "dt_bias": jnp.log(jnp.expm1(rate / a)).astype(dt),
                "out_norm": about_one((dv,)),
                "wo": dense(hv * dv, (hv * dv, d))}

    def attn():
        return {"wq": dense(d, (d, d)), "wk": dense(d, (d, d)),
                "wv": dense(d, (d, d)), "q_norm": about_one((d,)),
                "k_norm": about_one((d,)), "wo": dense(d, (d, d))}

    layers = []
    for i in range(n):
        full = is_full_attention(i, sizes)
        layers.append({"attn" if full else "gdn": attn() if full else gdn(),
                       "mixer_norm": about_one((d,)),
                       "mlp": {"w_gate": dense(d, (d, f)),
                               "w_up": dense(d, (d, f)),
                               "w_down": dense(f, (f, d))},
                       "mlp_norm": about_one((d,))})
    return {"embed": dense(1, (v, d)), "layers": layers,
            "final_norm": about_one((d,)), "lm_head": dense(d, (d, v))}


# ------------------------------------------------------------------ forward
def rms_norm(x, w, eps):
    return x / rms(x, eps) * w


def gated_delta_net(p, x, sizes, mm, q):
    """The layer, ``KEY_HEADS`` key heads (with the value heads they serve)
    at a time: heads meet only in ``W_o``, so a group's columns of the
    projections, the convolution and the gates give its part of the
    output, and the parts add up.  The sum is the carry of a scan and each
    group is recomputed in the backward pass: float32 intermediates of
    16384 tokens over all 30 heads would not fit beside the state."""
    hk, hv, dk, dv = (sizes["linear_num_key_heads"],
                      sizes["linear_num_value_heads"],
                      sizes["linear_key_head_dim"],
                      sizes["linear_value_head_dim"])
    kh = max(n for n in range(1, KEY_HEADS + 1) if hk % n == 0)
    groups, rep = hk // kh, hv // hk

    def columns(w, first, heads, dim):
        """[..., first + heads * dim ...] -> [groups, ..., a group's]."""
        w = w[..., first:first + heads * dim]
        w = w.reshape(w.shape[:-1] + (groups, heads // groups * dim))
        return jnp.moveaxis(w, -2, 0)

    of_group = {
        "w_q": columns(p["w_qkvz"], 0, hk, dk),
        "w_k": columns(p["w_qkvz"], hk * dk, hk, dk),
        "w_v": columns(p["w_qkvz"], 2 * hk * dk, hv, dv),
        "w_z": columns(p["w_qkvz"], 2 * hk * dk + hv * dv, hv, dv),
        "conv_q": columns(p["conv"], 0, hk, dk),
        "conv_k": columns(p["conv"], hk * dk, hk, dk),
        "conv_v": columns(p["conv"], 2 * hk * dk, hv, dv),
        "w_b": columns(p["w_ba"], 0, hv, 1),
        "w_a": columns(p["w_ba"], hv, hv, 1),
        "A_log": columns(p["A_log"], 0, hv, 1),
        "dt_bias": columns(p["dt_bias"], 0, hv, 1),
        "wo": p["wo"].reshape(groups, hv // groups * dv, -1)}

    def group(w):
        return heads_of_gated_delta_net(w, p["out_norm"], x, kh, kh * rep,
                                        sizes, mm, q)

    out, _ = jax.lax.scan(
        lambda total, w: (total + jax.checkpoint(group)(w), None),
        jnp.zeros_like(x), of_group)
    return out


def heads_of_gated_delta_net(w, out_norm, x, hk, hv, sizes, mm, q):
    """What ``hk`` key heads and their ``hv`` value heads add to the
    layer's output; ``w`` holds their columns (and ``wo`` their rows)."""
    b, t, _ = x.shape
    dk, dv = sizes["linear_key_head_dim"], sizes["linear_value_head_dim"]
    taps = sizes["linear_conv_kernel_dim"]

    def conv(y, kernel):
        # causal and depthwise: tap j weighs the input taps-1-j back
        padded = q.operand(jnp.pad(y, ((0, 0), (taps - 1, 0), (0, 0))))
        return silu(q.result(sum(q.operand(kernel[j]) * padded[:, j:j + t]
                                 for j in range(taps))))

    split = lambda y, heads, dim: y.reshape(b, t, heads, dim)
    unit = lambda y: y / jnp.sqrt(
        jnp.sum(jnp.square(y), axis=-1, keepdims=True) + 1e-6)
    project = lambda name: mm("btd,de->bte", x, w[name])
    qs = split(conv(project("w_q"), w["conv_q"]), hk, dk)
    ks = split(conv(project("w_k"), w["conv_k"]), hk, dk)
    vs = split(conv(project("w_v"), w["conv_v"]), hv, dv)
    qs = jnp.repeat(unit(qs), hv // hk, axis=2) / np.sqrt(dk)
    ks = jnp.repeat(unit(ks), hv // hk, axis=2)
    beta = sigmoid(project("w_b"))
    if sizes["linear_allow_neg_eigval"]:
        beta = 2.0 * beta               # I - beta k k^T may reach -1
    g = -jnp.exp(w["A_log"]) * jax.nn.softplus(project("w_a") + w["dt_bias"])
    # the rule's products (S^T k, k u^T, S^T q) take q, k and v as operands
    o = q.result(recurrence(q.operand(qs), q.operand(ks), q.operand(vs),
                            g, beta))
    o = out_norm * (o / rms(o, sizes["rms_norm_eps"])) * silu(
        split(project("w_z"), hv, dv))
    return mm("bte,ed->btd", o.reshape(b, t, hv * dv), w["wo"])


def full_attention(p, x, sizes, mm):
    b, t, d = x.shape
    h, eps = sizes["num_attention_heads"], sizes["rms_norm_eps"]
    heads = lambda y: y.reshape(b, t, h, d // h)
    qs = rms_norm(mm("btd,de->bte", x, p["wq"]), p["q_norm"], eps)
    ks = rms_norm(mm("btd,de->bte", x, p["wk"]), p["k_norm"], eps)
    vs = mm("btd,de->bte", x, p["wv"])
    o = attention(heads(qs), heads(ks), heads(vs), mm)      # no rotary
    return mm("bte,ed->btd", o.reshape(b, t, d), p["wo"])


def blocks_of(x, block):
    """x [B, T, ...] -> [T / block, B, block, ...] (T padded with zeros)."""
    pad = (-x.shape[1]) % block
    x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
    return jnp.moveaxis(
        x.reshape((x.shape[0], -1, block) + x.shape[2:]), 1, 0)


def _mixer(p, x, sizes, mm, q):
    p = jax.tree_util.tree_map(lambda w: w.astype(jnp.float32), p)
    y = (full_attention(p["attn"], x, sizes, mm) if "attn" in p
         else gated_delta_net(p["gdn"], x, sizes, mm, q))
    return x + rms_norm(y, p["mixer_norm"], sizes["rms_norm_eps"])


def _mlp(p, x, sizes, mm):
    p = jax.tree_util.tree_map(lambda w: w.astype(jnp.float32), p)

    def block(xb):                      # the MLP acts on a token alone
        w = p["mlp"]
        y = mm("btf,fd->btd", silu(mm("btd,df->btf", xb, w["w_gate"]))
               * mm("btd,df->btf", xb, w["w_up"]), w["w_down"])
        return xb + rms_norm(y, p["mlp_norm"], sizes["rms_norm_eps"])

    t = x.shape[1]
    out = jax.lax.map(jax.checkpoint(block), blocks_of(x, TOKEN_BLOCK))
    return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], -1, x.shape[2])[:, :t]


def _matmul(q):
    return lambda spec, a, b: q.result(
        jnp.einsum(spec, q.operand(a), q.operand(b)))


def hidden(params, tokens, sizes, q):
    """The last layer's output ``[B, T, hidden]``, before the final norm."""
    mm = _matmul(q)
    x = params["embed"].astype(jnp.float32)[tokens]
    for p in params["layers"]:      # each half recomputed on its own
        mlp = {"mlp_norm": p["mlp_norm"], "mlp": p["mlp"]}
        mixer = {k: v for k, v in p.items() if k not in mlp}
        x = jax.checkpoint(functools.partial(
            _mixer, sizes=sizes, mm=mm, q=q))(mixer, x)
        x = jax.checkpoint(functools.partial(
            _mlp, sizes=sizes, mm=mm))(mlp, x)
    return x


def logits_of(params, x, sizes, q):
    return _matmul(q)("btd,dv->btv", rms_norm(
        x, params["final_norm"].astype(jnp.float32), sizes["rms_norm_eps"]),
        params["lm_head"].astype(jnp.float32))


def forward(params, tokens, sizes):
    """Logits ``[B, T, vocab]``, whole: for the tests' sizes."""
    q = quantizer("float32")
    return logits_of(params, hidden(params, tokens, sizes, q), sizes, q)


def loss_fn(params, tokens, targets, sizes, precision="float32"):
    q = quantizer(precision)
    x = hidden(params, tokens, sizes, q)

    def block(args):                    # the summed loss of a block
        xb, tb, real = args
        logits = logits_of(params, xb, sizes, q)
        logp = logits - jax.scipy.special.logsumexp(logits, axis=-1,
                                                    keepdims=True)
        picked = jnp.take_along_axis(logp, tb[..., None], axis=-1)[..., 0]
        return -jnp.sum(jnp.where(real, picked, 0.0))

    real = jnp.ones(targets.shape, bool)
    sums = jax.lax.map(jax.checkpoint(block), tuple(
        blocks_of(y, TOKEN_BLOCK) for y in (x, targets, real)))
    return jnp.sum(sums) / targets.size


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
    of the parameters' change, leaf by leaf (``reference/qwen3_next.py``'s
    ``follow``, with this model): a rank's sequences one at a time, their
    gradients added up in the gradients' storage type, as the ranks' are."""
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
