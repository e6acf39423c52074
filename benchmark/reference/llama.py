"""Plain float32 Mistral/Llama-style decoder for the benchmark's ``correct``.

``jax.numpy`` only, nothing imported from ``horovod_tpu``: RMSNorm, rotary
embeddings (the half-split rotation of the published implementations),
grouped-query attention under a causal sliding-window mask, SwiGLU, the
mean next-token cross-entropy, Adam written out.  Parameters are a dict
with the key names the system under test uses (a layout, not code):
``embed``, ``layers`` (a list of ``attn_norm wq wk wv wo mlp_norm w1 w3
w2``), ``final_norm``, ``lm_head``.

The weights and the data of a run are made HERE, from the seed, in the
type the configuration stores them in, and handed to the program.

Every operation computes in float32 (``highest`` matmul precision is set
by ``follow``).  What the configuration states about STORAGE is kept:
weights, gradients and Adam's moments live in ``sizes["dtype"]`` between
steps, since a float32 copy of the state of a memory-full cell would not
fit beside its activations.  Attention is computed one key/value group at
a time and each layer is recomputed in the backward pass, for the same
reason.

``precision`` other than ``float32`` rounds the operands of every matrix
product in both passes (``common.quantizer``): the control.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .common import leaf_norms, quantizer
from .resnet import scalars

ADAM = {"lr": 1e-4, "b1": 0.9, "b2": 0.999, "eps": 1e-8}


# ------------------------------------------------------------ weights, data
def init_weights(key, sizes):
    """Normal(0, 1/fan_in) matrices, unit norm scales, in one traced
    function and in the configuration's storage type."""
    d, h, kv, hd = (sizes["hidden_size"], sizes["num_attention_heads"],
                    sizes["num_key_value_heads"], sizes["head_dim"])
    f, v, n = (sizes["intermediate_size"], sizes["vocab_size"],
               sizes["num_hidden_layers"])
    dt = jnp.dtype(sizes["dtype"])
    keys = iter(jax.random.split(key, 2 + 7 * n))

    def dense(fan_in, shape):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                / np.sqrt(fan_in)).astype(dt)

    layers = [{"attn_norm": jnp.ones((d,), dt),
               "wq": dense(d, (d, h * hd)), "wk": dense(d, (d, kv * hd)),
               "wv": dense(d, (d, kv * hd)), "wo": dense(h * hd, (h * hd, d)),
               "mlp_norm": jnp.ones((d,), dt),
               "w1": dense(d, (d, f)), "w3": dense(d, (d, f)),
               "w2": dense(f, (f, d))} for _ in range(n)]
    return {"embed": dense(d, (v, d)), "layers": layers,
            "final_norm": jnp.ones((d,), dt), "lm_head": dense(d, (d, v))}


def make_batch(key, sizes, rank):
    """Rank ``rank``'s fixed batch of token rows and their next tokens."""
    b, t = sizes["batch_per_chip"], sizes["seq_len"]
    toks = jax.random.randint(jax.random.fold_in(key, rank), (b, t + 1), 0,
                              sizes["vocab_size"], jnp.int32)
    return toks[:, :-1], toks[:, 1:]


# ------------------------------------------------------------------ forward
def rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * w


def rope(x, theta):
    """x [B, T, heads, head_dim]; pairs (i, i + half) rotate together."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs[None]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(q, k, v, window, mm):
    """q [B,T,H,hd], k/v [B,T,KV,hd] -> [B,T,H,hd]; one group at a time."""
    b, t, h, hd = q.shape
    kv = k.shape[2]
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    mask = (j <= i) & ((i - j < window) if window else True)

    def group(qg, kg, vg):              # [B,T,rep,hd], [B,T,hd], [B,T,hd]
        s = mm("btrd,bsd->brts", qg, kg) / np.sqrt(hd)
        s = jnp.where(mask[None, None], s, -jnp.inf)
        s = s - jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s)
        p = p / jnp.sum(p, axis=-1, keepdims=True)
        return mm("brts,bsd->btrd", p, vg)

    qg = jnp.moveaxis(q.reshape(b, t, kv, h // kv, hd), 2, 0)
    out = jax.lax.map(lambda a: jax.checkpoint(group)(*a),
                      (qg, jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0)))
    return jnp.moveaxis(out, 0, 2).reshape(b, t, h, hd)


def _layer(p, x, sizes, mm):
    p = jax.tree_util.tree_map(lambda w: w.astype(jnp.float32), p)
    b, t, _ = x.shape
    h, kv, hd = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                 sizes["head_dim"])
    a = rms_norm(x, p["attn_norm"], sizes["rms_norm_eps"])
    q = rope(mm("btd,de->bte", a, p["wq"]).reshape(b, t, h, hd),
             sizes["rope_theta"])
    k = rope(mm("btd,de->bte", a, p["wk"]).reshape(b, t, kv, hd),
             sizes["rope_theta"])
    v = mm("btd,de->bte", a, p["wv"]).reshape(b, t, kv, hd)
    o = attention(q, k, v, sizes.get("sliding_window"), mm)
    x = x + mm("bte,ed->btd", o.reshape(b, t, h * hd), p["wo"])
    m = rms_norm(x, p["mlp_norm"], sizes["rms_norm_eps"])
    gate = mm("btd,df->btf", m, p["w1"])
    up = mm("btd,df->btf", m, p["w3"])
    return x + mm("btf,fd->btd", gate / (1.0 + jnp.exp(-gate)) * up, p["w2"])


def loss_fn(params, tokens, targets, sizes, precision="float32"):
    q = quantizer(precision)

    def mm(spec, a, b):
        return q.result(jnp.einsum(spec, q.operand(a), q.operand(b)))

    x = params["embed"].astype(jnp.float32)[tokens]
    for p in params["layers"]:
        x = jax.checkpoint(functools.partial(_layer, sizes=sizes, mm=mm))(p, x)
    x = rms_norm(x, params["final_norm"].astype(jnp.float32),
                 sizes["rms_norm_eps"])
    logits = mm("btd,dv->btv", x, params["lm_head"].astype(jnp.float32))
    logp = logits - jax.scipy.special.logsumexp(logits, axis=-1,
                                                keepdims=True)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))


# -------------------------------------------------------------- three steps
def adam_step(params, grads, mu, nu, count):
    """Adam in float32, state rounded back to its storage type."""
    c = ADAM

    def leaf(p, g, m, n):
        g32 = g.astype(jnp.float32)
        m32 = c["b1"] * m.astype(jnp.float32) + (1 - c["b1"]) * g32
        n32 = c["b2"] * n.astype(jnp.float32) + (1 - c["b2"]) * g32 * g32
        step = (m32 / (1 - c["b1"] ** count)) / (
            jnp.sqrt(n32 / (1 - c["b2"] ** count)) + c["eps"])
        return ((p.astype(jnp.float32) - c["lr"] * step).astype(p.dtype),
                m32.astype(m.dtype), n32.astype(n.dtype))

    out = jax.tree_util.tree_map(leaf, params, grads, mu, nu)
    pick = lambda i: jax.tree_util.tree_map(
        lambda _, o: o[i], params, out)
    return pick(0), pick(1), pick(2)


@functools.lru_cache(maxsize=None)
def _programs(sizes_items, precision):
    """The jitted pieces of ``follow``, compiled once for a set of sizes."""
    sizes = dict(sizes_items)
    return (jax.jit(lambda k: init_weights(k, sizes)),
            jax.jit(lambda k, r: make_batch(k, sizes, r)),
            jax.jit(jax.value_and_grad(functools.partial(
                loss_fn, sizes=sizes, precision=precision))),
            jax.jit(adam_step, donate_argnums=(0, 2, 3)))


def follow(sizes, key, world, steps, precision="float32"):
    """The first ``steps`` synchronous data-parallel steps at the seeded
    weights: per-rank losses, the norm of the first averaged gradient and
    of the parameters' change, leaf by leaf."""
    weights, batch, grad, update = _programs(scalars(sizes), precision)
    with jax.default_matmul_precision("highest"):
        params = weights(key)
        mu = jax.tree_util.tree_map(jnp.zeros_like, params)
        nu = jax.tree_util.tree_map(jnp.zeros_like, params)
        losses = [[] for _ in range(world)]
        first = None
        for step in range(1, steps + 1):
            mean = None
            for r in range(world):
                loss, g = grad(params, *batch(key, r))
                losses[r].append(float(loss))
                mean = g if mean is None else jax.tree_util.tree_map(
                    jnp.add, mean, g)
            if world > 1:
                mean = jax.tree_util.tree_map(lambda x: x / world, mean)
            if first is None:
                first = leaf_norms(mean)
            params, mu, nu = update(params, mean, mu, nu, step)
            del mean, g
        delta = leaf_norms(params, minus=weights(key))
    return {"losses": losses, "grad_norms": first, "delta_norms": delta}
