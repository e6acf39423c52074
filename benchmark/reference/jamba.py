"""Plain float32 Jamba decoder for the benchmark's ``correct``: the first
stage of a two-stage pipeline (one period of the layer pattern) with the
tied matrix read both ways.

``jax.numpy`` only, nothing imported from ``horovod_tpu``.  The equations
are written from the published ``jamba`` ``config.json``; what it does not
settle is marked *assumed* (the configuration file lists the same):

- ``RMSNorm(x; w) = x / rms(x) * w`` (a plain weight), eps from the config.
- layer ``i``: ``x <- x + mixer_i(RMSNorm(x))``, then ``x <- x +
  MLP(RMSNorm(x))``; the mixer is attention where ``i % attn_layer_period
  == attn_layer_offset`` and Mamba elsewhere (the order of the layer types
  from period and offset alone: *assumed*); ``MLP(u) = (SiLU(u W_gate) * u
  W_up) W_down`` in every layer (``num_experts`` 1).  Logits ``=
  RMSNorm(x_L; w_final) E^T`` with ``E`` the embedding matrix
  (``tie_word_embeddings``): one leaf, one gradient, the lookup's scatter
  and the head's product summed in float32 before it is rounded.
- **Mamba** (Mamba-1): ``[x | z] = u W_in`` (column order *assumed*); a
  causal depthwise convolution with bias over ``x``, then SiLU; ``[dt_r |
  B | C] = x W_x``; an RMSNorm with a learned weight on each of ``dt_r``,
  ``B`` and ``C`` (the family's addition to Mamba-1; that they sit here:
  *assumed*); ``delta = softplus(dt_r W_dt + b_dt)``; ``A = -exp(A_log)``
  ``[d_inner, state]``; for every channel ``c`` and state ``s`` ``h_t[c,
  s] = exp(delta_t[c] A[c, s]) h_{t-1}[c, s] + delta_t[c] B_t[s] x_t[c]``,
  ``y_t[c] = sum_s C_t[s] h_t[c, s] + D[c] x_t[c]`` — computed HERE **token
  by token** (a ``lax.scan`` over t with ``h [d_inner, state]``, recomputed
  in segments in the backward pass), so that the program's kernels and its
  chunks are checked against something that shares neither; ``y * SiLU(z)``
  and ``W_out``.
- **attention**: ``num_attention_heads`` query heads on
  ``num_key_value_heads`` key and value heads of ``head_dim`` (``hidden_size
  / num_attention_heads``: *assumed*, the config has no key), no bias, **no
  rotary** and no other position signal (*assumed*), causal softmax
  attention as a masked softmax, scale ``head_dim ** -0.5``; ``W_o``.
- loss: mean next-token cross-entropy over the whole vocabulary.

Parameters are a dict in the layout the system under test uses (a layout,
not code).  Weights and data of a run are made HERE from the seed, in the
configuration's storage type; every operation computes in float32
(``follow`` sets ``highest`` matmul precision).  ``follow`` takes the
gradient a layer a jitted call (``gradient``): the layers' inputs are kept
going forward, each layer is run again and transposed on its own going
back, and the head's logits and loss are taken a block of tokens at a
time, so that the float32 activations of 8192 tokens at 65536 rows fit
beside the weights, the gradient and both moments.  ``precision`` other
than ``float32`` rounds the operands of every matrix product and of the
convolution in both passes (``common.quantizer``): the control.  Of the
recurrence x, B and C are the rounded operands; its state stays float32,
as an accumulator.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .common import leaf_norms, quantizer
from .llama import ADAM, adam_step    # noqa: F401  (ADAM: the family's too)
from .nemotron_h import attention
from .olmo_hybrid import blocks_of, rms_norm
from .qwen3_next import make_batch, silu    # noqa: F401
from .resnet import scalars

SEGMENT = 128           # tokens of the recurrence recomputed together
TOKEN_BLOCK = 1024      # tokens of the head's logits
STEP_MIN, STEP_MAX = 1e-3, 0.1      # the published draw of the step


def is_attention(sizes, layer):
    return (layer % sizes["attn_layer_period"]) == sizes["attn_layer_offset"]


def mamba_dims(sizes):
    """(channels, state width, rank of the step's projection, taps)."""
    return (sizes["mamba_expand"] * sizes["hidden_size"],
            sizes["mamba_d_state"], sizes["mamba_dt_rank"],
            sizes["mamba_d_conv"])


# ------------------------------------------------------------ weights, data
def init_weights(key, sizes):
    """Normal(0, 1/fan_in) matrices; norm weights (each layer's two, the
    final norm's, the three inside a Mamba layer) uniform in 0.5..1.5, so
    that a missing norm is far off.  A Mamba layer's vectors as published
    for Mamba-1: ``A_log = log(1 .. state)`` for every channel, ``D = 1``,
    the step log-uniform in 0.001..0.1 (``dt_bias`` its inverse softplus);
    the convolution's bias uniform in ±0.5 (a depthwise Conv1d's default at
    4 taps)."""
    d, v, f = (sizes["hidden_size"], sizes["vocab_size"],
               sizes["intermediate_size"])
    di, n, r, taps = mamba_dims(sizes)
    h, kv, hd = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                 sizes["head_dim"])
    layers = sizes["num_hidden_layers"]
    dt = jnp.dtype(sizes["dtype"])
    keys = iter(jax.random.split(key, 2 + 16 * layers))

    def dense(fan_in, shape):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                / np.sqrt(fan_in)).astype(dt)

    def uniform(lo, hi, shape):
        return jax.random.uniform(next(keys), shape, jnp.float32, lo, hi)

    def ssm():
        step = jnp.exp(uniform(np.log(STEP_MIN), np.log(STEP_MAX), (di,)))
        return {"w_in": dense(d, (d, 2 * di)),
                "conv": dense(taps, (taps, di)),
                "conv_bias": uniform(-0.5, 0.5, (di,)).astype(dt),
                "w_x": dense(di, (di, r + 2 * n)),
                "dt_norm": uniform(0.5, 1.5, (r,)).astype(dt),
                "b_norm": uniform(0.5, 1.5, (n,)).astype(dt),
                "c_norm": uniform(0.5, 1.5, (n,)).astype(dt),
                "w_dt": dense(r, (r, di)),
                "dt_bias": jnp.log(jnp.expm1(step)).astype(dt),
                "A_log": jnp.broadcast_to(jnp.log(jnp.arange(
                    1, n + 1, dtype=jnp.float32)), (di, n)).astype(dt),
                "D": jnp.ones((di,), dt),
                "w_out": dense(di, (di, d))}

    def attn():
        return {"wq": dense(d, (d, h * hd)), "wk": dense(d, (d, kv * hd)),
                "wv": dense(d, (d, kv * hd)),
                "wo": dense(h * hd, (h * hd, d))}

    def layer(i):
        mixer = {"attn": attn()} if is_attention(sizes, i) else {"ssm": ssm()}
        return {"mixer_norm": uniform(0.5, 1.5, (d,)).astype(dt), **mixer,
                "mlp_norm": uniform(0.5, 1.5, (d,)).astype(dt),
                "mlp": {"w_gate": dense(d, (d, f)), "w_up": dense(d, (d, f)),
                        "w_down": dense(f, (f, d))}}

    return {"embed": dense(d, (v, d)),
            "layers": [layer(i) for i in range(layers)],
            "final_norm": uniform(0.5, 1.5, (d,)).astype(dt)}


# ------------------------------------------------------------------ forward
def recurrence(x, delta, A, B, C):
    """The selective scan a token at a time.  x and delta [b,T,d], A [d,n],
    B and C [b,T,n] -> ``sum_s C_t[s] h_t[c, s]`` [b,T,d]; the state
    ``h [b, d, n]`` starts at zero.  Padding steps (delta = 0) leave it as
    it is."""
    b, t, d = x.shape
    pad = (-t) % SEGMENT

    def segments(v):
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
        v = jnp.moveaxis(v, 1, 0)                       # time leads
        return v.reshape((-1, SEGMENT) + v.shape[1:])

    def token(h, of_token):
        x_t, delta_t, B_t, C_t = of_token
        h = jnp.exp(delta_t[..., None] * A) * h + (
            (delta_t * x_t)[..., None] * B_t[:, None, :])
        return h, jnp.einsum("bdn,bn->bd", h, C_t)

    segment = jax.checkpoint(lambda h, xs: jax.lax.scan(token, h, xs))
    _, y = jax.lax.scan(segment, jnp.zeros((b, d, A.shape[1]), jnp.float32),
                        tuple(segments(v) for v in (x, delta, B, C)))
    return jnp.moveaxis(y.reshape((-1,) + y.shape[2:]), 0, 1)[:, :t]


def mamba(p, u, sizes, mm, q):
    b, t, _ = u.shape
    di, n, r, taps = mamba_dims(sizes)
    eps = sizes["rms_norm_eps"]
    xz = mm("btd,de->bte", u, p["w_in"])
    x, z = xz[..., :di], xz[..., di:]
    # causal and depthwise: tap j weighs the input taps-1-j back
    padded = q.operand(jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0))))
    x = silu(q.result(sum(q.operand(p["conv"][j]) * padded[:, j:j + t]
                          for j in range(taps))) + p["conv_bias"])
    dbc = mm("bte,ef->btf", x, p["w_x"])
    dt_r = rms_norm(dbc[..., :r], p["dt_norm"], eps)
    B = rms_norm(dbc[..., r:r + n], p["b_norm"], eps)
    C = rms_norm(dbc[..., r + n:], p["c_norm"], eps)
    delta = jax.nn.softplus(mm("btr,re->bte", dt_r, p["w_dt"])
                            + p["dt_bias"])
    # the recurrence takes x, B and C as operands
    y = q.result(recurrence(q.operand(x), delta, -jnp.exp(p["A_log"]),
                            q.operand(B), q.operand(C))) + p["D"] * x
    return mm("bte,ed->btd", y * silu(z), p["w_out"])


def full_attention(p, x, sizes, mm):
    b, t, _ = x.shape
    h, kv, hd = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                 sizes["head_dim"])
    qs = mm("btd,de->bte", x, p["wq"]).reshape(b, t, h, hd)
    ks = mm("btd,de->bte", x, p["wk"]).reshape(b, t, kv, hd)
    vs = mm("btd,de->bte", x, p["wv"]).reshape(b, t, kv, hd)
    o = attention(qs, ks, vs, mm)                           # no rotary
    return mm("bte,ed->btd", o.reshape(b, t, h * hd), p["wo"])


def _matmul(q):
    return lambda spec, a, b: q.result(
        jnp.einsum(spec, q.operand(a), q.operand(b)))


def layer(p, x, sizes, q):
    """One layer: the mixer behind its norm, then the MLP behind its own,
    each recomputed on its own in the backward pass."""
    mm, eps = _matmul(q), sizes["rms_norm_eps"]
    p = jax.tree_util.tree_map(lambda w: w.astype(jnp.float32), p)

    def mixer(p, x):
        u = rms_norm(x, p["mixer_norm"], eps)
        return x + (full_attention(p["attn"], u, sizes, mm) if "attn" in p
                    else mamba(p["ssm"], u, sizes, mm, q))

    def mlp(p, x):
        u, w = rms_norm(x, p["mlp_norm"], eps), p["mlp"]
        return x + mm("btf,fd->btd", silu(mm("btd,df->btf", u, w["w_gate"]))
                      * mm("btd,df->btf", u, w["w_up"]), w["w_down"])

    return jax.checkpoint(mlp)(p, jax.checkpoint(mixer)(p, x))


def embedded(embed, tokens):
    return embed.astype(jnp.float32)[tokens]


def logits_of(embed, final_norm, x, sizes, q):
    """The tied head: the final norm's output times the embedding's
    transpose."""
    return _matmul(q)("btd,vd->btv", rms_norm(
        x, final_norm.astype(jnp.float32), sizes["rms_norm_eps"]),
        embed.astype(jnp.float32))


def head_loss(embed, final_norm, x, targets, sizes, q):
    """Mean next-token cross-entropy of the last layer's output, the logits
    a block of tokens at a time."""
    def block(args):                    # the summed loss of a block
        xb, tb, real = args
        logits = logits_of(embed, final_norm, xb, sizes, q)
        logp = logits - jax.scipy.special.logsumexp(logits, axis=-1,
                                                    keepdims=True)
        picked = jnp.take_along_axis(logp, tb[..., None], axis=-1)[..., 0]
        return -jnp.sum(jnp.where(real, picked, 0.0))

    real = jnp.ones(targets.shape, bool)
    sums = jax.lax.map(jax.checkpoint(block), tuple(
        blocks_of(y, TOKEN_BLOCK) for y in (x, targets, real)))
    return jnp.sum(sums) / targets.size


def hidden(params, tokens, sizes, q):
    """The last layer's output ``[B, T, hidden]``, before the final norm."""
    x = embedded(params["embed"], tokens)
    for p in params["layers"]:
        x = layer(p, x, sizes, q)
    return x


def forward(params, tokens, sizes):
    """Logits ``[B, T, vocab]``, whole: for the tests' sizes."""
    q = quantizer("float32")
    return logits_of(params["embed"], params["final_norm"],
                     hidden(params, tokens, sizes, q), sizes, q)


def loss_fn(params, tokens, targets, sizes, precision="float32"):
    """The loss in one traced function: for the tests' sizes (``gradient``
    is what ``follow`` takes, and gives the same)."""
    q = quantizer(precision)
    return head_loss(params["embed"], params["final_norm"],
                     hidden(params, tokens, sizes, q), targets, sizes, q)


# ------------------------------------------- the same, a layer a jitted call
@functools.lru_cache(maxsize=None)
def _pieces(sizes_items, precision):
    """The jitted pieces of ``gradient``, compiled once for a set of sizes
    (a program a kind of layer): a layer forward, a layer transposed, the
    head with its gradients, and the tied matrix's one gradient."""
    sizes = dict(sizes_items)
    q = quantizer(precision)
    one = functools.partial(layer, sizes=sizes, q=q)
    stored = lambda g, like: jax.tree_util.tree_map(
        lambda y, w: y.astype(w.dtype), g, like)

    def layer_back(p, x, ct):
        g, ct = jax.vjp(one, p, x)[1](ct)
        return stored(g, p), ct

    def head(embed, final_norm, x, targets):
        """``(loss, (the head's float32 part of the tied gradient, the
        final norm's gradient, the cotangent of x))``."""
        loss, g = jax.value_and_grad(
            lambda e, w, y: head_loss(e, w, y, targets, sizes, q),
            argnums=(0, 1, 2))(embed.astype(jnp.float32), final_norm, x)
        return loss, g

    def tied(of_head, tokens, ct, embed):
        """The one gradient of the one matrix: the head's product plus the
        lookup's scatter, summed in float32 and rounded once."""
        return of_head.at[tokens].add(ct).astype(embed.dtype)

    return {"embed": jax.jit(embedded), "layer": jax.jit(one),
            "layer_back": jax.jit(layer_back), "head": jax.jit(head),
            "tied": jax.jit(tied, donate_argnums=(0,))}


def gradient(pieces, params, tokens, targets):
    """``(loss, gradient)`` of ``loss_fn`` at ``params`` for one batch, the
    gradient in the weights' storage type."""
    x = pieces["embed"](params["embed"], tokens)
    entered = []
    for p in params["layers"]:
        entered.append(x)
        x = pieces["layer"](p, x)
    loss, (of_head, final_norm, ct) = pieces["head"](
        params["embed"], params["final_norm"], x, targets)
    del x
    layers = []
    for p in reversed(params["layers"]):
        g, ct = pieces["layer_back"](p, entered.pop(), ct)
        layers.append(g)
    return float(loss), {
        "embed": pieces["tied"](of_head, tokens, ct, params["embed"]),
        "layers": layers[::-1], "final_norm": final_norm}


# -------------------------------------------------------------- three steps
@functools.lru_cache(maxsize=None)
def _programs(sizes_items):
    sizes = dict(sizes_items)
    add = lambda a, b: jax.tree_util.tree_map(jnp.add, a, b)
    return (jax.jit(lambda k: init_weights(k, sizes)),
            jax.jit(lambda k, r: make_batch(k, sizes, r)),
            jax.jit(add, donate_argnums=(0, 1)),
            jax.jit(adam_step, donate_argnums=(0, 2, 3)))


def follow(sizes, key, world, steps, precision="float32"):
    """The first ``steps`` synchronous data-parallel steps at the seeded
    weights: per-rank losses, the norm of the first averaged gradient and
    of the parameters' change, leaf by leaf (``reference/nemotron_h.py``'s
    ``follow``, with this model and its gradient a layer a call): a rank's
    sequences one at a time, their gradients added up in the gradients'
    storage type, as the ranks' are."""
    weights, batch, add, update = _programs(scalars(sizes))
    pieces = _pieces(scalars(sizes), precision)
    with jax.default_matmul_precision("highest"):
        params = weights(key)
        mu = jax.tree_util.tree_map(jnp.zeros_like, params)
        nu = jax.tree_util.tree_map(jnp.zeros_like, params)
        losses = [[] for _ in range(world)]
        first = None
        for step in range(1, steps + 1):
            mean, parts = None, 0
            for r in range(world):
                tokens, targets = batch(key, r)
                of_rank = []
                for b in range(tokens.shape[0]):    # equally long: the mean
                    loss, g = gradient(pieces, params, tokens[b:b + 1],
                                       targets[b:b + 1])
                    mean = g if mean is None else add(mean, g)
                    of_rank.append(loss)
                    parts += 1
                losses[r].append(sum(of_rank) / len(of_rank))
            del g
            if parts > 1:
                mean = jax.tree_util.tree_map(lambda x: x / parts, mean)
            if first is None:
                first = leaf_norms(mean)
            params, mu, nu = update(params, mean, mu, nu, step)
            del mean
        delta = leaf_norms(params, minus=weights(key))
    return {"losses": losses, "grad_norms": first, "delta_norms": delta}
