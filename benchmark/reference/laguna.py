"""Plain float32 Laguna decoder for the benchmark's ``correct``: one chip's
share of a deployment in which 16 chips share each layer.

``jax.numpy`` only, nothing imported from ``horovod_tpu``.  The equations
are written from the published ``laguna`` ``config.json``; what it does not
settle is marked *assumed* (the configuration file lists the same):

- ``RMSNorm(x; w) = x / rms(x) * w`` (a plain weight), eps from the config.
- layer ``i``: ``x <- x + Attn_i(RMSNorm(x))``, then ``x <- x +
  MLP_i(RMSNorm(x))`` (pre-norm blocks: *assumed*); a final RMSNorm and an
  untied head.  The layer table is three lists, a layer an entry
  (``layer_types``, ``num_attention_heads_per_layer``, ``mlp_layer_types``:
  the file's ``*_run`` strings hold the entries of the layers run).
- **attention** of layer ``i`` with ``H_i`` query heads on
  ``num_key_value_heads`` key and value heads of ``head_dim``, no bias:
  ``q = h Wq``, ``k, v = h Wk, h Wv``, ``g = sigmoid(h Wg)`` (``hidden ->
  H_i``, a gate a head, read from the normed input: *assumed*).  Rotary on
  q and k in the half-split pairing (*assumed*): a ``full_attention``
  layer turns the first ``partial_rotary_factor`` of a head by YaRN's
  frequencies — with ``f_j = theta^(-2j / width)`` the interpolated ``f_j /
  factor`` and the extrapolated ``f_j`` blended by the linear ramp between
  the two correction dimensions that ``beta_fast`` and ``beta_slow`` give
  at the original positions, ``cos`` and ``sin`` times
  ``attention_factor`` — and a ``sliding_attention`` layer all of it by
  plain frequencies.  Scores ``q k^T / sqrt(head_dim)``, causal; in a
  sliding layer key ``j`` is seen by query ``t`` only where ``0 <= t - j <
  sliding_window``: computed HERE as a **masked softmax** a block of query
  rows at a time, and in a sliding layer over the slice of keys a block can
  see, so that the program's kernels and their block schedule are checked
  against something that shares neither.  ``o = concat_h(g_h *
  softmax(...)_h v) Wo`` (the gate before ``Wo``: *assumed*).  No norm on q
  or k (*assumed*: the config has no key for one).
- **MLP**: a ``dense`` layer is ``(SiLU(u W_gate) * u W_up) W_down`` at
  ``intermediate_size``.  A ``sparse`` layer: ``s = sigmoid(u W_r)`` over
  ALL published experts (sigmoid scoring without a selection bias:
  *assumed*), the ``num_experts_per_tok`` largest chosen and weighed by
  ``moe_routed_scaling_factor * s_e / sum_chosen s``; the routed part is a
  plain loop over the experts HELD HERE (``first_expert`` ..
  ``first_expert + num_experts``) with a mask; what the absent experts
  would have added is left out.  The shared expert, ungated (*assumed*), is
  computed for every token and added.
- loss: mean next-token cross-entropy over the vocabulary slice; no
  auxiliary loss (*assumed*).

Parameters are a dict in the layout the system under test uses (a layout,
not code).  Weights and data of a run are made HERE from the seed, in the
configuration's storage type; every operation computes in float32
(``follow`` sets ``highest`` matmul precision).  ``follow`` takes the
gradient a layer a jitted call (``gradient``, as ``reference/jamba.py``'s):
the layers' inputs are kept going forward, each layer is run again and
transposed on its own going back, and the head's logits and loss are taken
a block of tokens at a time, so that the float32 activations of 16384
tokens fit beside the weights, the gradient and both moments.
``precision`` other than ``float32`` rounds the operands of every matrix
product in both passes (``common.quantizer``): the control.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import jamba as _jamba
from .common import leaf_norms, quantizer
from .jamba import _matmul, embedded
from .llama import ADAM, adam_step    # noqa: F401  (ADAM: the family's too)
from .olmo_hybrid import rms_norm
from .qwen3_next import make_batch, sigmoid, silu    # noqa: F401
from .resnet import scalars

QUERY_BLOCK = 512       # queries of one attention block
MLP_TOKENS = 4096       # tokens of the dense MLP's and an expert's products
FULL, SLIDING = "full_attention", "sliding_attention"


def layer_table(sizes):
    """``[(layer type, query heads, MLP type)]`` of the layers run."""
    table = list(zip(
        sizes["layer_types_run"].split(),
        (int(h) for h in sizes["num_attention_heads_per_layer_run"].split()),
        sizes["mlp_layer_types_run"].split()))
    if len(table) != sizes["num_hidden_layers"]:
        raise ValueError(f"the *_run lists name {len(table)} layers, "
                         f"num_hidden_layers {sizes['num_hidden_layers']}")
    return table


# ------------------------------------------------------------ weights, data
def init_weights(key, sizes):
    """Normal(0, 1/fan_in) matrices; norm weights uniform in 0.5..1.5, so
    that a missing norm is far off; the selection bias zeros (the config
    has none)."""
    d, v = sizes["hidden_size"], sizes["vocab_size"]
    kv, hd = sizes["num_key_value_heads"], sizes["head_dim"]
    f, fe, fs = (sizes["intermediate_size"], sizes["moe_intermediate_size"],
                 sizes["shared_expert_intermediate_size"])
    held, published = sizes["num_experts"], sizes["num_experts_published"]
    dt = jnp.dtype(sizes["dtype"])
    table = layer_table(sizes)
    keys = iter(jax.random.split(key, 3 + 16 * len(table)))

    def dense(fan_in, shape):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                / np.sqrt(fan_in)).astype(dt)

    def about_one(shape):
        return (1.0 + jax.random.uniform(next(keys), shape, jnp.float32,
                                         -0.5, 0.5)).astype(dt)

    layers = []
    for _, h, mlp in table:
        layer = {
            "attn_norm": about_one((d,)),
            "attn": {"wq": dense(d, (d, h * hd)), "wk": dense(d, (d, kv * hd)),
                     "wv": dense(d, (d, kv * hd)), "wg": dense(d, (d, h)),
                     "wo": dense(h * hd, (h * hd, d))},
            "mlp_norm": about_one((d,))}
        if mlp == "sparse":
            layer["moe"] = {
                "router": dense(d, (d, published)),
                "router_bias": jnp.zeros((published,), dt),
                "w1": dense(d, (held, d, fe)), "w3": dense(d, (held, d, fe)),
                "w2": dense(fe, (held, fe, d)),
                "shared_w1": dense(d, (d, fs)),
                "shared_w3": dense(d, (d, fs)),
                "shared_w2": dense(fs, (fs, d))}
        else:
            layer["mlp"] = {"w_gate": dense(d, (d, f)),
                            "w_up": dense(d, (d, f)),
                            "w_down": dense(f, (f, d))}
        layers.append(layer)
    return {"embed": dense(d, (v, d)), "layers": layers,
            "final_norm": about_one((d,)), "lm_head": dense(d, (d, v))}


# ------------------------------------------------------------------- rotary
def rotary_of(sizes, sliding):
    """``(frequencies [width / 2], scale of cos and sin, width)`` of a
    layer kind's rotary, from the file's flat keys."""
    kind = "sliding" if sliding else "full"
    width = int(sizes["head_dim"] * sizes[f"{kind}_partial_rotary_factor"])
    theta = float(sizes[f"{kind}_rope_theta"])
    j = np.arange(width // 2, dtype=np.float64)
    plain = theta ** (-2.0 * j / width)
    if sizes[f"{kind}_rope_type"] == "default":
        return plain, 1.0, width
    factor = float(sizes[f"{kind}_rope_factor"])
    original = sizes[f"{kind}_rope_original_max_position_embeddings"]

    def correction_dim(turns):
        return width * math.log(original / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(correction_dim(sizes[f"{kind}_rope_beta_fast"])), 0)
    high = min(math.ceil(correction_dim(sizes[f"{kind}_rope_beta_slow"])),
               width - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((j - low) / (high - low), 0.0, 1.0)
    scale = sizes.get(f"{kind}_rope_attention_factor")
    if scale is None:
        scale = 0.1 * math.log(factor) + 1.0
    return plain / factor * ramp + plain * (1.0 - ramp), float(scale), width


def turned(x, freqs, scale, width):
    """x [B, T, heads, head_dim]; the first ``width`` of the head rotate,
    pairs (i, i + width / 2) together."""
    half = width // 2
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * jnp.asarray(
        freqs, jnp.float32)[None]
    cos = scale * jnp.cos(ang)[None, :, None]
    sin = scale * jnp.sin(ang)[None, :, None]
    a, b = x[..., :half], x[..., half:width]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., width:]], axis=-1)


# ---------------------------------------------------------------- attention
def attention(qs, ks, vs, mm, window=None):
    """qs [B,T,H,hd], ks/vs [B,T,KV,hd] -> [B,T,H,hd]: a masked softmax,
    one (sequence, key-value head, block of queries) at a time.  With a
    ``window`` key ``j`` is seen by query ``t`` where ``0 <= t - j <
    window``, and a block of queries is held against the slice of keys it
    can see (its own positions and the ``window - 1`` before them)."""
    b, t, h, hd = qs.shape
    kv = ks.shape[2]
    block = min(QUERY_BLOCK, t)
    blocks = -(-t // block)
    pad = blocks * block - t
    banded = window is not None and window - 1 + block < t
    lead = window - 1 if banded else 0      # keys padded in front
    seen = lead + block if banded else t    # keys a block is held against

    def of_block(qb, start, kg, vg):    # [rep,Q,hd], [], [lead+T,hd] twice
        i = start + jnp.arange(block)[:, None]
        first = start if banded else 0      # the slice's first key, padded
        j = first - lead + jnp.arange(seen)[None, :]
        kb = jax.lax.dynamic_slice_in_dim(kg, first, seen)
        vb = jax.lax.dynamic_slice_in_dim(vg, first, seen)
        keep = (j >= 0) & (j <= i)
        if window is not None:
            keep &= i - j < window
        s = mm("rqd,sd->rqs", qb, kb) / np.sqrt(hd)
        s = jnp.where(keep[None], s, -jnp.inf)
        s = s - jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s)
        p = p / jnp.sum(p, axis=-1, keepdims=True)
        return mm("rqs,sd->rqd", p, vb)

    def group(args):                    # [rep,T,hd], [T,hd], [T,hd]
        qg, kg, vg = args
        qg = jnp.pad(qg, ((0, 0), (0, pad), (0, 0))).reshape(
            h // kv, blocks, block, hd)
        # rows past t see keys of their own (padding), never none
        kg = jnp.pad(kg, ((lead, pad), (0, 0)))
        vg = jnp.pad(vg, ((lead, pad), (0, 0)))
        out = jax.lax.map(
            lambda a: jax.checkpoint(of_block)(a[0], a[1], kg, vg),
            (jnp.moveaxis(qg, 1, 0), jnp.arange(blocks) * block))
        return jnp.moveaxis(out, 0, 1).reshape(h // kv, -1, hd)[:, :t]

    qg = qs.reshape(b, t, kv, h // kv, hd).transpose(0, 2, 3, 1, 4)
    out = jax.lax.map(group, (qg.reshape(b * kv, h // kv, t, hd),
                              ks.transpose(0, 2, 1, 3).reshape(b * kv, t, hd),
                              vs.transpose(0, 2, 1, 3).reshape(b * kv, t, hd)))
    return out.reshape(b, kv, h // kv, t, hd).transpose(
        0, 3, 1, 2, 4).reshape(b, t, h, hd)


def gated_attention(p, u, sizes, mm, sliding):
    """A layer's attention of the normed ``u``: the head count is the
    projection's own."""
    b, t, _ = u.shape
    kv, hd = sizes["num_key_value_heads"], sizes["head_dim"]
    h = p["wg"].shape[1]
    rot = rotary_of(sizes, sliding)
    qs = turned(mm("btd,de->bte", u, p["wq"]).reshape(b, t, h, hd), *rot)
    ks = turned(mm("btd,de->bte", u, p["wk"]).reshape(b, t, kv, hd), *rot)
    vs = mm("btd,de->bte", u, p["wv"]).reshape(b, t, kv, hd)
    gate = sigmoid(mm("btd,dh->bth", u, p["wg"]))
    o = attention(qs, ks, vs, mm,
                  sizes["sliding_window"] if sliding else None)
    return mm("bte,ed->btd", (o * gate[..., None]).reshape(b, t, h * hd),
              p["wo"])


# ---------------------------------------------------------------------- MLP
def swiglu(x, w1, w3, w2, mm):
    """``(SiLU(x w1) * x w3) w2`` of x [S, d], ``MLP_TOKENS`` rows at a
    time, each block recomputed in the backward pass."""
    def block(xb):
        return mm("sf,fd->sd", silu(mm("sd,df->sf", xb, w1))
                  * mm("sd,df->sf", xb, w3), w2)

    s, d = x.shape
    rows = min(MLP_TOKENS, s)
    pad = (-s) % rows
    out = jax.lax.map(jax.checkpoint(block),
                      jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, rows, d))
    return out.reshape(-1, d)[:s]


def route(p, x, sizes, mm):
    """[S, top_k] expert ids over all published experts, chosen by their
    sigmoid scores (plus the selection bias, which is zeros), and their
    weights: ``s`` over the chosen's sum, times the scaling factor."""
    scores = sigmoid(mm("sd,de->se", x, p["router"]))
    _, ids = jax.lax.top_k(scores + p["router_bias"],
                           sizes["num_experts_per_tok"])
    top = jnp.take_along_axis(scores, ids, axis=-1)
    return ids, (sizes["moe_routed_scaling_factor"] * top
                 / jnp.sum(top, axis=-1, keepdims=True))


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
        return w[:, None] * swiglu(x, w1, w3, w2, mm)

    # the sum is the carry, and no expert's backward pass needs it
    routed, _ = jax.lax.scan(
        lambda total, of: (total + jax.checkpoint(expert)(*of), None),
        jnp.zeros_like(x), (jnp.arange(held), p["w1"], p["w3"], p["w2"]))
    return routed, swiglu(x, p["shared_w1"], p["shared_w3"], p["shared_w2"],
                          mm)


# -------------------------------------------------------------------- layer
def layer(p, x, sizes, q, sliding):
    """One layer: attention behind its norm, then the MLP behind its own,
    each recomputed on its own in the backward pass.  The experts' stacks
    stay in their storage type until an expert is computed."""
    mm, eps = _matmul(q), sizes["rms_norm_eps"]
    p = jax.tree_util.tree_map(
        lambda w: w if w.ndim == 3 else w.astype(jnp.float32), p)
    b, t, d = x.shape

    def mixer(p, x):
        return x + gated_attention(
            p["attn"], rms_norm(x, p["attn_norm"], eps), sizes, mm, sliding)

    def mlp(p, x):
        u = rms_norm(x, p["mlp_norm"], eps).reshape(b * t, d)
        if "moe" in p:
            y = sum(expert_layer(p["moe"], u, sizes, mm))
        else:
            w = p["mlp"]
            y = swiglu(u, w["w_gate"], w["w_up"], w["w_down"], mm)
        return x + y.reshape(b, t, d)

    return jax.checkpoint(mlp)(p, jax.checkpoint(mixer)(p, x))


def logits_of(lm_head, final_norm, x, sizes, q):
    """The untied head: ``reference/jamba.py``'s, which reads a matrix of
    ``[rows, hidden]``, given this one's transpose."""
    return _jamba.logits_of(lm_head.T, final_norm, x, sizes, q)


def head_loss(lm_head, final_norm, x, targets, sizes, q):
    """Mean next-token cross-entropy of the last layer's output, the logits
    a block of tokens at a time (``reference/jamba.py``'s)."""
    return _jamba.head_loss(lm_head.T, final_norm, x, targets, sizes, q)


def hidden(params, tokens, sizes, q):
    """The last layer's output ``[B, T, hidden]``, before the final norm."""
    x = embedded(params["embed"], tokens)
    for p, (kind, _, _) in zip(params["layers"], layer_table(sizes)):
        x = layer(p, x, sizes, q, kind == SLIDING)
    return x


def forward(params, tokens, sizes):
    """Logits ``[B, T, vocab]``, whole: for the tests' sizes."""
    q = quantizer("float32")
    return logits_of(params["lm_head"], params["final_norm"],
                     hidden(params, tokens, sizes, q), sizes, q)


def loss_fn(params, tokens, targets, sizes, precision="float32"):
    """The loss in one traced function: for the tests' sizes (``gradient``
    is what ``follow`` takes, and gives the same)."""
    q = quantizer(precision)
    return head_loss(params["lm_head"], params["final_norm"],
                     hidden(params, tokens, sizes, q), targets, sizes, q)


# ------------------------------------------- the same, a layer a jitted call
@functools.lru_cache(maxsize=None)
def _pieces(sizes_items, precision):
    """The jitted pieces of ``gradient``, compiled once for a set of sizes
    (a program a shape of layer: a full or a sliding one, on a dense MLP or
    an expert layer): a layer forward, a layer transposed, the head with
    its gradients, and the embedding's scatter."""
    sizes = dict(sizes_items)
    q = quantizer(precision)
    stored = lambda g, like: jax.tree_util.tree_map(
        lambda y, w: y.astype(w.dtype), g, like)

    def one(p, x, sliding):
        return layer(p, x, sizes, q, sliding)

    def layer_back(p, x, ct, sliding):
        g, ct = jax.vjp(lambda p, x: one(p, x, sliding), p, x)[1](ct)
        return stored(g, p), ct

    def head(lm_head, final_norm, x, targets):
        """``(loss, (the head's, the final norm's gradient, the cotangent
        of x))``."""
        loss, g = jax.value_and_grad(
            lambda w, n, y: head_loss(w, n, y, targets, sizes, q),
            argnums=(0, 1, 2))(lm_head, final_norm, x)
        return loss, g

    def scatter(embed, tokens, ct):
        return jnp.zeros(embed.shape, jnp.float32).at[tokens].add(
            ct).astype(embed.dtype)

    return {"embed": jax.jit(embedded),
            "layer": jax.jit(one, static_argnums=(2,)),
            "layer_back": jax.jit(layer_back, static_argnums=(3,)),
            "head": jax.jit(head), "scatter": jax.jit(scatter)}


def gradient(pieces, params, tokens, targets, sizes):
    """``(loss, gradient)`` of ``loss_fn`` at ``params`` for one batch, the
    gradient in the weights' storage type."""
    kinds = [kind == SLIDING for kind, _, _ in layer_table(sizes)]
    x = pieces["embed"](params["embed"], tokens)
    entered = []
    for p, sliding in zip(params["layers"], kinds):
        entered.append(x)
        x = pieces["layer"](p, x, sliding)
    loss, (lm_head, final_norm, ct) = pieces["head"](
        params["lm_head"], params["final_norm"], x, targets)
    del x
    layers = []
    for p, sliding in zip(reversed(params["layers"]), reversed(kinds)):
        g, ct = pieces["layer_back"](p, entered.pop(), ct, sliding)
        layers.append(g)
    return float(loss), {
        "embed": pieces["scatter"](params["embed"], tokens, ct),
        "layers": layers[::-1], "final_norm": final_norm,
        "lm_head": lm_head}


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
    of the parameters' change, leaf by leaf (``reference/jamba.py``'s
    ``follow``, with this model): a rank's sequences one at a time, their
    gradients added up in the gradients' storage type, as the ranks'
    are."""
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
                                       targets[b:b + 1], sizes)
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
