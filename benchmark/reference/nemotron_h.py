"""Plain float32 Nemotron-H decoder for the benchmark's ``correct``: the
first stage of a pipeline (one period of the layer pattern) with its share
of the routed experts and its slice of the vocabulary.

``jax.numpy`` only, nothing imported from ``horovod_tpu``.  The equations
are written from the published ``nemotron_h`` ``config.json``; what it does
not settle is marked *assumed* (the configuration file lists the same):

- ``RMSNorm(x; w) = x / rms(x) * w`` (a plain weight), eps from the config.
- layer ``i``: ``x <- x + mixer_i(RMSNorm(x; w_i))``, ONE mixer a layer;
  its kind is character ``i`` of ``hybrid_override_pattern`` (``M``, ``E``,
  ``*``); the first ``num_hidden_layers`` characters are run.  Logits ``=
  RMSNorm(x_L; w_final) W_head``.
- **M, Mamba-2**: ``[z | x | B | C | dt] = u W_in`` (column order
  *assumed*); a causal depthwise convolution with bias over ``[x|B|C]``,
  then SiLU; ``x [T, H, P]``, ``B, C [T, G, N]``, head ``h`` reads group
  ``h // (H / G)``; ``delta = softplus(dt + dt_bias)`` (no clamp:
  *assumed*), ``a = exp(-delta exp(A_log))``; per head ``S_t = a_t S_{t-1}
  + delta_t x_t B_t^T`` (``P x N``), ``y_t = S_t C_t + D x_t`` — computed
  HERE as that **token-by-token recurrence** (a ``lax.scan`` over t,
  recomputed in segments in the backward pass), so that the program's
  chunked algebra is checked against something that does not share it;
  then ``RMSNorm_groups(y * SiLU(z)) * w`` with the mean square over each
  of the ``G`` groups of channels (gate first, then the norm) and
  ``W_out``.
- **\\*, attention**: ``num_attention_heads`` query heads on
  ``num_key_value_heads`` key and value heads of ``head_dim``, no bias,
  **no rotary** and no other position signal (*assumed*: ``rope_theta`` is
  in the config and unused), causal softmax attention, scale ``head_dim **
  -0.5``, one (head, block of queries) at a time; ``W_o``.
- **E, LatentMoE**: ``s = sigmoid(u W_r)`` over ALL published experts (the
  router reads the hidden state, not the latent: *assumed*); the
  ``num_experts_per_tok`` with the largest ``s + b`` (``b`` the selection
  bias, a constant buffer: *assumed*), weighed by their ``s`` over its sum,
  times ``routed_scaling_factor``; ``l = u W_down``; expert ``e``:
  ``relu(l W1_e)^2 W2_e``; the routed part is a plain loop over the experts
  HELD HERE (``first_expert`` .. ``first_expert + n_routed_experts``) with
  a mask, then ``W_up``; what the absent experts would have added is left
  out.  The shared expert ``relu(u Ws1)^2 Ws2`` (no gate) is computed for
  every token.
- loss: mean next-token cross-entropy over the vocabulary slice; no
  auxiliary loss, no multi-token prediction (left out, and listed in the
  configuration's ``reduced``).

Parameters are a dict in the layout the system under test uses (a layout,
not code).  Weights and data of a run are made HERE from the seed, in the
configuration's storage type; every operation computes in float32
(``follow`` sets ``highest`` matmul precision).  Each layer is recomputed in
the backward pass, a Mamba layer takes ``GROUPS_AT_ONCE`` of its groups at
a time, an expert's matrices become float32 when that expert is computed,
the head's logits and loss are taken a block of tokens at a time, and
``follow`` takes a rank's sequences one at a time, so that the float32
activations of 8192 tokens fit beside the state.  ``precision`` other than
``float32`` rounds the operands of every matrix product and of the
convolution in both passes (``common.quantizer``): the control.  Of the
recurrence's products x, B and C are the rounded operands; its state stays
float32, as an accumulator.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .common import leaf_norms, quantizer
from .llama import ADAM, adam_step    # noqa: F401  (ADAM: the family's too)
from .olmo_hybrid import blocks_of, rms_norm
from .qwen3_next import make_batch, sigmoid, silu    # noqa: F401
from .resnet import scalars

SEGMENT = 128           # tokens of the recurrence recomputed together
QUERY_BLOCK = 2048      # queries of one attention block
TOKEN_BLOCK = 2048      # tokens of the head's logits
GROUPS_AT_ONCE = 2      # B/C groups of a Mamba layer taken together
KINDS = {"M": "ssm", "E": "moe", "*": "attn"}


def pattern(sizes):
    """The kinds of the layers that are run."""
    return sizes["hybrid_override_pattern"][:sizes["num_hidden_layers"]]


def ssm_dims(sizes):
    """(heads, head width, groups, state width)."""
    return (sizes["mamba_num_heads"], sizes["mamba_head_dim"],
            sizes["n_groups"], sizes["ssm_state_size"])


# ------------------------------------------------------------ weights, data
def init_weights(key, sizes):
    """Normal(0, 1/fan_in) matrices; norm weights uniform in 0.5..1.5, so
    that a missing norm is far off.  A Mamba layer's vectors as published:
    ``A`` uniform in (1, 16), the step log-uniform in ``time_step_min ..
    time_step_max`` floored at ``time_step_floor`` (``dt_bias`` its inverse
    softplus), ``D = 1``; the convolution's bias uniform in ±0.5 (a
    depthwise Conv1d's default at 4 taps).  The selection bias
    Normal(0, 0.01): small beside the scores' spread, and enough to change
    which experts are chosen."""
    d, v = sizes["hidden_size"], sizes["vocab_size"]
    H, P, G, N = ssm_dims(sizes)
    h, kv, hd = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                 sizes["head_dim"])
    lat, f, fs = (sizes["moe_latent_size"], sizes["moe_intermediate_size"],
                  sizes["moe_shared_expert_intermediate_size"])
    held, published = sizes["n_routed_experts"], sizes[
        "num_experts_published"]
    taps, kinds = sizes["conv_kernel"], pattern(sizes)
    dt = jnp.dtype(sizes["dtype"])
    keys = iter(jax.random.split(key, 2 + 12 * len(kinds)))

    def dense(fan_in, shape):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                / np.sqrt(fan_in)).astype(dt)

    def uniform(lo, hi, shape):
        return jax.random.uniform(next(keys), shape, jnp.float32, lo, hi)

    def ssm():
        width = H * P + 2 * G * N
        step = jnp.maximum(jnp.exp(uniform(
            np.log(sizes["time_step_min"]), np.log(sizes["time_step_max"]),
            (H,))), sizes["time_step_floor"])
        return {"w_in": dense(d, (d, H * P + width + H)),
                "conv": dense(taps, (taps, width)),
                "conv_bias": uniform(-0.5, 0.5, (width,)).astype(dt),
                "A_log": jnp.log(uniform(1.0, 16.0, (H,))).astype(dt),
                "D": jnp.ones((H,), dt),
                "dt_bias": jnp.log(jnp.expm1(step)).astype(dt),
                "norm": uniform(0.5, 1.5, (H * P,)).astype(dt),
                "w_out": dense(H * P, (H * P, d))}

    def attn():
        return {"wq": dense(d, (d, h * hd)), "wk": dense(d, (d, kv * hd)),
                "wv": dense(d, (d, kv * hd)),
                "wo": dense(h * hd, (h * hd, d))}

    def moe():
        return {"router": dense(d, (d, published)),
                "router_bias": (0.01 * jax.random.normal(
                    next(keys), (published,), jnp.float32)).astype(dt),
                "w_down": dense(d, (d, lat)), "w_up": dense(lat, (lat, d)),
                "w1": dense(lat, (held, lat, f)),
                "w2": dense(f, (held, f, lat)),
                "shared_w1": dense(d, (d, fs)),
                "shared_w2": dense(fs, (fs, d))}

    mixers = {"ssm": ssm, "attn": attn, "moe": moe}
    layers = [{"norm": uniform(0.5, 1.5, (d,)).astype(dt),
               KINDS[c]: mixers[KINDS[c]]()} for c in kinds]
    return {"embed": dense(d, (v, d)), "layers": layers,
            "final_norm": uniform(0.5, 1.5, (d,)).astype(dt),
            "lm_head": dense(d, (d, v))}


# ------------------------------------------------------------------ forward
def relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def recurrence(x, delta, log_a, B, C):
    """The selective state-space recurrence a token at a time.  x
    [b,T,H,P], delta and log_a [b,T,H], B and C [b,T,N] (one group's) -> y
    [b,T,H,P]; the state starts at zero.  Padding steps (delta = 0, log_a =
    0) leave the state as it is."""
    b, t, h, p = x.shape
    pad = (-t) % SEGMENT

    def segments(v):
        v = jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
        v = jnp.moveaxis(v, 1, 0)                       # time leads
        return v.reshape((-1, SEGMENT) + v.shape[1:])

    def token(state, of_token):
        x_t, delta_t, log_a_t, B_t, C_t = of_token
        state = state * jnp.exp(log_a_t)[..., None, None] + (
            (delta_t[..., None] * x_t)[..., None] * B_t[:, None, None, :])
        return state, jnp.einsum("bhpn,bn->bhp", state, C_t)

    segment = jax.checkpoint(lambda state, xs: jax.lax.scan(token, state, xs))
    state = jnp.zeros((b, h, p, B.shape[-1]), jnp.float32)
    _, y = jax.lax.scan(segment, state,
                        tuple(segments(v) for v in (x, delta, log_a, B, C)))
    return jnp.moveaxis(y.reshape((-1,) + y.shape[2:]), 0, 1)[:, :t]


def mamba2(p, u, sizes, mm, q):
    """The layer, ``GROUPS_AT_ONCE`` of its ``B``/``C`` groups (with the
    heads that read them, and their run of the gated norm) at a time:
    groups meet only in ``W_out``, so a part's columns of the projection,
    the convolution and the vectors give its part of the output, and the
    parts add up.  The sum is the carry of a scan and each part is
    recomputed in the backward pass."""
    H, P, G, N = ssm_dims(sizes)
    at_once = max(n for n in range(1, GROUPS_AT_ONCE + 1) if G % n == 0)
    parts, d_inner = G // at_once, H * P

    def run(w, first, width):
        """[..., first + width ...] -> [parts, ..., a part's]."""
        w = w[..., first:first + width]
        w = w.reshape(w.shape[:-1] + (parts, width // parts))
        return jnp.moveaxis(w, -2, 0)

    of_part = {}
    for name, first, width in (("z", 0, d_inner), ("x", d_inner, d_inner),
                               ("B", 2 * d_inner, G * N),
                               ("C", 2 * d_inner + G * N, G * N),
                               ("dt", 2 * d_inner + 2 * G * N, H)):
        of_part["w_" + name] = run(p["w_in"], first, width)
        if name in ("x", "B", "C"):
            of_part["conv_" + name] = run(p["conv"], first - d_inner, width)
            of_part["bias_" + name] = run(p["conv_bias"], first - d_inner,
                                          width)
    for name in ("A_log", "D", "dt_bias"):
        of_part[name] = run(p[name], 0, H)
    of_part["norm"] = run(p["norm"], 0, d_inner)
    of_part["w_out"] = p["w_out"].reshape(parts, d_inner // parts, -1)

    def part(w):
        return groups_of_mamba2(w, u, at_once, H // G, sizes, mm, q)

    out, _ = jax.lax.scan(
        lambda total, w: (total + jax.checkpoint(part)(w), None),
        jnp.zeros_like(u), of_part)
    return out


def groups_of_mamba2(w, u, groups, rep, sizes, mm, q):
    """What ``groups`` groups of ``rep`` heads each add to the layer's
    output; ``w`` holds their columns (and ``w_out`` their rows)."""
    b, t, _ = u.shape
    _, P, _, N = ssm_dims(sizes)
    taps, eps = sizes["conv_kernel"], sizes["norm_eps"]

    def conv(y, kernel, bias):
        # causal and depthwise: tap j weighs the input taps-1-j back
        padded = q.operand(jnp.pad(y, ((0, 0), (taps - 1, 0), (0, 0))))
        return silu(q.result(sum(q.operand(kernel[j]) * padded[:, j:j + t]
                                 for j in range(taps))) + bias)

    project = lambda name: mm("btd,de->bte", u, w["w_" + name])
    after_conv = lambda name: conv(project(name), w["conv_" + name],
                                   w["bias_" + name])
    x = after_conv("x").reshape(b, t, groups, rep, P)
    B = after_conv("B").reshape(b, t, groups, N)
    C = after_conv("C").reshape(b, t, groups, N)
    delta = jax.nn.softplus(project("dt") + w["dt_bias"])
    log_a = -delta * jnp.exp(w["A_log"])
    heads = lambda v: v.reshape(b, t, groups, rep)
    # the recurrence's products (x B^T, S C) take x, B and C as operands
    y = jnp.stack([q.result(recurrence(
        q.operand(x[:, :, g]), heads(delta)[:, :, g], heads(log_a)[:, :, g],
        q.operand(B[:, :, g]), q.operand(C[:, :, g])))
        for g in range(groups)], axis=2)
    y = y + w["D"].reshape(groups, rep)[..., None] * x
    # the gate first, then the norm: the mean square over a group's channels
    gated = y.reshape(b, t, groups, rep * P) * silu(project("z")).reshape(
        b, t, groups, rep * P)
    normed = gated / jnp.sqrt(jnp.mean(jnp.square(gated), axis=-1,
                                       keepdims=True) + eps)
    return mm("bte,ed->btd", normed.reshape(b, t, -1) * w["norm"],
              w["w_out"])


def attention(qs, ks, vs, mm):
    """qs [B,T,H,hd], ks/vs [B,T,KV,hd] -> [B,T,H,hd], causal; one
    (sequence, query head, block of queries) at a time."""
    b, t, h, hd = qs.shape
    rep = h // ks.shape[2]
    blocks = -(-t // QUERY_BLOCK)
    pad = blocks * QUERY_BLOCK - t
    j = jnp.arange(t)[None, :]

    def block(qb, start, kh, vh):       # [Q,hd], [], [T,hd], [T,hd]
        i = start + jnp.arange(QUERY_BLOCK)[:, None]
        s = mm("qd,sd->qs", qb, kh) / np.sqrt(hd)
        s = jnp.where(j <= i, s, -jnp.inf)
        s = s - jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s)
        p = p / jnp.sum(p, axis=-1, keepdims=True)
        return mm("qs,sd->qd", p, vh)

    def head(args):                     # [T,hd] each
        qh, kh, vh = args
        qh = jnp.pad(qh, ((0, pad), (0, 0))).reshape(blocks, QUERY_BLOCK, hd)
        out = jax.lax.map(
            lambda a: jax.checkpoint(block)(a[0], a[1], kh, vh),
            (qh, jnp.arange(blocks) * QUERY_BLOCK))
        return out.reshape(-1, hd)[:t]

    by_head = lambda y, n: jnp.repeat(y, n, axis=2).transpose(
        0, 2, 1, 3).reshape(b * h, t, hd)
    out = jax.lax.map(head, (by_head(qs, 1), by_head(ks, rep),
                             by_head(vs, rep)))
    return out.reshape(b, h, t, hd).transpose(0, 2, 1, 3)


def full_attention(p, x, sizes, mm):
    b, t, _ = x.shape
    h, kv, hd = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                 sizes["head_dim"])
    qs = mm("btd,de->bte", x, p["wq"]).reshape(b, t, h, hd)
    ks = mm("btd,de->bte", x, p["wk"]).reshape(b, t, kv, hd)
    vs = mm("btd,de->bte", x, p["wv"]).reshape(b, t, kv, hd)
    o = attention(qs, ks, vs, mm)                           # no rotary
    return mm("bte,ed->btd", o.reshape(b, t, h * hd), p["wo"])


def route(p, x, sizes, mm):
    """[S, top_k] expert ids over all published experts, chosen by ``s +
    b``, and their weights: ``s`` over its sum, times the scaling
    factor."""
    scores = sigmoid(mm("sd,de->se", x, p["router"]))
    _, ids = jax.lax.top_k(scores + p["router_bias"],
                           sizes["num_experts_per_tok"])
    top = jnp.take_along_axis(scores, ids, axis=-1)
    return ids, (sizes["routed_scaling_factor"] * top
                 / jnp.sum(top, axis=-1, keepdims=True))


def expert_layer(p, x, sizes, mm, first_expert=None, held=None):
    """x [S, d].  ``(routed, shared)`` parts: the routed part of the
    experts ``first_expert .. first_expert + held`` (the configuration's
    share by default) through the latent and back, and the shared expert's,
    which every chip computes alike.  The down-projection and ``W_up`` are
    every chip's too; ``W_up`` is linear, so the shares' routed parts add
    up to the whole layer's."""
    first = sizes["first_expert"] if first_expert is None else first_expert
    held = sizes["n_routed_experts"] if held is None else held
    ids, weights = route(p, x, sizes, mm)
    latent = mm("sd,dl->sl", x, p["w_down"])

    def expert(e, w1, w2):              # one expert's matrices, as stored
        w1, w2 = w1.astype(jnp.float32), w2.astype(jnp.float32)
        w = jnp.sum(jnp.where(ids == first + e, weights, 0.0), axis=-1)
        return w[:, None] * mm("sf,fl->sl",
                               relu2(mm("sl,lf->sf", latent, w1)), w2)

    # the sum is the carry, and no expert's backward pass needs it
    routed, _ = jax.lax.scan(
        lambda total, of: (total + jax.checkpoint(expert)(*of), None),
        jnp.zeros_like(latent), (jnp.arange(held), p["w1"], p["w2"]))
    shared = mm("sf,fd->sd", relu2(mm("sd,df->sf", x, p["shared_w1"])),
                p["shared_w2"])
    return mm("sl,ld->sd", routed, p["w_up"]), shared


def _layer(p, x, sizes, mm, q):
    # the experts' stacks stay in their storage type until an expert is
    # computed
    p = jax.tree_util.tree_map(
        lambda w: w if w.ndim == 3 else w.astype(jnp.float32), p)
    u = rms_norm(x, p["norm"], sizes["norm_eps"])
    if "ssm" in p:
        return x + mamba2(p["ssm"], u, sizes, mm, q)
    if "attn" in p:
        return x + full_attention(p["attn"], u, sizes, mm)
    b, t, d = x.shape
    return x + sum(expert_layer(p["moe"], u.reshape(b * t, d), sizes,
                                mm)).reshape(b, t, d)


def _matmul(q):
    return lambda spec, a, b: q.result(
        jnp.einsum(spec, q.operand(a), q.operand(b)))


def hidden(params, tokens, sizes, q):
    """The last layer's output ``[B, T, hidden]``, before the final norm."""
    mm = _matmul(q)
    x = params["embed"].astype(jnp.float32)[tokens]
    for p in params["layers"]:      # each layer recomputed on its own
        x = jax.checkpoint(functools.partial(
            _layer, sizes=sizes, mm=mm, q=q))(p, x)
    return x


def logits_of(params, x, sizes, q):
    return _matmul(q)("btd,dv->btv", rms_norm(
        x, params["final_norm"].astype(jnp.float32), sizes["norm_eps"]),
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
    add = lambda a, b: jax.tree_util.tree_map(jnp.add, a, b)
    return (jax.jit(lambda k: init_weights(k, sizes)),
            jax.jit(lambda k, r: make_batch(k, sizes, r)),
            jax.jit(jax.value_and_grad(functools.partial(
                loss_fn, sizes=sizes, precision=precision))),
            jax.jit(add, donate_argnums=(0, 1)),
            jax.jit(adam_step, donate_argnums=(0, 2, 3)))


def follow(sizes, key, world, steps, precision="float32"):
    """The first ``steps`` synchronous data-parallel steps at the seeded
    weights: per-rank losses, the norm of the first averaged gradient and
    of the parameters' change, leaf by leaf (``reference/qwen3_next.py``'s
    ``follow``, with this model): a rank's sequences one at a time, their
    gradients added up in the gradients' storage type, as the ranks' are.
    The first gradient is the sum's first term itself, not added to a tree
    of zeros: with one sequence in all, the 2.9 GB that tree would hold
    beside the weights, the gradient and both moments are what does not
    fit."""
    weights, batch, grad, add, update = _programs(scalars(sizes), precision)
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
                    loss, g = grad(params, tokens[b:b + 1],
                                   targets[b:b + 1])
                    mean = g if mean is None else add(mean, g)
                    of_rank.append(float(loss))
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
