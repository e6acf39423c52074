"""Plain float32 ResNet (v1.5) for the benchmark's ``correct``.

``jax.numpy`` and one ``lax`` primitive, nothing imported from
``horovod_tpu``.  A convolution is XLA's sliding product over an input
that is SAME-padded here, explicitly (a sum of shifted matrix products
beside it is what the tests hold it against), batch norm uses the batch's own statistics (training mode), the loss is
the mean softmax cross-entropy, the optimizer is SGD with momentum written
out.  Parameters are a nested dict with the same key names the system
under test uses (a layout, not code): ``stem``, ``stage<i>`` (a list of
blocks of ``conv<j>`` / ``proj``), ``fc``.

The weights and the data of a run are made HERE, from the seed, and handed
to the program: the reference takes nothing the program has made.

``precision`` is ``float32`` for the reference proper.  ``bfloat16`` and
``float8`` round the operands of every convolution and matrix product, in
the forward and the backward pass, and accumulate in float32
(``common.quantizer``): the control that has to come out as not correct.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .common import leaf_norms, quantizer

STAGES = {18: ((2, 2, 2, 2), False), 34: ((3, 4, 6, 3), False),
          50: ((3, 4, 6, 3), True)}
BN_EPS = 1e-5
MOMENTUM = 0.9
BASE_LR = 0.01          # times the world size, as upstream's benchmark


# ------------------------------------------------------------ weights, data
def _block_shapes(depth, width):
    """[(stage, block, stride, {name: conv shape})] in forward order."""
    stages, bottleneck = STAGES[depth]
    expansion = 4 if bottleneck else 1
    in_ch, out = width, []
    for si, n_blocks in enumerate(stages):
        mid, out_ch = width * 2 ** si, width * 2 ** si * expansion
        for bi in range(n_blocks):
            stride = 2 if (si > 0 and bi == 0) else 1
            if bottleneck:
                convs = [(1, 1, in_ch, mid), (3, 3, mid, mid),
                         (1, 1, mid, out_ch)]
            else:
                convs = [(3, 3, in_ch, mid), (3, 3, mid, out_ch)]
            shapes = {f"conv{i}": s for i, s in enumerate(convs)}
            if in_ch != out_ch or stride != 1:
                shapes["proj"] = (1, 1, in_ch, out_ch)
            out.append((si, bi, stride, shapes))
            in_ch = out_ch
    return out, in_ch


def conv_shapes(sizes):
    """``([(kernel shape, output height)], classifier inputs)`` for every
    convolution, in forward order: what the FLOP count reads."""
    blocks, feat = _block_shapes(sizes["depth"], sizes["width"])
    bottleneck = STAGES[sizes["depth"]][1]
    h = -(-sizes["image_size"] // 2)                  # stem, stride 2
    out = [((7, 7, 3, sizes["width"]), h)]
    h = -(-h // 2)                                    # max pool, stride 2
    for _, _, stride, shapes in blocks:
        h_out = -(-h // stride)
        for name, shape in shapes.items():
            # v1.5: only a bottleneck's first 1x1 still sees the input size
            out.append((shape, h if bottleneck and name == "conv0"
                        else h_out))
        h = h_out
    return out, feat


def init_weights(key, sizes):
    """He-normal convolutions, unit batch-norm scales, a small classifier:
    ``(params, batch_stats)`` in float32, one traced function."""
    blocks, feat = _block_shapes(sizes["depth"], sizes["width"])
    keys = iter(jax.random.split(key, 2 + sum(len(b[3]) for b in blocks)))

    def conv(shape):
        fan_in = shape[0] * shape[1] * shape[2]
        return {"w": jax.random.normal(next(keys), shape, jnp.float32)
                * np.sqrt(2.0 / fan_in),
                "bn": {"scale": jnp.ones((shape[-1],), jnp.float32),
                       "bias": jnp.zeros((shape[-1],), jnp.float32)}}

    def stat(ch):
        return {"mean": jnp.zeros((ch,), jnp.float32),
                "var": jnp.ones((ch,), jnp.float32)}

    params = {"stem": conv((7, 7, 3, sizes["width"]))}
    stats = {"stem": stat(sizes["width"])}
    for si, _, _, shapes in blocks:
        params.setdefault(f"stage{si}", []).append(
            {n: conv(s) for n, s in shapes.items()})
        stats.setdefault(f"stage{si}", []).append(
            {n: stat(s[-1]) for n, s in shapes.items()})
    params["fc"] = {
        "w": jax.random.normal(next(keys), (feat, sizes["num_classes"]),
                               jnp.float32) * 0.01,
        "b": jnp.zeros((sizes["num_classes"],), jnp.float32)}
    return params, stats


def make_batch(key, sizes, rank):
    """Rank ``rank``'s fixed synthetic batch: rows that all differ."""
    k1, k2 = jax.random.split(jax.random.fold_in(key, rank))
    b, s = sizes["batch_per_chip"], sizes["image_size"]
    return (jax.random.normal(k1, (b, s, s, 3), jnp.float32),
            jax.random.randint(k2, (b,), 0, sizes["num_classes"], jnp.int32))


# ------------------------------------------------------------------ forward
def _same(n, k, s):
    out = -(-n // s)
    total = max((out - 1) * s + k - n, 0)
    return total // 2, total - total // 2, out


def _padded(x, w, stride):
    top, bottom, ho = _same(x.shape[1], w.shape[0], stride)
    left, right, wo = _same(x.shape[2], w.shape[1], stride)
    return jnp.pad(x, ((0, 0), (top, bottom), (left, right), (0, 0))), ho, wo


def conv2d(x, w, stride, q):
    """SAME convolution: the padding is worked out here, the sliding
    product is XLA's own primitive (the one departure from ``jax.numpy``:
    the shifted form below took 156 s to compile for the chip)."""
    xp, _, _ = _padded(x, w, stride)
    return q.result(jax.lax.conv_general_dilated(
        q.operand(xp), q.operand(w), (stride, stride), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC")))


def conv2d_shifted(x, w, stride, q):
    """The same convolution as a sum of shifted matrix products: what the
    tests hold ``conv2d`` against."""
    xp, ho, wo = _padded(x, w, stride)
    xq, wq = q.operand(xp), q.operand(w)
    out = 0.0
    for i in range(w.shape[0]):
        for j in range(w.shape[1]):
            out = out + xq[:, i:i + (ho - 1) * stride + 1:stride,
                           j:j + (wo - 1) * stride + 1:stride, :] @ wq[i, j]
    return q.result(out)


def batch_norm(x, bn):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) / jnp.sqrt(var + BN_EPS) * bn["scale"] + bn["bias"]


def max_pool_3x3_s2(x):
    top, bottom, ho = _same(x.shape[1], 3, 2)
    left, right, wo = _same(x.shape[2], 3, 2)
    xp = jnp.pad(x, ((0, 0), (top, bottom), (left, right), (0, 0)),
                 constant_values=-jnp.inf)
    out = None
    for i in range(3):
        for j in range(3):
            win = xp[:, i:i + (ho - 1) * 2 + 1:2, j:j + (wo - 1) * 2 + 1:2, :]
            out = win if out is None else jnp.maximum(out, win)
    return out


def _block(bp, x, stride, bottleneck, q):
    names = ("conv0", "conv1", "conv2") if bottleneck else ("conv0", "conv1")
    strided = "conv1" if bottleneck else "conv0"      # v1.5: on the 3x3
    h = x
    for name in names:
        h = conv2d(h, bp[name]["w"], stride if name == strided else 1, q)
        h = batch_norm(h, bp[name]["bn"])
        if name != names[-1]:
            h = jnp.maximum(h, 0.0)
    if "proj" in bp:
        x = batch_norm(conv2d(x, bp["proj"]["w"], stride, q), bp["proj"]["bn"])
    return jnp.maximum(h + x, 0.0)


def loss_fn(params, images, labels, sizes, precision="float32"):
    q = quantizer(precision)
    bottleneck = STAGES[sizes["depth"]][1]
    x = conv2d(images.astype(jnp.float32), params["stem"]["w"], 2, q)
    x = max_pool_3x3_s2(jnp.maximum(batch_norm(x, params["stem"]["bn"]), 0.0))
    for si in range(len(STAGES[sizes["depth"]][0])):
        for bi, bp in enumerate(params[f"stage{si}"]):
            stride = 2 if (si > 0 and bi == 0) else 1
            # one block's activations at a time in the backward pass
            x = jax.checkpoint(functools.partial(
                _block, stride=stride, bottleneck=bottleneck, q=q))(bp, x)
    feat = jnp.mean(x, axis=(1, 2))
    logits = q.result(q.operand(feat) @ q.operand(params["fc"]["w"])) \
        + params["fc"]["b"]
    logp = logits - jax.scipy.special.logsumexp(logits, axis=-1,
                                                keepdims=True)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


# -------------------------------------------------------------- three steps
@functools.lru_cache(maxsize=None)
def _programs(sizes_items, precision):
    """The jitted pieces of ``follow``, compiled once for a set of sizes."""
    sizes = dict(sizes_items)
    return (jax.jit(lambda k: init_weights(k, sizes)[0]),
            jax.jit(lambda k, r: make_batch(k, sizes, r)),
            jax.jit(jax.value_and_grad(functools.partial(
                loss_fn, sizes=sizes, precision=precision))))


def scalars(sizes):
    return tuple(sorted((k, v) for k, v in sizes.items()
                        if isinstance(v, (int, float, str, bool))))


def follow(sizes, key, world, steps, precision="float32"):
    """The first ``steps`` synchronous data-parallel steps at the seeded
    weights, every rank's shard with its own batch statistics and the
    gradients averaged: per-rank losses, the norm of the first averaged
    gradient and of the parameters' change, leaf by leaf."""
    weights, batch, grad = _programs(scalars(sizes), precision)
    with jax.default_matmul_precision("highest"):
        params = start = weights(key)
        lr = BASE_LR * world
        trace = jax.tree_util.tree_map(jnp.zeros_like, params)
        losses = [[] for _ in range(world)]
        first = None
        for _ in range(steps):
            mean = None
            for r in range(world):
                loss, g = grad(params, *batch(key, r))
                losses[r].append(float(loss))
                mean = g if mean is None else jax.tree_util.tree_map(
                    jnp.add, mean, g)
            mean = jax.tree_util.tree_map(lambda x: x / world, mean)
            if first is None:
                first = leaf_norms(mean)
            trace = jax.tree_util.tree_map(
                lambda t, g: MOMENTUM * t + g, trace, mean)
            params = jax.tree_util.tree_map(
                lambda p, t: p - lr * t, params, trace)
        delta = leaf_norms(params, minus=start)
    return {"losses": losses, "grad_norms": first, "delta_norms": delta}
