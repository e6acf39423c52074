"""ResNet-50 — BASELINE config #2, the canonical Horovod benchmark
(reference: ``examples/pytorch/pytorch_imagenet_resnet50.py`` and
``*_synthetic_benchmark.py``; published numbers in ``docs/benchmarks.rst``).

TPU-first notes: NHWC layout, bf16 compute / f32 batch-norm statistics and
params (the MXU-friendly mixed precision), cross-replica SyncBatchNorm via
psum over the dp axis (parity with the reference's
``horovod/torch/sync_batch_norm.py``), explicit-SPMD train step like the
other models.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax

from ..compat import shard_map
from jax.sharding import Mesh, PartitionSpec as P
from ..compat import axis_size as compat_axis_size

BLOCKS = {18: (2, 2, 2, 2), 34: (3, 4, 6, 3), 50: (3, 4, 6, 3),
          101: (3, 4, 23, 3), 152: (3, 8, 36, 3)}
BOTTLENECK = {50, 101, 152}


@dataclasses.dataclass(frozen=True)
class ResNetConfig:
    depth: int = 50
    num_classes: int = 1000
    width: int = 64
    compute_dtype: Any = jnp.bfloat16
    bn_momentum: float = 0.9
    bn_eps: float = 1e-5
    sync_bn_axis: Optional[str] = "hvd"   # cross-replica batch norm axis


def _conv_init(key, shape):
    fan_in = shape[0] * shape[1] * shape[2]
    return jax.random.normal(key, shape, jnp.float32) * np.sqrt(2.0 / fan_in)


def _bn_init(ch):
    return {"scale": jnp.ones((ch,), jnp.float32),
            "bias": jnp.zeros((ch,), jnp.float32)}


def _bn_stats(ch):
    return {"mean": jnp.zeros((ch,), jnp.float32),
            "var": jnp.ones((ch,), jnp.float32)}


def init_params(cfg: ResNetConfig, key):
    """Returns (params, batch_stats)."""
    keys = iter(jax.random.split(key, 1024))
    stages = BLOCKS[cfg.depth]
    bottleneck = cfg.depth in BOTTLENECK
    expansion = 4 if bottleneck else 1

    params: dict = {"stem": {"w": _conv_init(next(keys), (7, 7, 3, cfg.width)),
                             "bn": _bn_init(cfg.width)}}
    stats: dict = {"stem": _bn_stats(cfg.width)}
    in_ch = cfg.width
    for si, n_blocks in enumerate(stages):
        out_ch = cfg.width * (2 ** si) * expansion
        mid_ch = cfg.width * (2 ** si)
        blocks_p, blocks_s = [], []
        for bi in range(n_blocks):
            stride = 2 if (si > 0 and bi == 0) else 1
            bp: dict = {}
            bs: dict = {}
            if bottleneck:
                shapes = [(1, 1, in_ch, mid_ch), (3, 3, mid_ch, mid_ch),
                          (1, 1, mid_ch, out_ch)]
            else:
                shapes = [(3, 3, in_ch, mid_ch), (3, 3, mid_ch, out_ch)]
            for ci, shp in enumerate(shapes):
                bp[f"conv{ci}"] = {"w": _conv_init(next(keys), shp),
                                   "bn": _bn_init(shp[-1])}
                bs[f"conv{ci}"] = _bn_stats(shp[-1])
            if in_ch != out_ch or stride != 1:
                bp["proj"] = {"w": _conv_init(next(keys),
                                              (1, 1, in_ch, out_ch)),
                              "bn": _bn_init(out_ch)}
                bs["proj"] = _bn_stats(out_ch)
            blocks_p.append(bp)
            blocks_s.append(bs)
            in_ch = out_ch
        params[f"stage{si}"] = blocks_p
        stats[f"stage{si}"] = blocks_s
    params["fc"] = {"w": jax.random.normal(next(keys), (in_ch, cfg.num_classes),
                                           jnp.float32) * 0.01,
                    "b": jnp.zeros((cfg.num_classes,), jnp.float32)}
    return params, stats


def _batch_norm(x, bn, stats, cfg: ResNetConfig, train: bool):
    """BN in f32 with optional cross-replica (Sync) statistics.

    Parity: ``horovod/torch/sync_batch_norm.py`` — mean/var are averaged
    over the dp axis with psum before normalization.
    """
    xf = x.astype(jnp.float32)
    if train:
        axes = (0, 1, 2)
        mean = jnp.mean(xf, axis=axes)
        mean2 = jnp.mean(jnp.square(xf), axis=axes)
        if cfg.sync_bn_axis:
            n = compat_axis_size(cfg.sync_bn_axis)
            mean = lax.psum(mean, cfg.sync_bn_axis) / n
            mean2 = lax.psum(mean2, cfg.sync_bn_axis) / n
        var = mean2 - jnp.square(mean)
        new_stats = {
            "mean": cfg.bn_momentum * stats["mean"]
                    + (1 - cfg.bn_momentum) * mean,
            "var": cfg.bn_momentum * stats["var"]
                   + (1 - cfg.bn_momentum) * var,
        }
    else:
        mean, var = stats["mean"], stats["var"]
        new_stats = stats
    y = (xf - mean) * lax.rsqrt(var + cfg.bn_eps) * bn["scale"] + bn["bias"]
    return y.astype(x.dtype), new_stats


def _conv(x, w, stride=1):
    return lax.conv_general_dilated(
        x, w.astype(x.dtype), window_strides=(stride, stride), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def forward(params, stats, images, cfg: ResNetConfig, train: bool = True):
    """images [B, H, W, 3] -> (logits [B, classes], new_stats)."""
    x = images.astype(cfg.compute_dtype)
    new_stats: dict = {}

    y = _conv(x, params["stem"]["w"], stride=2)
    y, new_stats["stem"] = _batch_norm(y, params["stem"]["bn"], stats["stem"],
                                       cfg, train)
    y = jax.nn.relu(y)
    y = lax.reduce_window(y, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          "SAME")

    bottleneck = cfg.depth in BOTTLENECK
    for si in range(len(BLOCKS[cfg.depth])):
        blocks_p = params[f"stage{si}"]
        blocks_s = stats[f"stage{si}"]
        stage_stats = []
        for bi, (bp, bs) in enumerate(zip(blocks_p, blocks_s)):
            stride = 2 if (si > 0 and bi == 0) else 1
            res = y
            bstat: dict = {}
            n_convs = 3 if bottleneck else 2
            h = y
            for ci in range(n_convs):
                s = stride if ci == (1 if bottleneck else 0) else 1
                h = _conv(h, bp[f"conv{ci}"]["w"], stride=s)
                h, bstat[f"conv{ci}"] = _batch_norm(
                    h, bp[f"conv{ci}"]["bn"], bs[f"conv{ci}"], cfg, train)
                if ci < n_convs - 1:
                    h = jax.nn.relu(h)
            if "proj" in bp:
                res = _conv(res, bp["proj"]["w"], stride=stride)
                res, bstat["proj"] = _batch_norm(
                    res, bp["proj"]["bn"], bs["proj"], cfg, train)
            y = jax.nn.relu(h + res)
            stage_stats.append(bstat)
        new_stats[f"stage{si}"] = stage_stats

    y = jnp.mean(y.astype(jnp.float32), axis=(1, 2))
    logits = y @ params["fc"]["w"] + params["fc"]["b"]
    return logits, new_stats


def loss_fn(params, stats, images, labels, cfg: ResNetConfig,
            axis_name: Optional[str] = "hvd"):
    logits, new_stats = forward(params, stats, images, cfg, train=True)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    denom = float(nll.size)
    if axis_name:
        denom = denom * compat_axis_size(axis_name)
    return jnp.sum(nll) / denom, new_stats


def make_train_step(cfg: ResNetConfig, optimizer,
                    axis_name: Optional[str] = "hvd"):
    # The four parts of the step under ``jax.named_scope``: metadata only
    # (the HLO is the same without them), and what a device trace shows an
    # operation to belong to.  ``value_and_grad`` split into its two
    # halves so that each has its own name.
    def step(params, stats, opt_state, images, labels):
        with jax.named_scope("forward"):
            loss_partial, backward, new_stats = jax.vjp(
                lambda p: loss_fn(p, stats, images, labels, cfg, axis_name),
                params, has_aux=True)
        with jax.named_scope("backward"):
            grads, = backward(jnp.ones_like(loss_partial))
        with jax.named_scope("gradient_exchange"):
            if axis_name:
                grads = jax.tree_util.tree_map(
                    lambda g: lax.psum(g, axis_name), grads)
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        loss = lax.psum(loss_partial, axis_name) if axis_name else loss_partial
        return params, new_stats, opt_state, loss

    return step


def make_sharded_train_step(cfg: ResNetConfig, optimizer, mesh: Mesh,
                            axis_name: str = "hvd"):
    step = make_train_step(cfg, optimizer, axis_name)
    return jax.jit(shard_map(
        step, mesh=mesh,
        in_specs=(P(), P(), P(), P(axis_name), P(axis_name)),
        out_specs=(P(), P(), P(), P()), check_vma=False),
        donate_argnums=(0, 1, 2))


def synthetic_batch(batch: int, image_size: int = 224,
                    num_classes: int = 1000,
                    seed: int = 0) -> Tuple[np.ndarray, np.ndarray]:
    rng = np.random.RandomState(seed)
    x = rng.randn(batch, image_size, image_size, 3).astype(np.float32)
    y = rng.randint(0, num_classes, size=(batch,)).astype(np.int32)
    return x, y
