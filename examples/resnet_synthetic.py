"""Synthetic ResNet throughput benchmark (img/s) under data parallelism.

The rebuild of the reference's headline benchmark
(``examples/pytorch/pytorch_synthetic_benchmark.py``): train ResNet on
random data and report per-rank and aggregate images/sec.

Two step modes:

- ``--step-mode eager`` (default; works in every launch mode): compiled
  forward/backward, eager ``DistributedOptimizer.update`` whose allreduce
  rides the collective engine — measures the same framework path a user's
  training loop exercises.
- ``--step-mode spmd`` (single-process, >=1 local devices): the whole step —
  gradients, ``psum`` allreduce, parameter update — is one jitted
  ``shard_map`` over the device mesh, the TPU-first fused path (the
  benchmark's ``resnet50-spmd-1c`` cell runs this mode on the chip;
  ``--step-mode eager`` is ``resnet50-eager-1c`` / ``-np4``).

Run::

    torovodrun -np 4 python examples/resnet_synthetic.py --depth 50
    JAX_PLATFORMS=cpu torovodrun -np 2 python examples/resnet_synthetic.py \
        --depth 18 --image-size 32 --batch-size 4 --num-iters 2 --num-warmup 1
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvd
from horovod_tpu.models import resnet


def parse_args():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--depth", type=int, default=50,
                   choices=sorted(resnet.BLOCKS),
                   help="ResNet depth (18/34/50/101/152)")
    p.add_argument("--batch-size", type=int, default=32,
                   help="per-rank batch size")
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--num-classes", type=int, default=1000)
    p.add_argument("--num-iters", type=int, default=10,
                   help="timed iterations")
    p.add_argument("--num-warmup", type=int, default=3,
                   help="untimed warmup iterations (includes compile)")
    p.add_argument("--step-mode", choices=("eager", "spmd"), default="eager")
    p.add_argument("--fp32", action="store_true",
                   help="compute in float32 instead of bfloat16")
    return p.parse_args()


def make_eager_step(cfg, optimizer):
    """Compiled fwd/bwd + eager distributed update (per-process mode)."""
    @jax.jit
    def grads_fn(params, stats, images, labels):
        def loss(p, s):
            return resnet.loss_fn(p, s, images, labels, cfg, axis_name=None)
        (l, stats), grads = jax.value_and_grad(loss, has_aux=True)(
            params, stats)
        return l, stats, grads

    apply_fn = jax.jit(optax.apply_updates)

    def step(params, stats, opt_state, images, labels):
        l, stats, grads = grads_fn(params, stats, images, labels)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return apply_fn(params, updates), stats, opt_state, l

    return step


def build(depth=50, num_classes=1000, fp32=False, step_mode="eager", seed=0):
    """One run's ``(step, params, stats, opt_state)`` after ``hvd.init()``:
    ResNet weights from ``seed`` synchronized from rank 0, the distributed
    optimizer and the step function of ``step_mode``.  ``main`` and
    ``chip_smoke.py`` both train through this."""
    cfg = resnet.ResNetConfig(
        depth=depth, num_classes=num_classes,
        compute_dtype=jnp.float32 if fp32 else jnp.bfloat16,
        sync_bn_axis=None)
    params, stats = resnet.init_params(cfg, jax.random.PRNGKey(seed))
    params = hvd.broadcast_parameters(params, root_rank=0)

    optimizer = hvd.DistributedOptimizer(
        optax.sgd(0.01 * hvd.size(), momentum=0.9))
    opt_state = optimizer.init(params)
    if step_mode == "spmd":
        # One jitted shard_map step over the local device mesh: allreduce is
        # an in-graph psum XLA schedules over ICI.
        step = resnet.make_sharded_train_step(cfg, optimizer, hvd.mesh())
    else:
        step = make_eager_step(cfg, optimizer)
    return step, params, stats, opt_state


def main():
    args = parse_args()
    hvd.init()
    rank, size = hvd.rank(), hvd.size()

    step, params, stats, opt_state = build(
        args.depth, args.num_classes, args.fp32, args.step_mode)
    images, labels = resnet.synthetic_batch(
        args.batch_size, image_size=args.image_size,
        num_classes=args.num_classes, seed=rank)

    for _ in range(args.num_warmup):
        params, stats, opt_state, l = step(params, stats, opt_state,
                                           images, labels)
    jax.block_until_ready(l)

    t0 = time.time()
    for _ in range(args.num_iters):
        params, stats, opt_state, l = step(params, stats, opt_state,
                                           images, labels)
    jax.block_until_ready(l)
    dt = time.time() - t0

    img_per_sec = args.batch_size * args.num_iters / dt
    total = hvd.to_local(hvd.allreduce(np.float32(img_per_sec),
                                       name="imgs", op=hvd.Sum))
    if rank == 0:
        print(f"ResNet-{args.depth} batch={args.batch_size} world={size} "
              f"mode={args.step_mode}")
        print(f"per-rank: {img_per_sec:.1f} img/s")
        print(f"total:    {float(total):.1f} img/s", flush=True)
        print("DONE", flush=True)
    hvd.shutdown()


if __name__ == "__main__":
    main()
