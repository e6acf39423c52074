"""ResNet through horovod_tpu's public entry points, built for one mix.

``build`` and ``make_eager_step`` are ``examples/resnet_synthetic.py``'s,
copied so that a later PR cannot move the traffic: weights synchronized
from rank 0 with ``hvd.broadcast_parameters``, ``hvd.DistributedOptimizer``
around SGD with momentum at ``0.01 * hvd.size()``, and the step of the
mix's ``step_mode``.  What differs: the weights and the fixed batch come
from the benchmark's own generator (``reference/resnet.py``), made on the
device from the seed in one jitted call, and the eager step's phases carry
``jax.profiler.TraceAnnotation`` spans.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax
from jax.sharding import PartitionSpec as P

from horovod_tpu.models import resnet

from ..reference import resnet as data
from ..reference.common import mesh_batch

ITEM = "image"


def model_flops_per_item(sizes):
    """Forward plus backward for one image, from the convolutions' shapes:
    a multiply-add is 2, the backward pass twice the forward, nothing
    recomputed."""
    convs, feat = data.conv_shapes(sizes)
    forward = sum(2 * kh * kw * cin * cout * h * h
                  for (kh, kw, cin, cout), h in convs)
    return 3.0 * (forward + 2 * feat * sizes["num_classes"])


def make_eager_step(cfg, optimizer, annotate):
    """Compiled forward/backward, then the eager distributed update: three
    phases a step, each under a host span."""
    @jax.jit
    def grads_fn(params, stats, images, labels):
        def loss(p, s):
            return resnet.loss_fn(p, s, images, labels, cfg, axis_name=None)
        (l, stats), grads = jax.value_and_grad(loss, has_aux=True)(
            params, stats)
        return l, stats, grads

    apply_fn = jax.jit(optax.apply_updates)
    programs = {}

    def step(state, batch):
        params, stats, opt_state = state
        with annotate("bench/grads"):
            l, stats, grads = programs["grads"](params, stats, *batch)
        with annotate("bench/update"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
        with annotate("bench/apply"):
            params = apply_fn(params, updates)
        return (params, stats, opt_state), l

    def compile_for(state, batch):
        programs["grads"] = grads_fn.lower(
            state[0], state[1], *batch).compile()
        return programs["grads"]

    return step, compile_for


def build(hvd, cell, key, annotate):
    sizes, mode = cell.sizes, cell.mix["step_mode"]
    cfg = resnet.ResNetConfig(
        depth=sizes["depth"], num_classes=sizes["num_classes"],
        width=sizes["width"], compute_dtype=jnp.dtype(sizes["dtype"]),
        sync_bn_axis=None)
    weights = jax.jit(lambda k: data.init_weights(k, sizes))
    # Only rank 0 starts at the seed's weights: broadcast_parameters has
    # to carry them, or the comparison with the reference fails.
    rank = hvd.rank()
    params, stats = weights(key if rank == 0
                            else jax.random.fold_in(key, 7919 + rank))
    params = hvd.broadcast_parameters(params, root_rank=0)
    optimizer = hvd.DistributedOptimizer(
        optax.sgd(data.BASE_LR * hvd.size(), momentum=data.MOMENTUM))
    state = (params, stats, optimizer.init(params))

    if mode == "spmd":
        # One process drives every device of the mesh: the global batch is
        # the ranks' batches in mesh order.
        mesh = hvd.mesh()
        batch = mesh_batch(data.make_batch, key, sizes, mesh, P("hvd"))
        jitted = resnet.make_sharded_train_step(cfg, optimizer, mesh)
        compiled = jitted.lower(*state, *batch).compile()

        def step(state, batch):
            with annotate("bench/enqueue"):
                *state, loss = compiled(*state, *batch)
            return tuple(state), loss
    else:
        batch = jax.jit(lambda k: data.make_batch(k, sizes, rank))(key)
        step, compile_for = make_eager_step(cfg, optimizer, annotate)
        compiled = compile_for(state, batch)

    return {
        "step": step, "state": state, "batch": batch,
        "items_per_step_per_chip": sizes["batch_per_chip"],
        "flops_per_item": model_flops_per_item(sizes),
        "params_of": lambda s: s[0],
        # optax.sgd(momentum): the trace after one step IS the gradient
        # the optimizer was given.
        "first_gradient_of": lambda s: s[2].inner_state[0].trace,
        "seed_params": lambda: weights(key)[0],
        "temp_bytes": int(compiled.memory_analysis().temp_size_in_bytes),
        "compiled_text": compiled.as_text,
    }
