"""What both plain references share: the control's rounding and the
per-leaf norms that ``compare.py`` reads (``jax.numpy`` only)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


class quantizer:
    """The control's rounding: every matrix product and convolution of the
    reference is written ``q.result(product(q.operand(a), q.operand(b)))``.

    ``float32``: both are the identity (the reference proper).  Below it,
    ``operand`` rounds what enters a product to the lower type and back,
    passing the gradient straight through, and ``result`` is the identity
    forward and rounds the cotangent that enters the product's backward
    pass: both passes compute on rounded operands and accumulate in
    float32.  ``float8`` takes e4m3 forward and e5m2 backward, each with one
    scale a tensor, as float8 training recipes do."""

    TYPES = {"bfloat16": (jnp.bfloat16, jnp.bfloat16),
             "float8": (jnp.float8_e4m3fn, jnp.float8_e5m2)}

    def __init__(self, precision):
        if precision != "float32" and precision not in self.TYPES:
            raise ValueError(f"unknown precision {precision!r}")
        self.exact = precision == "float32"
        if not self.exact:
            self.forward, backward = self.TYPES[precision]
            self._result = jax.custom_vjp(lambda y: y)
            self._result.defvjp(
                lambda y: (y, None),
                lambda _, g: (self.rounded(g, backward),))

    @staticmethod
    def rounded(x, dtype):
        if jnp.dtype(dtype).itemsize > 1:
            return x.astype(dtype).astype(jnp.float32)
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / float(
            jnp.finfo(dtype).max)
        return (x / scale).astype(dtype).astype(jnp.float32) * scale

    def operand(self, x):
        if self.exact:
            return x
        return x + jax.lax.stop_gradient(self.rounded(x, self.forward) - x)

    def result(self, y):
        return y if self.exact else self._result(y)


def mesh_batch(make_batch, key, sizes, mesh, spec):
    """The batch of a process that drives every device of ``mesh``: the
    ranks' batches in mesh order, made and placed in one jitted call."""
    from jax.sharding import NamedSharding
    return jax.jit(lambda k: tuple(
        jnp.concatenate(parts) for parts in zip(*(
            make_batch(k, sizes, r) for r in range(mesh.size)))),
        out_shardings=NamedSharding(mesh, spec))(key)


def leaf_norms(tree, minus=None):
    """{leaf path: l2 norm} of ``tree`` (of ``tree - minus`` where given),
    in float32.  One jitted call, so that no difference is ever held whole,
    and one small host transfer."""
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    f32 = lambda x: x.astype(jnp.float32)

    def norms(xs, ys):
        if ys is None:
            return [jnp.sqrt(jnp.sum(jnp.square(f32(x)))) for x in xs]
        return [jnp.sqrt(jnp.sum(jnp.square(f32(x) - f32(y))))
                for x, y in zip(xs, ys)]

    values = jax.jit(norms)(
        [x for _, x in leaves],
        None if minus is None else jax.tree_util.tree_leaves(minus))
    # the key says how many dimensions the leaf has: compare.py holds
    # matrices and kernels leaf by leaf, and leaves vectors out
    return {f"{jax.tree_util.keystr(p)}:{x.ndim}d": float(n)
            for (p, x), n in zip(leaves, values)}
