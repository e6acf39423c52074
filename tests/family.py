"""What the decoder families' tests share (``test_qwen3_next.py``,
``test_olmo_hybrid.py``, ``test_nemotron_h.py``, ``test_ouro.py``,
``test_jamba.py``): the seeded weights and batch of a reference at a set of
sizes, and what the plain reference makes of them, **worked out once a
process and kept**.  A parametrised case then compiles only what it varies
(the flash kernel on or off, the scan a group at a time, a planted fault).

The reference stays ``benchmark/reference/*``: the yardstick, which shares
no code with the program.  A new family's tests start from here: a
``Seeded(ref, SIZES, KEY)`` at the top of the file, ``want`` read from it.

A plain module, imported as ``slice_harness`` is (``tests/`` is no package).
"""

import contextlib
import functools

import jax
import jax.numpy as jnp


def worst_rel(a, b):
    """The largest ``max |x - y| / max |y|`` over two trees' leaves."""
    return max(float(jnp.max(jnp.abs(x - y)) / (jnp.max(jnp.abs(y)) + 1e-12))
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


class Seeded:
    """A reference module's own draw at ``sizes`` from ``key`` (weights, and
    rank 0's batch), and the reference's results on it under ``highest``
    matmul precision.  Nothing is computed until it is read, so an instance
    at a test module's top costs collection nothing; everything read is
    kept, so the cases of a file (one xdist worker under ``--dist
    loadfile``) share it.

    ``change`` is applied to the weights first (a second instance for a
    test that needs other weights, shared by its cases)."""

    def __init__(self, ref, sizes, key, change=None):
        self.ref, self.sizes, self.key, self.change = ref, sizes, key, change
        self._kept = {}

    @functools.cached_property
    def _drawn(self):
        params = self.ref.init_weights(self.key, self.sizes)
        if self.change is not None:
            params = self.change(params)
        return (params,) + tuple(self.ref.make_batch(self.key, self.sizes, 0))

    def __iter__(self):
        """``params, tokens, targets``; the tree's containers are new each
        time, the arrays are the kept ones."""
        params, tokens, targets = self._drawn
        return iter((jax.tree_util.tree_map(lambda x: x, params), tokens,
                     targets))

    def kept(self, name, compute):
        """``compute(params, tokens, targets)`` under ``highest`` matmul
        precision, once a process for this draw and ``name``."""
        if name not in self._kept:
            with jax.default_matmul_precision("highest"):
                self._kept[name] = jax.block_until_ready(compute(*self))
        return self._kept[name]

    @property
    def logits(self):
        """``ref.forward`` on the draw."""
        return self.kept("logits", lambda params, tokens, _: jax.jit(
            lambda p: self.ref.forward(p, tokens, self.sizes))(params))

    @property
    def loss_and_grads(self):
        """``(loss, gradients)`` of ``ref.loss_fn`` on the draw."""
        return self.kept("loss_and_grads", lambda params, tokens, targets:
                         jax.jit(jax.value_and_grad(lambda p: self.ref.loss_fn(
                             p, tokens, targets, self.sizes)))(params))


@contextlib.contextmanager
def planted(module, name, value):
    """``module.name`` is ``value`` inside the block, and what is traced
    there is traced afresh.  JAX keeps a traced region (a ``jax.checkpoint``
    or ``custom_vjp`` body) by its function and its arguments' shapes: a
    region that read the sound name would otherwise be reused by the broken
    trace, and the broken one by the next test.  So the caches are cleared
    on the way in and on the way out, and nowhere else: read what is
    :meth:`Seeded.kept` BEFORE entering, so that it is arrays by then."""
    sound = getattr(module, name)
    jax.clear_caches()
    setattr(module, name, value)
    try:
        yield
    finally:
        setattr(module, name, sound)
        jax.clear_caches()
