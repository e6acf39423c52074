"""What the decoder families' tests share (``test_qwen3_next.py``,
``test_olmo_hybrid.py``, ``test_nemotron_h.py``, ``test_ouro.py``,
``test_jamba.py``): the seeded weights and batch of a reference at a set of
sizes, and what the plain reference makes of them, **worked out once a
process and kept**.  A parametrised case then compiles only what it varies
(the flash kernel on or off, the scan a group at a time, a planted fault).
The three families that run the dropless expert layer share its block
cases (:func:`expert_blocks_case`): the layer against a plain loop over the
held experts under four routings.

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


# ------------------------------------------------- the expert layer's blocks
def experts_by_loop(x, p, cfg):
    """``dropless_moe_ffn``'s ``y`` by a plain loop over the held experts:
    every token through each, weighed by what the router gave it (0 where
    it did not choose the expert).  No sort, no block, no grouped product."""
    from horovod_tpu.models import moe
    ids, weights = moe.dropless_route(x, p["router"], cfg,
                                      p.get("router_bias"))

    def expert(z, w1, w3, w2, weight=None):
        hidden = (jax.nn.silu(z @ w1) * (z @ w3) if cfg.gated
                  else jnp.square(jax.nn.relu(z @ w1)))
        return (hidden if weight is None else hidden * weight[:, None]) @ w2

    z = x @ p["w_down"] if cfg.d_latent else x
    y = jnp.zeros_like(z)
    for e in range(cfg.held):
        chose = jnp.sum(jnp.where(ids == cfg.first_expert + e, weights, 0),
                        axis=1)
        y = y + expert(z, p["w1"][e], p["w3"][e] if cfg.gated else None,
                       p["w2"][e], chose)
    if cfg.d_latent:
        y = y @ p["w_up"]
    if cfg.d_shared:
        shared = expert(x, p["shared_w1"], p.get("shared_w3"),
                        p["shared_w2"])
        if cfg.shared_gate:
            shared = shared * jax.nn.sigmoid(x @ p["shared_gate"])[:, None]
        y = y + shared
    return y


# routing -> (what the four held experts' logits get on top, live blocks
# of 8).  ``three_blocks``: the first held expert is every token's choice,
# the second about a third's: 96 + 30-odd rows, the last block part full.
EXPERT_ROUTINGS = {"even": (0.0, 1), "all_held": (40.0, 8),
                   "none_held": (-40.0, 0),
                   "three_blocks": ((40.0, 1.6, -40.0, -40.0), 3)}


def expert_blocks_case(cfg, routing):
    """The dropless layer (``cfg``: 64 experts, top-4, experts 8..12 held,
    float32) on 96 tokens against :func:`experts_by_loop`: ``y``, the
    gradients to ``x`` and to every matrix, and ``live_blocks`` against
    the counts.  The 384 sorted assignments make 8 blocks of 48; even
    routing sends 24 rows here.  The routing is steered through a
    constant first feature of ``x`` that the held experts' router columns
    read (both scorings, the bias or none)."""
    from horovod_tpu.models import moe
    assert (cfg.n_experts, cfg.top_k, cfg.first_expert, cfg.held) == (
        64, 4, 8, 4)
    assert moe.dropless_blocks(96 * 4, cfg) == 8
    push, live = EXPERT_ROUTINGS[routing]
    params = moe.dropless_init_params(cfg, jax.random.PRNGKey(5))
    x = jax.random.normal(jax.random.PRNGKey(6), (96, cfg.d_model)
                          ).at[:, 0].set(1.0)
    held = slice(cfg.first_expert, cfg.first_expert + cfg.held)
    params["router"] = params["router"].at[0, held].add(jnp.asarray(push))

    def program(p, x):
        y, counts = moe.dropless_moe_ffn(x, p, cfg)
        return jnp.sum(y * jnp.cos(y)), (y, counts)

    def plain(p, x):
        y = experts_by_loop(x, p, cfg)
        return jnp.sum(y * jnp.cos(y)), y

    with jax.default_matmul_precision("highest"):
        (_, (y, counts)), got = jax.jit(jax.value_and_grad(
            program, argnums=(0, 1), has_aux=True))(params, x)
        (_, y_want), want = jax.jit(jax.value_and_grad(
            plain, argnums=(0, 1), has_aux=True))(params, x)
    rows = int(counts.sum())
    assert int(moe.live_blocks(counts, 96 * 4, cfg)) == live == -(-rows // 48)
    if routing == "three_blocks":
        assert rows % 48                      # the last live block part full
    if routing == "all_held":
        assert rows == 96 * 4
    assert float(jnp.max(jnp.abs(y - y_want))) <= 1e-5 * float(
        jnp.max(jnp.abs(y_want)))
    got[0].pop("router_bias", None), want[0].pop("router_bias", None)
    assert set(got[0]) == set(want[0])
    for name in want[0]:
        scale = float(jnp.max(jnp.abs(want[0][name]))) or 1.0
        assert float(jnp.max(jnp.abs(got[0][name] - want[0][name]))
                     ) <= 1e-4 * scale, name
    assert worst_rel(got[1], want[1]) <= 1e-4
    # the held experts' matrices take a gradient where a row is held
    assert (float(jnp.max(jnp.abs(got[0]["w1"]))) > 0) == bool(rows)
