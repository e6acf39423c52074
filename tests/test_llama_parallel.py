"""Flagship-model parallelism correctness: dp/tp/sp sharded training must
match the single-device reference run numerically.

This is the rebuild's analogue of the reference's collective-vs-local
assertions (SURVEY.md §4) applied at full-model scale: if the Megatron tp
operators, ring attention, and gradient psums are right, a sharded step is
bit-compatible (up to fp tolerance) with the unsharded one.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from horovod_tpu.models import llama
from horovod_tpu.parallel import spmd
from horovod_tpu.parallel.mesh import infer_mesh
from jax.sharding import PartitionSpec as P


def _data(cfg, batch=8, seq=16, seed=0):
    rng = np.random.RandomState(seed)
    tokens = rng.randint(0, cfg.vocab_size, size=(batch, seq)).astype(np.int32)
    targets = rng.randint(0, cfg.vocab_size, size=(batch, seq)).astype(np.int32)
    return jnp.asarray(tokens), jnp.asarray(targets)


@functools.lru_cache(maxsize=None)
def _reference_run(steps=2, batch=8, seq=16, **kw):
    """Unsharded single-device ground truth (all axes disabled, f32; ``kw``
    to ``llama.tiny``): ``(losses, params)``, worked out once a process
    for the cases that share it."""
    cfg = llama.tiny(dtype=jnp.float32, dp_axis=None, tp_axis=None,
                     sp_axis=None, **kw)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    opt = optax.sgd(0.1)
    opt_state = opt.init(params)
    step = jax.jit(llama.make_train_step(cfg, opt))
    tokens, targets = _data(cfg, batch, seq)
    losses = []
    for _ in range(steps):
        params, opt_state, loss = step(params, opt_state, tokens, targets)
        losses.append(float(loss))
    return losses, params


@pytest.mark.parametrize("tp,sp", [(1, 1), (2, 1), (1, 2), (2, 2)])
def test_sharded_matches_reference(tp, sp):
    ref_losses, ref_params = _reference_run()

    cfg = llama.tiny(dtype=jnp.float32)
    mesh = infer_mesh(8, tp=tp, sp=sp)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    pspecs = llama.param_specs(cfg)
    opt = optax.sgd(0.1)
    opt_state = opt.init(params)
    os_specs = spmd.infer_specs_like(opt_state, params, pspecs)
    data_spec = P(("dp", "ep", "pp"), "sp")  # batch over dp, seq over sp

    step = spmd.make_sharded_train_step(
        llama.make_train_step(cfg, opt), mesh, pspecs, os_specs, data_spec)

    params = spmd.shard_params(params, pspecs, mesh)
    tokens, targets = _data(cfg)
    losses = []
    for _ in range(2):
        params, opt_state, loss = step(params, opt_state, tokens, targets)
        losses.append(float(loss))

    np.testing.assert_allclose(losses, ref_losses, rtol=2e-4)
    # Parameters after 2 steps must agree leaf-for-leaf.
    ref_leaves = jax.tree_util.tree_leaves(ref_params)
    out_leaves = jax.tree_util.tree_leaves(
        jax.tree_util.tree_map(np.asarray, params))
    for a, b in zip(out_leaves, ref_leaves):
        np.testing.assert_allclose(a, np.asarray(b), rtol=3e-3, atol=3e-5)


@pytest.mark.parametrize("sp,tp,heads,kv_heads", [
    (2, 1, 4, 2),
    (2, 2, 8, 4),   # per-tp-shard kv heads (2) still divide by sp
    (4, 1, 8, 4),
])
def test_ulysses_sp_matches_reference(sp, tp, heads, kv_heads):
    """sp_impl="ulysses" (head-exchange sequence parallelism) trains
    numerics-identical to the unsharded reference, like the ring path.
    Ulysses needs (kv_heads / tp) % sp == 0 — GQA kv travels un-repeated."""
    hkw = dict(n_heads=heads, n_kv_heads=kv_heads)
    ref_losses, _ = _reference_run(**hkw)

    cfg = llama.tiny(dtype=jnp.float32, sp_impl="ulysses", **hkw)
    mesh = infer_mesh(8, tp=tp, sp=sp)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    pspecs = llama.param_specs(cfg)
    opt = optax.sgd(0.1)
    opt_state = opt.init(params)
    os_specs = spmd.infer_specs_like(opt_state, params, pspecs)
    step = spmd.make_sharded_train_step(
        llama.make_train_step(cfg, opt), mesh, pspecs, os_specs,
        P(("dp", "ep", "pp"), "sp"))
    params = spmd.shard_params(params, pspecs, mesh)
    tokens, targets = _data(cfg)
    losses = []
    for _ in range(2):
        params, opt_state, loss = step(params, opt_state, tokens, targets)
        losses.append(float(loss))
    np.testing.assert_allclose(losses, ref_losses, rtol=2e-4)


@pytest.mark.parametrize("pp,tp,sp,n_micro,pp_loss", [
    (2, 1, 1, 2, "broadcast"),   # pure pp
    (2, 1, 1, 4, "broadcast"),   # more microbatches than stages
    (4, 1, 1, 2, "broadcast"),   # deeper pipeline (1-layer slabs, 4 layers)
    (2, 2, 1, 2, "broadcast"),   # pp × tp
    (2, 1, 2, 2, "broadcast"),   # pp × sp (ring attention inside a stage)
    # last_stage loss: no [M,mb,T,D] activation broadcast — only the
    # scalar partial rides the psum (VERDICT r4 weak #5); must be
    # numerics-identical to broadcast AND the unsharded reference.
    (2, 1, 1, 2, "last_stage"),
    (4, 1, 1, 2, "last_stage"),
    (2, 2, 1, 2, "last_stage"),
    (2, 1, 2, 2, "last_stage"),
])
def test_pipeline_matches_reference(pp, tp, sp, n_micro, pp_loss):
    """pp=k training ≡ unsharded reference: stacked layer slabs over the pp
    axis, GPipe schedule, grads reassembled by sync_grads (VERDICT r3 weak
    #5a: pipeline parallelism must compose with the flagship model)."""
    n_layers = 4 if pp == 4 else 2
    # batch 16: per-shard batch stays divisible by n_micro at every dp size.
    ref_losses, ref_params = _reference_run(n_layers=n_layers, batch=16)

    cfg = llama.tiny(dtype=jnp.float32, n_layers=n_layers,
                     pp_axis="pp", n_microbatches=n_micro,
                     pp_loss=pp_loss)
    mesh = infer_mesh(8, tp=tp, sp=sp, pp=pp)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    pspecs = llama.param_specs(cfg)
    opt = optax.sgd(0.1)
    opt_state = opt.init(params)
    os_specs = spmd.infer_specs_like(opt_state, params, pspecs)
    # Batch over dp/ep only — every pipeline stage sees the same tokens.
    data_spec = P(("dp", "ep"), "sp")

    step = spmd.make_sharded_train_step(
        llama.make_train_step(cfg, opt), mesh, pspecs, os_specs, data_spec)

    params = spmd.shard_params(params, pspecs, mesh)
    tokens, targets = _data(cfg, batch=16)
    losses = []
    for _ in range(2):
        params, opt_state, loss = step(params, opt_state, tokens, targets)
        losses.append(float(loss))

    np.testing.assert_allclose(losses, ref_losses, rtol=2e-4)
    # Stacked slab layout vs the reference's per-layer list: compare
    # layer-by-layer through the stack axis.
    stacked = jax.tree_util.tree_map(np.asarray, params)
    for i, ref_layer in enumerate(ref_params["layers"]):
        for k, ref_w in ref_layer.items():
            np.testing.assert_allclose(
                stacked["layers"][k][i], np.asarray(ref_w),
                rtol=3e-3, atol=3e-5, err_msg=f"layer {i} {k}")
    for k in ("embed", "final_norm", "lm_head"):
        np.testing.assert_allclose(stacked[k], np.asarray(ref_params[k]),
                                   rtol=3e-3, atol=3e-5, err_msg=k)


def test_pipeline_remat_matches_reference():
    """remat_stages=True (jax.checkpoint around each stage) must be
    numerics-identical to the stored-activation pipeline AND the unsharded
    reference — remat changes memory, never math."""
    ref_losses, ref_params = _reference_run(batch=16)

    cfg = llama.tiny(dtype=jnp.float32, pp_axis="pp", n_microbatches=2,
                     remat_stages=True)
    mesh = infer_mesh(8, pp=2)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    pspecs = llama.param_specs(cfg)
    opt = optax.sgd(0.1)
    opt_state = opt.init(params)
    os_specs = spmd.infer_specs_like(opt_state, params, pspecs)
    step = spmd.make_sharded_train_step(
        llama.make_train_step(cfg, opt), mesh, pspecs, os_specs,
        P(("dp", "ep"), "sp"))
    params = spmd.shard_params(params, pspecs, mesh)
    tokens, targets = _data(cfg, batch=16)
    losses = []
    for _ in range(2):
        params, opt_state, loss = step(params, opt_state, tokens, targets)
        losses.append(float(loss))
    np.testing.assert_allclose(losses, ref_losses, rtol=2e-4)


@pytest.mark.parametrize("ep,tp", [(2, 1), (4, 1), (2, 2)])
def test_llama_moe_matches_reference(ep, tp):
    """MoE llama with experts sharded over ep (tokens data-split over
    dp×ep, alltoall dispatch) == the unsharded MoE run.  capacity_factor
    = n_experts ⇒ zero drops, so both layouts keep every token.
    aux_weight=0 because the router-balance loss is PER-SHARD by design
    (Switch/GShard semantics: token_frac·prob_frac is nonlinear, so the
    shard mean differs from the global value — a modeling choice, not an
    implementation error); the exact-math contract covers everything
    else."""
    kw = dict(n_experts=4, capacity_factor=4.0, aux_weight=0.0)
    ref_losses, ref_params = _reference_run(batch=16, **kw)
    opt = optax.sgd(0.1)

    cfg = llama.tiny(dtype=jnp.float32, ep_axis="ep", **kw)
    mesh = infer_mesh(8, tp=tp, ep=ep)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    pspecs = llama.param_specs(cfg)
    opt_state = opt.init(params)
    os_specs = spmd.infer_specs_like(opt_state, params, pspecs)
    step = spmd.make_sharded_train_step(
        llama.make_train_step(cfg, opt), mesh, pspecs, os_specs,
        P(("dp", "ep", "pp"), "sp"))   # batch over dp AND ep
    params = spmd.shard_params(params, pspecs, mesh)
    tokens, targets = _data(cfg, batch=16)
    losses = []
    for _ in range(2):
        params, opt_state, loss = step(params, opt_state, tokens, targets)
        losses.append(float(loss))

    np.testing.assert_allclose(losses, ref_losses, rtol=2e-4)
    for (ka, a), (kb, b) in zip(
            jax.tree_util.tree_leaves_with_path(
                jax.tree_util.tree_map(np.asarray, params)),
            jax.tree_util.tree_leaves_with_path(
                jax.tree_util.tree_map(np.asarray, ref_params))):
        np.testing.assert_allclose(a, b, rtol=3e-3, atol=3e-5,
                                   err_msg=str(ka))


def test_llama_moe_pp_composes():
    """MoE + pipeline parallelism: the aux loss rides the pipeline carry
    (per-stage partials, psum'd over pp).  Exact-math check at
    aux_weight=0 vs the unsharded MoE run, plus an aux>0 run proving the
    composition trains (finite loss, params move)."""
    kw = dict(n_experts=4, capacity_factor=4.0, aux_weight=0.0)
    ref_losses, _ = _reference_run(batch=16, **kw)
    opt = optax.sgd(0.1)

    cfg = llama.tiny(dtype=jnp.float32, ep_axis="ep", pp_axis="pp",
                     n_microbatches=2, **kw)
    mesh = infer_mesh(8, pp=2, ep=2)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    pspecs = llama.param_specs(cfg)
    opt_state = opt.init(params)
    os_specs = spmd.infer_specs_like(opt_state, params, pspecs)
    pstep = spmd.make_sharded_train_step(
        llama.make_train_step(cfg, opt), mesh, pspecs, os_specs,
        P(("dp", "ep"), None))
    params = spmd.shard_params(params, pspecs, mesh)
    tokens, targets = _data(cfg, batch=16)
    losses = []
    for _ in range(2):
        params, opt_state, loss = pstep(params, opt_state, tokens, targets)
        losses.append(float(loss))
    np.testing.assert_allclose(losses, ref_losses, rtol=2e-4)

    # aux>0: prove the aux actually rides the pipeline carry into the
    # loss with the right magnitude.  Switch aux ∈ [1, E] per layer (1 at
    # perfect balance, E at collapse), and the pp path averages over
    # microbatches, so (loss_w − loss_0)/w must land in [1, E] — this
    # fails both if the carry plumbing returns 0 and if the per-microbatch
    # sum is not normalized (which would give ≈ n_microbatches × aux).
    w = 0.05
    cfg_a = llama.tiny(ep_axis="ep", pp_axis="pp", n_microbatches=2,
                       dtype=jnp.float32, n_experts=4,
                       capacity_factor=4.0, aux_weight=w)
    params_a = llama.init_params(cfg_a, jax.random.PRNGKey(0))
    opt_state_a = opt.init(params_a)
    specs_a = llama.param_specs(cfg_a)
    os_specs_a = spmd.infer_specs_like(opt_state_a, params_a, specs_a)
    astep = spmd.make_sharded_train_step(
        llama.make_train_step(cfg_a, opt), mesh, specs_a, os_specs_a,
        P(("dp", "ep"), None))
    params_a = spmd.shard_params(params_a, specs_a, mesh)
    _, _, loss_a = astep(params_a, opt_state_a, tokens, targets)
    # at aux_weight 0 the first step above is this step: same weights, same
    # tokens, same layout
    ratio = (float(loss_a) - losses[0]) / w
    assert 1.0 - 1e-3 <= ratio <= 4.0 + 1e-3, ratio


def test_kv_cache_decode_matches_forward():
    """Cached greedy decode == argmax of the full-context forward at every
    generated position (teacher-forced equivalence: the KV cache is exact,
    not an approximation)."""
    cfg = llama.tiny(dtype=jnp.float32, max_seq=64, dp_axis=None,
                     tp_axis=None, sp_axis=None, use_flash=False)
    params = llama.init_params(cfg, jax.random.PRNGKey(5))
    rng = np.random.RandomState(6)
    B, T0, N = 2, 7, 6
    prompt = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, T0)), jnp.int32)

    gen = jax.jit(lambda p, t: llama.generate(p, t, N, cfg))(params, prompt)
    assert gen.shape == (B, N)

    # Reference: recompute the FULL forward over (prompt + generated so
    # far) with no cache; its last-position argmax must reproduce each
    # generated token.
    # a length a program: op by op each length compiles some hundred
    forward = jax.jit(lambda p, s: llama.forward(p, s, cfg))
    seq = prompt
    for i in range(N):
        logits = forward(params, seq)
        nxt = np.asarray(jnp.argmax(logits[:, -1, :], axis=-1))
        np.testing.assert_array_equal(np.asarray(gen[:, i]), nxt,
                                      err_msg=f"token {i}")
        seq = jnp.concatenate(
            [seq, jnp.asarray(nxt, jnp.int32)[:, None]], axis=1)


def test_kv_cache_decode_moe():
    """Decode works through the MoE MLP too (routing per decoded token)."""
    cfg = llama.tiny(dtype=jnp.float32, max_seq=32, dp_axis=None,
                     tp_axis=None, sp_axis=None, use_flash=False,
                     n_experts=4, capacity_factor=4.0)
    params = llama.init_params(cfg, jax.random.PRNGKey(7))
    prompt = jnp.asarray(
        np.random.RandomState(8).randint(0, cfg.vocab_size, (1, 5)),
        jnp.int32)
    gen = jax.jit(lambda p, t: llama.generate(p, t, 4, cfg))(params, prompt)
    assert gen.shape == (1, 4)
    logits = llama.forward(params, prompt, cfg)
    np.testing.assert_array_equal(
        np.asarray(gen[:, 0]),
        np.asarray(jnp.argmax(logits[:, -1, :], axis=-1)))


def test_entry_forward_single_device():
    """Single-chip jittable forward (the __graft_entry__ contract)."""
    cfg = llama.tiny(dtype=jnp.float32, dp_axis=None, tp_axis=None,
                     sp_axis=None)
    params = llama.init_params(cfg, jax.random.PRNGKey(1))
    tokens, _ = _data(cfg, batch=2, seq=8)
    logits = jax.jit(lambda p, t: llama.forward(p, t, cfg))(params, tokens)
    assert logits.shape == (2, 8, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits)).all()


def test_tp_decode_matches_single_device():
    """tp=2 decode (heads split, psum at wo, cache sharded over its head
    axis) must produce the SAME logits as single-device decode at every
    step — prefill included (VERDICT r4 ask #4)."""
    from horovod_tpu.compat import shard_map

    cfg0 = llama.tiny(dtype=jnp.float32, max_seq=32, dp_axis=None,
                      tp_axis=None, sp_axis=None, use_flash=False)
    cfg_tp = llama.tiny(dtype=jnp.float32, max_seq=32, dp_axis=None,
                        tp_axis="tp", sp_axis=None, use_flash=False)
    params = llama.init_params(cfg0, jax.random.PRNGKey(21))
    rng = np.random.RandomState(22)
    B, T0, N = 2, 6, 5
    prompt = jnp.asarray(rng.randint(0, cfg0.vocab_size, (B, T0)),
                         jnp.int32)

    ref = jax.jit(lambda p, t: llama.generate(p, t, N, cfg0))(
        params, prompt)

    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("tp",))
    pspecs = llama.param_specs(cfg_tp)

    def run(p, t):
        return llama.generate(p, t, N, cfg_tp)

    gen = jax.jit(shard_map(
        run, mesh=mesh, in_specs=(pspecs, P(None, None)),
        out_specs=P(None, None), check_vma=False))(params, prompt)
    np.testing.assert_array_equal(np.asarray(gen), np.asarray(ref))

    # decode_step level too: same logits, not just same argmax.
    cache0 = llama.init_cache(cfg0, B, 32)
    l0, _ = jax.jit(lambda p, c, t: llama.prefill(p, c, t, cfg0))(
        params, cache0, prompt)

    def pf(p, t):
        c = llama.init_cache(cfg_tp, B, 32)
        logits, _ = llama.prefill(p, c, t, cfg_tp)
        return logits

    ltp = jax.jit(shard_map(
        pf, mesh=mesh, in_specs=(pspecs, P(None, None)),
        out_specs=P(None, None), check_vma=False))(params, prompt)
    np.testing.assert_allclose(np.asarray(ltp), np.asarray(l0),
                               rtol=1e-5, atol=1e-5)



def test_sampling_modes():
    """temperature/top-k/top-p sampling: greedy default unchanged,
    temperature→0-ish concentrates on the argmax, top_p/top_k masks
    restrict support, rng is required and reproducible."""
    cfg = llama.tiny(dtype=jnp.float32, max_seq=32, dp_axis=None,
                     tp_axis=None, sp_axis=None, use_flash=False)
    params = llama.init_params(cfg, jax.random.PRNGKey(31))
    prompt = jnp.asarray(
        np.random.RandomState(32).randint(0, cfg.vocab_size, (2, 5)),
        jnp.int32)

    def generate(n, **kw):
        """``llama.generate`` as one program; ``rng`` is its argument."""
        rng = kw.pop("rng", None)
        return jax.jit(lambda p, t, rng: llama.generate(
            p, t, n, cfg, rng=rng, **kw))(params, prompt, rng)

    greedy = generate(4)
    # Tiny temperature ≈ greedy (argmax dominates the categorical).
    near_greedy = generate(4, temperature=1e-4, rng=jax.random.PRNGKey(1))
    np.testing.assert_array_equal(np.asarray(greedy),
                                  np.asarray(near_greedy))
    # Same rng → same sample; different rng → (almost surely) different.
    s1 = generate(8, temperature=5.0, rng=jax.random.PRNGKey(2))
    s2 = generate(8, temperature=5.0, rng=jax.random.PRNGKey(2))
    s3 = generate(8, temperature=5.0, rng=jax.random.PRNGKey(3))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    assert not np.array_equal(np.asarray(s1), np.asarray(s3))
    with pytest.raises(ValueError, match="rng"):
        llama.generate(params, prompt, 2, cfg, temperature=1.0)

    # Unit level: top_k=1 ≡ greedy regardless of temperature; top_p→0
    # keeps only the argmax.
    logits = jnp.asarray(np.random.RandomState(33).randn(4, 16),
                         jnp.float32)
    am = np.asarray(jnp.argmax(logits, -1))
    k1 = llama.sample_logits(logits, jax.random.PRNGKey(4),
                             temperature=3.0, top_k=1)
    np.testing.assert_array_equal(np.asarray(k1), am)
    p0 = llama.sample_logits(logits, jax.random.PRNGKey(5),
                             temperature=3.0, top_p=1e-6)
    np.testing.assert_array_equal(np.asarray(p0), am)
    # top_k=3: every draw lands in the 3 largest logits.
    draws = [np.asarray(llama.sample_logits(
        logits, jax.random.PRNGKey(i), temperature=5.0, top_k=3))
        for i in range(20)]
    top3 = np.argsort(-np.asarray(logits), axis=-1)[:, :3]
    for d in draws:
        for b in range(4):
            assert d[b] in top3[b]


def test_decode_chunk_matches_step_loop():
    """decode_chunk over [B, Tq] == Tq sequential decode_steps (same
    logits, same cache) — the verify primitive of speculative decoding."""
    cfg = llama.tiny(dtype=jnp.float32, max_seq=32, dp_axis=None,
                     tp_axis=None, sp_axis=None, use_flash=False)
    params = llama.init_params(cfg, jax.random.PRNGKey(41))
    rng = np.random.RandomState(42)
    B, T0, Tq = 2, 4, 5
    prompt = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, T0)), jnp.int32)
    chunk = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, Tq)), jnp.int32)

    # a program each (the position is traced: the loop's steps share one)
    _, c0 = jax.jit(lambda p, c, t: llama.prefill(p, c, t, cfg))(
        params, llama.init_cache(cfg, B, 32), prompt)
    cl, cc = jax.jit(lambda p, c, t, pos: llama.decode_chunk(
        p, c, t, pos, cfg))(params, c0, chunk, T0)
    step = jax.jit(lambda p, c, t, pos: llama.decode_step(p, c, t, pos, cfg))

    cs = c0
    step_logits = []
    for i in range(Tq):
        li, cs = step(params, cs, chunk[:, i], T0 + i)
        step_logits.append(np.asarray(li))
    np.testing.assert_allclose(np.asarray(cl),
                               np.stack(step_logits, axis=1),
                               rtol=1e-5, atol=1e-5)
    for lc, ls in zip(cc, cs):
        np.testing.assert_allclose(np.asarray(lc["k"]), np.asarray(ls["k"]),
                                   rtol=1e-5, atol=1e-5)


def test_speculative_generate_matches_greedy():
    """Speculative decoding is EXACT greedy decoding: with a different
    (disagreeing) draft model, with self-speculation (full acceptance),
    and at n_draft=1, the output must equal plain generate()."""
    cfg = llama.tiny(dtype=jnp.float32, max_seq=128, dp_axis=None,
                     tp_axis=None, sp_axis=None, use_flash=False)
    params = llama.init_params(cfg, jax.random.PRNGKey(43))
    draft = llama.init_params(cfg, jax.random.PRNGKey(44))
    prompt = jnp.asarray(
        np.random.RandomState(45).randint(0, cfg.vocab_size, (2, 5)),
        jnp.int32)
    N = 10
    ref = np.asarray(jax.jit(
        lambda p, t: llama.generate(p, t, N, cfg))(params, prompt))

    for dp, nd in ((draft, 3), (params, 4), (draft, 1)):
        spec = np.asarray(jax.jit(
            lambda p, d, t: llama.speculative_generate(
                p, d, t, N, cfg, n_draft=nd))(params, dp, prompt))
        np.testing.assert_array_equal(spec, ref, err_msg=f"n_draft={nd}")


def test_sliding_window_train_and_decode(monkeypatch):
    """Mistral-style sliding-window llama: flash path == jnp path for the
    loss, cached decode == full-context forward argmax, and sp rejects
    the window with a clear error."""
    kw = dict(dtype=jnp.float32, max_seq=64, dp_axis=None, tp_axis=None,
              sp_axis=None, sliding_window=6)
    cfg_jnp = llama.tiny(use_flash=False, **kw)
    cfg_flash = llama.tiny(use_flash=True, **kw)
    params = llama.init_params(cfg_jnp, jax.random.PRNGKey(51))
    tokens, targets = _data(cfg_jnp, batch=2, seq=24)

    loss = lambda cfg: float(jax.jit(
        lambda p: llama.loss_fn(p, tokens, targets, cfg))(params))
    l_jnp, l_flash = loss(cfg_jnp), loss(cfg_flash)
    np.testing.assert_allclose(l_flash, l_jnp, rtol=2e-5)
    # The window changes the math (vs full causal attention).
    cfg_full = llama.tiny(use_flash=False, dtype=jnp.float32, max_seq=64,
                          dp_axis=None, tp_axis=None, sp_axis=None)
    assert abs(loss(cfg_full) - l_jnp) > 1e-6

    # Cached decode under the window == windowed full-context forward.
    prompt = tokens[:, :7]
    gen = jax.jit(lambda p, t: llama.generate(p, t, 5, cfg_jnp))(
        params, prompt)
    forward = jax.jit(lambda p, s: llama.forward(p, s, cfg_jnp))
    seq = prompt
    for i in range(5):
        logits = forward(params, seq)
        nxt = np.asarray(jnp.argmax(logits[:, -1, :], axis=-1))
        np.testing.assert_array_equal(np.asarray(gen[:, i]), nxt,
                                      err_msg=f"token {i}")
        seq = jnp.concatenate(
            [seq, jnp.asarray(nxt, jnp.int32)[:, None]], axis=1)

    # sp × window is rejected at trace time.
    cfg_sp = llama.tiny(dtype=jnp.float32, sliding_window=6)
    mesh = infer_mesh(8, sp=2)
    pspecs = llama.param_specs(cfg_sp)
    sp_params = llama.init_params(cfg_sp, jax.random.PRNGKey(52))
    from horovod_tpu.compat import shard_map
    sp_tokens, _ = _data(cfg_sp, batch=8, seq=16, seed=53)
    with pytest.raises(ValueError, match="sliding_window"):
        jax.jit(shard_map(
            lambda p, t: llama.forward(p, t, cfg_sp), mesh=mesh,
            in_specs=(pspecs, P(("dp", "ep", "pp"), "sp")),
            out_specs=P(("dp", "ep", "pp"), "sp"), check_vma=False))(
            sp_params, sp_tokens).block_until_ready()


def test_rolling_cache_matches_full_cache():
    """Rolling (ring-buffer) KV cache for windowed decode: O(W+slack)
    memory, positions wrap — must generate EXACTLY what the full-length
    masked cache generates, across multiple ring wraps, with prompts
    longer than the ring, through speculative decoding, and BEYOND
    max_seq (the unbounded-generation property)."""
    W, slack = 8, 4
    base = dict(dtype=jnp.float32, dp_axis=None, tp_axis=None,
                sp_axis=None, sliding_window=W, use_flash=False)
    cfg_full = llama.tiny(max_seq=64, **base)
    cfg_roll = llama.tiny(max_seq=64, rolling_cache=True,
                          rolling_slack=slack, **base)
    params = llama.init_params(cfg_full, jax.random.PRNGKey(61))
    rng = np.random.RandomState(62)
    prompt = jnp.asarray(rng.randint(0, cfg_full.vocab_size, (2, 10)),
                         jnp.int32)
    N = 20                                   # ring R=12 wraps twice

    def generate(prompt, n, cfg):
        return np.asarray(jax.jit(
            lambda p, t: llama.generate(p, t, n, cfg))(params, prompt))

    ref, roll = generate(prompt, N, cfg_full), generate(prompt, N, cfg_roll)
    np.testing.assert_array_equal(roll, ref)
    # Ring memory really is O(W + slack).
    c = llama.init_cache(cfg_roll, 2)
    assert c[0]["k"].shape[1] == W + slack

    # Prompt longer than the ring.
    prompt2 = jnp.asarray(rng.randint(0, cfg_full.vocab_size, (1, 20)),
                          jnp.int32)
    np.testing.assert_array_equal(generate(prompt2, 6, cfg_roll),
                                  generate(prompt2, 6, cfg_full))

    # Prompt SHORTER than the window: never-written ring slots derive
    # negative positions and must be masked — qpos-W is negative too in
    # this regime, so the p_j >= 0 term is what excludes them (the
    # review-caught dilution bug).
    prompt3 = jnp.asarray(rng.randint(0, cfg_full.vocab_size, (2, 3)),
                          jnp.int32)
    np.testing.assert_array_equal(generate(prompt3, 8, cfg_roll),
                                  generate(prompt3, 8, cfg_full))

    # Speculative decoding on the rolling cache (chunk 3 <= slack).
    draft = llama.init_params(cfg_full, jax.random.PRNGKey(63))
    spec = np.asarray(jax.jit(lambda p, d, t: llama.speculative_generate(
        p, d, t, N, cfg_roll, n_draft=2))(params, draft, prompt))
    np.testing.assert_array_equal(spec, ref)

    # Chunks beyond the slack are rejected (their earlier rows would
    # attend freshly-overwritten slots).
    cache = llama.init_cache(cfg_roll, 1)
    big = jnp.zeros((1, slack + 1), jnp.int32)
    with pytest.raises(ValueError, match="rolling_slack"):
        llama.decode_chunk(params, cache, big, 0, cfg_roll)

    # Unbounded generation: past max_seq, where the full cache refuses.
    cfg_small = llama.tiny(max_seq=16, **base)
    cfg_small_roll = llama.tiny(max_seq=16, rolling_cache=True,
                                rolling_slack=slack, **base)
    with pytest.raises(ValueError, match="slots"):
        llama.generate(params, prompt, 30, cfg_small)
    long_out = generate(prompt, 30, cfg_small_roll)
    assert long_out.shape == (2, 30)
    np.testing.assert_array_equal(long_out[:, :N], ref)


def test_kv_cache_budget_enforced():
    """Decoding past the cache raises instead of silently clamping writes
    onto the last slot; n_tokens=0 returns an empty [B, 0]."""
    cfg = llama.tiny(dtype=jnp.float32, max_seq=8, dp_axis=None,
                     tp_axis=None, sp_axis=None, use_flash=False)
    params = llama.init_params(cfg, jax.random.PRNGKey(9))
    prompt = jnp.asarray(
        np.random.RandomState(10).randint(0, cfg.vocab_size, (1, 6)),
        jnp.int32)
    with pytest.raises(ValueError, match="slots"):
        llama.generate(params, prompt, 6, cfg)      # positions 6..11 > 8
    assert llama.generate(params, prompt, 3, cfg).shape == (1, 3)
    assert llama.generate(params, prompt, 0, cfg).shape == (1, 0)
