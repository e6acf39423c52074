"""Model zoo tests: each model trains data-parallel on the virtual mesh and
the sharded run matches a single-device reference where applicable.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from horovod_tpu.compat import shard_map
from jax.sharding import PartitionSpec as P

from horovod_tpu.parallel.mesh import infer_mesh, make_mesh
from horovod_tpu.parallel import spmd


# ----------------------------------------------------------------- MNIST CNN
def test_mnist_trains():
    from horovod_tpu.models import mnist
    mesh = make_mesh({"hvd": 8})
    params = mnist.init_params(jax.random.PRNGKey(0))
    opt = optax.adam(1e-3)
    opt_state = opt.init(params)
    step = mnist.make_sharded_train_step(opt, mesh)
    x, y = mnist.synthetic_batch(64)
    losses = []
    for i in range(6):
        params, opt_state, loss = step(params, opt_state, jnp.asarray(x),
                                       jnp.asarray(y))
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.7, losses


def test_mnist_dp_matches_single_device():
    from horovod_tpu.models import mnist
    x, y = mnist.synthetic_batch(32, seed=1)

    params0 = mnist.init_params(jax.random.PRNGKey(1))
    opt = optax.sgd(0.05)

    # single device
    step1 = jax.jit(mnist.make_train_step(opt, axis_name=None))
    p_ref, s_ref = params0, opt.init(params0)
    for _ in range(2):
        p_ref, s_ref, l_ref = step1(p_ref, s_ref, jnp.asarray(x),
                                    jnp.asarray(y))

    # 8-way dp
    mesh = make_mesh({"hvd": 8})
    stepN = mnist.make_sharded_train_step(opt, mesh)
    p, s = params0, opt.init(params0)
    for _ in range(2):
        p, s, l = stepN(p, s, jnp.asarray(x), jnp.asarray(y))

    np.testing.assert_allclose(float(l), float(l_ref), rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(p),
                    jax.tree_util.tree_leaves(p_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-6)


# ----------------------------------------------------------------- ResNet
def test_resnet18_trains_with_syncbn():
    from horovod_tpu.models import resnet
    cfg = resnet.ResNetConfig(depth=18, num_classes=10, width=16,
                              compute_dtype=jnp.float32)
    mesh = make_mesh({"hvd": 8})
    params, stats = jax.jit(lambda k: resnet.init_params(cfg, k))(
        jax.random.PRNGKey(0))
    opt = optax.sgd(0.05, momentum=0.9)
    opt_state = opt.init(params)
    step = resnet.make_sharded_train_step(cfg, opt, mesh)
    x, y = resnet.synthetic_batch(16, image_size=32, num_classes=10)
    losses = []
    for _ in range(4):
        params, stats, opt_state, loss = step(params, stats, opt_state,
                                              jnp.asarray(x), jnp.asarray(y))
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses
    # running BN stats actually moved
    assert not np.allclose(np.asarray(stats["stem"]["mean"]), 0.0)


def test_resnet50_forward_shape():
    from horovod_tpu.models import resnet
    cfg = resnet.ResNetConfig(depth=50, num_classes=1000, width=8,
                              compute_dtype=jnp.float32, sync_bn_axis=None)
    params, stats = jax.jit(lambda k: resnet.init_params(cfg, k))(
        jax.random.PRNGKey(0))
    x, _ = resnet.synthetic_batch(2, image_size=64)
    logits, new_stats = jax.jit(
        lambda p, s, x: resnet.forward(p, s, x, cfg, train=False))(
        params, stats, jnp.asarray(x))
    assert logits.shape == (2, 1000)
    assert np.isfinite(np.asarray(logits)).all()


# ----------------------------------------------------------------- BERT
def test_bert_sharded_matches_reference():
    from horovod_tpu.models import bert

    tokens = np.random.RandomState(0).randint(0, 256, (8, 32)).astype(np.int32)
    targets = np.random.RandomState(1).randint(0, 256, (8, 32)).astype(np.int32)
    mask = (np.random.RandomState(2).rand(8, 32) < 0.25).astype(np.float32)

    cfg_ref = bert.tiny(dtype=jnp.float32, dp_axis=None, tp_axis=None,
                        sp_axis=None)
    params = bert.init_params(cfg_ref, jax.random.PRNGKey(0))
    opt = optax.sgd(0.1)
    step_ref = jax.jit(bert.make_train_step(cfg_ref, opt))
    p_ref, s_ref = params, opt.init(params)
    ref_losses = []
    for _ in range(2):
        p_ref, s_ref, l = step_ref(p_ref, s_ref, jnp.asarray(tokens),
                                   jnp.asarray(targets), jnp.asarray(mask))
        ref_losses.append(float(l))

    cfg = bert.tiny(dtype=jnp.float32)
    mesh = infer_mesh(8, tp=2, sp=2)
    pspecs = bert.param_specs(cfg)
    p, s = params, opt.init(params)
    os_specs = spmd.infer_specs_like(s, params, pspecs)
    data_spec = P(("dp", "ep", "pp"), "sp")
    step = jax.jit(shard_map(
        bert.make_train_step(cfg, opt), mesh=mesh,
        in_specs=(pspecs, os_specs, data_spec, data_spec, data_spec),
        out_specs=(pspecs, os_specs, P()), check_vma=False))
    losses = []
    for _ in range(2):
        p, s, l = step(p, s, jnp.asarray(tokens), jnp.asarray(targets),
                       jnp.asarray(mask))
        losses.append(float(l))
    np.testing.assert_allclose(losses, ref_losses, rtol=2e-4)


# ----------------------------------------------------------------- DLRM
def test_dlrm_sharded_matches_reference():
    from horovod_tpu.models import dlrm

    cfg_ref = dlrm.tiny(dp_axis=None, ep_axis=None)
    dense, sparse, labels = dlrm.synthetic_batch(cfg_ref, 16)
    params = dlrm.init_params(cfg_ref, jax.random.PRNGKey(0))
    opt = optax.sgd(0.1)
    step_ref = jax.jit(dlrm.make_train_step(cfg_ref, opt))
    p_ref, s_ref = params, opt.init(params)
    ref_losses = []
    for _ in range(2):
        p_ref, s_ref, l = step_ref(p_ref, s_ref, jnp.asarray(dense),
                                   jnp.asarray(sparse), jnp.asarray(labels))
        ref_losses.append(float(l))

    cfg = dlrm.tiny()
    mesh = infer_mesh(8, ep=4)   # dp=2 x ep=4
    pspecs = dlrm.param_specs(cfg)
    p, s = params, opt.init(params)
    os_specs = spmd.infer_specs_like(s, params, pspecs)
    data_spec = P(("dp", "pp", "ep", "sp", "tp"))   # batch over dp AND ep
    step = jax.jit(shard_map(
        dlrm.make_train_step(cfg, opt), mesh=mesh,
        in_specs=(pspecs, os_specs, data_spec, data_spec, data_spec),
        out_specs=(pspecs, os_specs, P()), check_vma=False))
    losses = []
    for _ in range(2):
        p, s, l = step(p, s, jnp.asarray(dense), jnp.asarray(sparse),
                       jnp.asarray(labels))
        losses.append(float(l))
    np.testing.assert_allclose(losses, ref_losses, rtol=2e-4)
    # table shards, recombined, match the reference tables
    tables = np.asarray(jax.device_get(p["tables"]))
    np.testing.assert_allclose(tables, np.asarray(p_ref["tables"]),
                               rtol=2e-3, atol=1e-6)


def test_llama_remat_layers_matches():
    """remat_layers=True recomputes the forward in backward (memory
    lever for models that do not otherwise fit) and must be numerically
    invisible: same logits, same grads."""
    import jax
    import jax.numpy as jnp
    from horovod_tpu.models import llama

    base = dict(n_heads=4, n_kv_heads=2, d_model=64, d_ff=128,
                vocab_size=128, dtype=jnp.float32,
                dp_axis=None, tp_axis=None, sp_axis=None)
    cfg = llama.tiny(**base)
    cfg_r = llama.tiny(**base, remat_layers=True)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    toks = jnp.asarray(np.random.RandomState(0).randint(0, 128, (2, 33)),
                       jnp.int32)

    forward = lambda c: jax.jit(lambda p: llama.forward(p, toks, c))(params)
    np.testing.assert_array_equal(np.asarray(forward(cfg)),
                                  np.asarray(forward(cfg_r)))

    def loss(p, c):
        lg = llama.forward(p, toks, c)
        return jnp.mean((lg - 1.0) ** 2)

    g = jax.jit(jax.grad(lambda p: loss(p, cfg)))(params)
    g_r = jax.jit(jax.grad(lambda p: loss(p, cfg_r)))(params)
    for a, b in zip(jax.tree_util.tree_leaves(g),
                    jax.tree_util.tree_leaves(g_r)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)


# ----------------------------------------------------------------- ViT
def test_vit_sharded_matches_reference():
    """dp x tp ViT training == the unsharded single-device run, exactly
    the bert contract (the encoder blocks ARE bert's)."""
    from horovod_tpu.models import vit

    rng = np.random.RandomState(0)
    images = rng.randn(8, 32, 32, 3).astype(np.float32)
    labels = rng.randint(0, 10, 8).astype(np.int32)

    cfg_ref = vit.tiny(dtype=jnp.float32, dp_axis=None, tp_axis=None)
    params = vit.init_params(cfg_ref, jax.random.PRNGKey(0))
    opt = optax.sgd(0.1)
    step_ref = jax.jit(vit.make_train_step(cfg_ref, opt))
    p_ref, s_ref = params, opt.init(params)
    ref_losses = []
    for _ in range(3):
        p_ref, s_ref, l = step_ref(p_ref, s_ref, jnp.asarray(images),
                                   jnp.asarray(labels))
        ref_losses.append(float(l))
    assert ref_losses[-1] < ref_losses[0]   # it actually trains

    cfg = vit.tiny(dtype=jnp.float32)
    mesh = make_mesh({"dp": 4, "tp": 2})
    pspecs = vit.param_specs(cfg)
    p, s = params, opt.init(params)
    os_specs = spmd.infer_specs_like(s, params, pspecs)
    step = jax.jit(shard_map(
        vit.make_train_step(cfg, opt), mesh=mesh,
        in_specs=(pspecs, os_specs, P("dp"), P("dp")),
        out_specs=(pspecs, os_specs, P()), check_vma=False))
    losses = []
    for _ in range(3):
        p, s, l = step(p, s, jnp.asarray(images), jnp.asarray(labels))
        losses.append(float(l))
    np.testing.assert_allclose(losses, ref_losses, rtol=2e-4)


def test_vit_config_validation():
    from horovod_tpu.models import vit

    with pytest.raises(ValueError, match="divisible"):
        vit.ViTConfig(image_size=30, patch_size=16)
    with pytest.raises(ValueError, match="sequence parallelism"):
        vit.tiny(sp_axis="sp")
    cfg = vit.tiny()
    assert cfg.n_patches == 16


# ----------------------------------------------------------------- GPT-2
def test_gpt2_sharded_matches_reference():
    """dp x tp GPT-2 training == the unsharded single-device run (the
    llama/bert/vit contract, third decoder architecture)."""
    from horovod_tpu.models import gpt2

    rng = np.random.RandomState(0)
    tokens = rng.randint(0, 256, (8, 32)).astype(np.int32)
    targets = rng.randint(0, 256, (8, 32)).astype(np.int32)

    cfg_ref = gpt2.tiny(dtype=jnp.float32, dp_axis=None, tp_axis=None)
    params = gpt2.init_params(cfg_ref, jax.random.PRNGKey(0))
    opt = optax.sgd(0.1)
    step_ref = jax.jit(gpt2.make_train_step(cfg_ref, opt))
    p_ref, s_ref = params, opt.init(params)
    ref_losses = []
    for _ in range(3):
        p_ref, s_ref, l = step_ref(p_ref, s_ref, jnp.asarray(tokens),
                                   jnp.asarray(targets))
        ref_losses.append(float(l))
    assert ref_losses[-1] < ref_losses[0]

    cfg = gpt2.tiny(dtype=jnp.float32)
    mesh = make_mesh({"dp": 4, "tp": 2})
    pspecs = gpt2.param_specs(cfg)
    p, s = params, opt.init(params)
    os_specs = spmd.infer_specs_like(s, params, pspecs)
    step = jax.jit(shard_map(
        gpt2.make_train_step(cfg, opt), mesh=mesh,
        in_specs=(pspecs, os_specs, P("dp"), P("dp")),
        out_specs=(pspecs, os_specs, P()), check_vma=False))
    losses = []
    for _ in range(3):
        p, s, l = step(p, s, jnp.asarray(tokens), jnp.asarray(targets))
        losses.append(float(l))
    np.testing.assert_allclose(losses, ref_losses, rtol=2e-4)


def test_gpt2_generate_matches_full_forward():
    """Greedy KV-cache generation == argmax over full re-forward, token
    for token (the decode-path exactness contract, GPT-2 edition)."""
    from horovod_tpu.models import gpt2

    cfg = gpt2.tiny(dtype=jnp.float32, dp_axis=None, tp_axis=None)
    params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
    prompt = jnp.asarray(np.random.RandomState(0).randint(0, 256, (2, 8)),
                         jnp.int32)
    out = gpt2.generate(params, prompt, 6, cfg)
    # a length a program: op by op each length compiles some hundred
    forward = jax.jit(lambda p, s: gpt2.forward(p, s, cfg))
    seq = prompt
    for _ in range(6):
        lg = forward(params, seq)
        nxt = jnp.argmax(lg[:, -1], axis=-1).astype(jnp.int32)
        seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(seq[:, 8:]))

    with pytest.raises(ValueError, match="single-device"):
        gpt2.decode_step(params, gpt2.init_cache(gpt2.tiny(), 2),
                         prompt[:, 0], 0, gpt2.tiny())
