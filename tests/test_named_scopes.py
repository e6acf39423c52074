"""``jax.named_scope`` around the four parts of the step programs
(``models/resnet.py``, ``models/llama.py``, ``models/ouro.py``, whose
looped step names its layers' parts and its passes' ends too): names for a
device trace, and nothing else.  Each step is lowered and compiled at test size with the
scopes and with ``jax.named_scope`` turned into a no-op; the two HLO texts
must be equal once the metadata is taken out, and the scoped one must name
every part."""

import contextlib
import re

import jax
import jax.numpy as jnp
import optax
import pytest
from jax.sharding import PartitionSpec as P

from horovod_tpu.parallel import make_mesh, spmd
from horovod_tpu.parallel.mesh import infer_mesh

SCOPES = ("forward", "backward", "gradient_exchange", "optimizer")
METADATA = re.compile(r",? ?metadata=\{[^}]*\}")
# the module's tables of source files, functions and stack frames, which
# the instructions' metadata points into
TABLES = re.compile(r"^(FileNames|FunctionNames|FileLocations|StackFrames)\n"
                    r"(\d+ .*\n)*", re.M)


def stripped(hlo_text):
    return METADATA.sub("", TABLES.sub("", hlo_text))


def renumbered(hlo_text):
    """Every instruction named by the order of its first appearance.  For
    the looped step alone: the numbers XLA hands out count the lowerings of
    cached inner functions (``jit_silu_.23`` against ``jit_silu_.5``), and
    its second lowering in one process shifts them."""
    names = {}
    return re.sub(r"%[\w.\-]+",
                  lambda m: names.setdefault(m.group(0), f"%{len(names)}"),
                  hlo_text)


def resnet_step():
    from horovod_tpu.models import resnet
    cfg = resnet.ResNetConfig(depth=18, num_classes=10, width=8,
                              compute_dtype=jnp.float32)
    mesh = make_mesh({"hvd": 8})
    params, stats = resnet.init_params(cfg, jax.random.PRNGKey(0))
    opt = optax.sgd(0.05, momentum=0.9)
    x, y = resnet.synthetic_batch(16, image_size=32, num_classes=10)
    step = resnet.make_sharded_train_step(cfg, opt, mesh)
    return step.lower(params, stats, opt.init(params), jnp.asarray(x),
                      jnp.asarray(y))


def llama_step():
    from horovod_tpu.models import llama
    cfg = llama.tiny(dtype=jnp.float32)
    mesh = infer_mesh(8, tp=2, sp=1)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    pspecs = llama.param_specs(cfg)
    opt = optax.adam(1e-3)
    opt_state = opt.init(params)
    step = spmd.make_sharded_train_step(
        llama.make_train_step(cfg, opt), mesh, pspecs,
        spmd.infer_specs_like(opt_state, params, pspecs),
        P(("dp", "ep", "pp"), "sp"))
    tokens = jnp.zeros((8, 16), jnp.int32)
    return step.lower(spmd.shard_params(params, pspecs, mesh), opt_state,
                      tokens, tokens)


def ouro_step():
    from horovod_tpu.compat import shard_map
    from horovod_tpu.models import ouro
    cfg = ouro.tiny()
    mesh = make_mesh({"hvd": 8})
    params = ouro.init_params(cfg, jax.random.PRNGKey(0))
    opt = optax.adam(1e-3)
    tokens = jnp.zeros((8, 16), jnp.int32)
    return jax.jit(shard_map(
        ouro.make_train_step(cfg, opt), mesh=mesh,
        in_specs=(P(), P(), P("hvd"), P("hvd")), out_specs=(P(), P(), P()),
        check_vma=False)).lower(params, opt.init(params), tokens, tokens)


def jamba_step():
    from horovod_tpu.compat import shard_map
    from horovod_tpu.models import jamba
    jax.clear_caches()      # a checkpointed region traced before is kept
    cfg = jamba.tiny()
    mesh = make_mesh({"hvd": 8})
    params = jamba.init_params(cfg, jax.random.PRNGKey(0))
    opt = optax.adam(1e-3)
    tokens = jnp.zeros((8, 16), jnp.int32)
    return jax.jit(shard_map(
        jamba.make_train_step(cfg, opt), mesh=mesh,
        in_specs=(P(), P(), P("hvd"), P("hvd")), out_specs=(P(), P(), P()),
        check_vma=False)).lower(params, opt.init(params), tokens, tokens)


# the Mamba-1 hybrid's own: the mixer's four parts, the attention layer, a
# layer's MLP and the tied head (``benchmark/families/jamba.py`` ``SCOPES``)
JAMBA_SCOPES = ("ssm/proj", "ssm/conv", "ssm/scan", "ssm/out", "attn/full",
                "mlp", "head")

# the looped step's own: a layer's two halves, and what ends a pass
# (``benchmark/families/ouro.py`` ``SCOPES``)
OURO_SCOPES = ("attn/full", "mlp", "head", "loop/exit", "loop/carry")


same = lambda text: text


@pytest.mark.parametrize("lower, scopes, names", [
    (resnet_step, SCOPES, same), (llama_step, SCOPES, same),
    (ouro_step, ("forward", "backward", "optimizer") + OURO_SCOPES,
     renumbered),
    (jamba_step, ("forward", "backward", "optimizer") + JAMBA_SCOPES,
     renumbered)],
    ids=["resnet", "llama", "ouro", "jamba"])
def test_named_scopes_change_metadata_only(lower, scopes, names, monkeypatch):
    scoped = lower().compile().as_text()
    for scope in scopes:
        assert re.search(r'op_name="[^"]*\b%s\b' % scope, scoped), scope
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = lower().compile().as_text()
    assert not re.search(r'op_name="[^"]*\b(%s)/' % "|".join(scopes), bare)
    assert names(stripped(scoped)) == names(stripped(bare))


def test_the_looped_steps_scopes_are_the_ones_the_benchmark_reads():
    import os
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark.families import ouro as family
    assert family.SCOPES == OURO_SCOPES


def test_the_jamba_steps_scopes_are_the_ones_the_benchmark_reads():
    import os
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark.families import jamba as family
    assert family.SCOPES == JAMBA_SCOPES
