"""``jax.named_scope`` around the four parts of the step programs
(``models/resnet.py``, ``models/llama.py``, ``models/ouro.py``, whose
looped step names its layers' parts and its passes' ends too): names for a
device trace, and nothing else.  Each step is lowered at test size with the
scopes and with ``jax.named_scope`` turned into a no-op: the two StableHLO
texts, which carry no name (a name is a location, printed only on request),
must be equal, so the compiler is given the same program; and the scoped
one, compiled, must name every part in ``op_name``."""

import contextlib
import re

import jax
import jax.numpy as jnp
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu.parallel import make_mesh, spmd
from horovod_tpu.parallel.mesh import infer_mesh

SCOPES = ("forward", "backward", "gradient_exchange", "optimizer")


# Each ``*_step()`` makes its arguments once (their shapes, where the step
# does not place them: a text is all that is read) and returns ``lower()``,
# which builds the step anew (new function objects: nothing of a trace with
# other scopes is reused) and lowers it.
def resnet_step():
    from horovod_tpu.models import resnet
    cfg = resnet.ResNetConfig(depth=18, num_classes=10, width=8,
                              compute_dtype=jnp.float32)
    mesh = make_mesh({"hvd": 8})
    params, stats = jax.eval_shape(lambda k: resnet.init_params(cfg, k),
                                   jax.random.PRNGKey(0))
    opt = optax.sgd(0.05, momentum=0.9)
    x, y = resnet.synthetic_batch(16, image_size=32, num_classes=10)
    args = (params, stats, jax.eval_shape(opt.init, params), jnp.asarray(x),
            jnp.asarray(y))
    return lambda: resnet.make_sharded_train_step(cfg, opt, mesh).lower(*args)


def llama_step():
    from horovod_tpu.models import llama
    cfg = llama.tiny(dtype=jnp.float32)
    mesh = infer_mesh(8, tp=2, sp=1)
    params = jax.eval_shape(lambda k: llama.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    pspecs = llama.param_specs(cfg)
    opt = optax.adam(1e-3)
    opt_state = jax.eval_shape(opt.init, params)
    tokens = jnp.zeros((8, 16), jnp.int32)
    placed = jax.tree_util.tree_map(
        lambda x, spec: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(mesh, spec)),
        params, pspecs)
    args = (placed, opt_state, tokens, tokens)
    return lambda: spmd.make_sharded_train_step(
        llama.make_train_step(cfg, opt), mesh, pspecs,
        spmd.infer_specs_like(opt_state, params, pspecs),
        P(("dp", "ep", "pp"), "sp")).lower(*args)


def family_step(name):
    """A decoder family's ``tiny()`` train step under ``shard_map`` over 8
    ranks, lowered from shapes."""
    def step():
        import importlib

        from horovod_tpu.compat import shard_map
        family = importlib.import_module(f"horovod_tpu.models.{name}")
        cfg = family.tiny()
        mesh = make_mesh({"hvd": 8})
        params = jax.eval_shape(lambda k: family.init_params(cfg, k),
                                jax.random.PRNGKey(0))
        opt = optax.adam(1e-3)
        tokens = jnp.zeros((8, 16), jnp.int32)
        args = (params, jax.eval_shape(opt.init, params), tokens, tokens)
        return lambda: jax.jit(shard_map(
            family.make_train_step(cfg, opt), mesh=mesh,
            in_specs=(P(), P(), P("hvd"), P("hvd")),
            out_specs=(P(), P(), P()), check_vma=False)).lower(*args)
    return step


# the Mamba-1 hybrid's own: the mixer's four parts, the attention layer, a
# layer's MLP and the tied head (``benchmark/families/jamba.py`` ``SCOPES``)
JAMBA_SCOPES = ("ssm/proj", "ssm/conv", "ssm/scan", "ssm/out", "attn/full",
                "mlp", "head")

# the window/full expert decoder's own: a layer kind a scope, layer 0's
# SwiGLU, the expert layer's parts and the head
# (``benchmark/families/laguna.py`` ``SCOPES``)
LAGUNA_SCOPES = ("attn/full", "attn/window", "mlp", "moe/route",
                 "moe/dispatch", "moe/experts", "moe/shared", "moe/combine",
                 "head")

# the latent-attention expert decoder's own: the prediction module first
# (an instruction goes to the first scope its op_name holds), the low-rank
# paths beneath the attention's scope (``benchmark/families/joyai.py``
# ``SCOPES``)
JOYAI_SCOPES = ("mtp", "attn/latent/proj", "attn/latent", "mlp", "moe/route",
                "moe/dispatch", "moe/experts", "moe/shared", "moe/combine",
                "head")

# the looped step's own: a layer's two halves, and what ends a pass
# (``benchmark/families/ouro.py`` ``SCOPES``)
OURO_SCOPES = ("attn/full", "mlp", "head", "loop/exit", "loop/carry")


@pytest.mark.parametrize("step, scopes", [
    (resnet_step, SCOPES), (llama_step, SCOPES),
    (family_step("ouro"), ("forward", "backward", "optimizer") + OURO_SCOPES),
    (family_step("jamba"),
     ("forward", "backward", "optimizer") + JAMBA_SCOPES),
    (family_step("laguna"),
     ("forward", "backward", "optimizer") + LAGUNA_SCOPES),
    (family_step("joyai"),
     ("forward", "backward", "optimizer") + JOYAI_SCOPES)],
    ids=["resnet", "llama", "ouro", "jamba", "laguna", "joyai"])
def test_named_scopes_change_metadata_only(step, scopes, monkeypatch):
    lower = step()
    jax.clear_caches()      # a checkpointed region traced before is kept
    scoped = lower()
    compiled = scoped.compile().as_text()
    for scope in scopes:
        assert re.search(r'op_name="[^"]*\b%s\b' % scope, compiled), scope
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    jax.clear_caches()
    bare = lower()
    assert not re.search(r'loc\("[^"]*\b(%s)/' % "|".join(scopes),
                         bare.as_text(debug_info=True))
    assert scoped.as_text() == bare.as_text()


@pytest.mark.parametrize("name, scopes", [
    ("ouro", OURO_SCOPES), ("jamba", JAMBA_SCOPES),
    ("laguna", LAGUNA_SCOPES), ("joyai", JOYAI_SCOPES)])
def test_a_familys_scopes_are_the_ones_the_benchmark_reads(name, scopes):
    import importlib
    import os
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    family = importlib.import_module(f"benchmark.families.{name}")
    assert family.SCOPES == scopes


def test_the_prediction_modules_parts_are_mtps_in_the_scopes_table():
    """``benchmark/trace_scopes.within`` on the ``joyai`` step's compiled
    text: every scope of the family's table is some instruction's, and an
    instruction of the module's attention, expert layer or head pass
    (``mtp/.../attn/latent``, ``mtp/.../moe/experts``, ``mtp/.../head``)
    goes to ``mtp``, the low-rank paths of a main layer to
    ``attn/latent/proj`` and not to ``attn/latent``."""
    import os
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark import trace_scopes
    jax.clear_caches()
    text = family_step("joyai")()().compile().as_text()
    table = trace_scopes.within(JOYAI_SCOPES, text)
    assert set(table.values()) == set(JOYAI_SCOPES)
    names = dict(re.findall(r'%([\w.\-]+) = [^\n]*?op_name="([^"]*)"', text))
    for inner in ("attn/latent", "moe/experts", "head"):
        both = [n for n, op in names.items()
                if re.search(r"\bmtp\b", op) and inner in op]
        assert both and all(table[n] == "mtp" for n in both), inner
    proj = [n for n, op in names.items() if "attn/latent/proj" in op
            and not re.search(r"\bmtp\b", op)]
    assert proj and all(table[n] == "attn/latent/proj" for n in proj)
