"""The plain float32 ResNet reference against ``horovod_tpu/models`` at tiny
sizes on the CPU, and the control: a run below the configuration's
precision has to fail the comparison that a sound run passes."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import compare                           # noqa: E402
from benchmark.families import resnet as resnet_family  # noqa: E402
from benchmark.reference import resnet as ref_resnet    # noqa: E402
from benchmark.reference.common import quantizer   # noqa: E402
from horovod_tpu.models import resnet                   # noqa: E402

RESNET = dict(depth=18, width=8, image_size=32, num_classes=10,
              batch_per_chip=4, dtype="float32")
# float32 against float32 at these sizes differs by reassociation only.
SOUND = {"loss_rel": 1e-5, "grad_norm_gap": 1e-4, "delta_norm_gap": 2e-3}
KEY = jax.random.PRNGKey(5)


def same_layout(a, b):
    assert jax.tree_util.tree_structure(a) == jax.tree_util.tree_structure(b)
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        assert x.shape == y.shape and x.dtype == y.dtype


def worst_rel(a, b):
    return max(float(jnp.max(jnp.abs(x - y)) / (jnp.max(jnp.abs(y)) + 1e-12))
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


def as_record(followed, rank=0):
    return {"rank": rank, "first_losses": followed["losses"][rank],
            "grad_norms": followed["grad_norms"],
            "delta_norms": followed["delta_norms"], "digest": "",
            "last_loss": 1.0, "params_changed": True}


# ------------------------------------------------------------------- resnet
@pytest.mark.parametrize("depth", [18, 50])
def test_resnet_weights_have_the_programs_layout(depth):
    sizes = dict(RESNET, depth=depth)
    cfg = resnet.ResNetConfig(depth=depth, num_classes=10, width=8)
    mine = jax.eval_shape(lambda k: ref_resnet.init_weights(k, sizes), KEY)
    theirs = jax.eval_shape(lambda k: resnet.init_params(cfg, k), KEY)
    same_layout(mine[0], theirs[0])
    same_layout(mine[1], theirs[1])


def test_resnet_reference_agrees_with_the_model_in_float32():
    params, stats = ref_resnet.init_weights(KEY, RESNET)
    cfg = resnet.ResNetConfig(depth=18, num_classes=10, width=8,
                              compute_dtype=jnp.float32, sync_bn_axis=None)
    x, y = ref_resnet.make_batch(KEY, RESNET, 0)
    with jax.default_matmul_precision("highest"):
        l1, g1 = jax.jit(jax.value_and_grad(
            lambda p: ref_resnet.loss_fn(p, x, y, RESNET)))(params)
        l2, g2 = jax.jit(jax.value_and_grad(lambda p: resnet.loss_fn(
            p, stats, x, y, cfg, axis_name=None)[0]))(params)
    assert abs(float(l1) - float(l2)) <= 1e-5 * abs(float(l2))
    assert worst_rel(g1, g2) <= 2e-3


@pytest.mark.parametrize("kernel, stride, size", [
    (7, 2, 32), (3, 1, 9), (3, 2, 9), (3, 2, 8), (1, 2, 7), (1, 1, 5)])
def test_the_convolution_equals_the_sum_of_shifted_products(kernel, stride,
                                                            size):
    k1, k2 = jax.random.split(KEY)
    x = jax.random.normal(k1, (2, size, size, 3))
    w = jax.random.normal(k2, (kernel, kernel, 3, 5))
    q = quantizer("float32")
    with jax.default_matmul_precision("highest"):
        a = ref_resnet.conv2d(x, w, stride, q)
        b = ref_resnet.conv2d_shifted(x, w, stride, q)
        c = jax.lax.conv_general_dilated(
            x, w, (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
    assert a.shape == b.shape == c.shape
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(a, c, rtol=1e-5, atol=1e-5)


def test_resnet50_flops_per_image_from_the_shapes():
    sizes = dict(depth=50, width=64, image_size=224, num_classes=1000)
    convs, feat = ref_resnet.conv_shapes(sizes)
    assert len(convs) == 53 and feat == 2048
    # 4.09 G multiply-adds forward, the figure the literature quotes
    flops = resnet_family.model_flops_per_item(sizes)
    assert flops == pytest.approx(3 * 2 * 4.09e9, rel=0.01)


def test_ranks_get_batches_whose_rows_all_differ():
    a, _ = ref_resnet.make_batch(KEY, RESNET, 0)
    b, _ = ref_resnet.make_batch(KEY, RESNET, 1)
    rows = np.concatenate([np.asarray(a), np.asarray(b)]).reshape(8, -1)
    assert len({r.tobytes() for r in rows}) == 8


# -------------------------------------------------------------- the control
def test_bfloat16_fails_a_float32_resnet(family=ref_resnet, sizes=RESNET):
    """The control at test size: the reference put in the program's place
    and computed in bfloat16 under a float32 configuration comes out as
    not correct, on three seeds, by the gradient norms; the reference
    itself passes."""
    for seed in (1, 2, 3):
        key = jax.random.PRNGKey(seed)
        reference = family.follow(sizes, key, 1, 3)
        assert compare.decide([as_record(reference)], reference, SOUND)[0]
        low = family.follow(sizes, key, 1, 3, "bfloat16")
        correct, rows = compare.decide([as_record(low)], reference, SOUND)
        assert not correct
        failed = [name for name, _, _, ok in rows if not ok]
        assert any(n.startswith("grad_norm_gap") for n in failed), rows


def test_two_ranks_average_their_gradients():
    one = ref_resnet.follow(RESNET, KEY, 1, 1)
    two = ref_resnet.follow(RESNET, KEY, 2, 1)
    assert one["losses"][0] == two["losses"][0]     # rank 0's own shard
    assert len(two["losses"]) == 2
    assert one["grad_norms"] != two["grad_norms"]
