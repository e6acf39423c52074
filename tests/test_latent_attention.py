"""Latent attention (``models/latent_attention.py``) at test size on the
CPU: the block against the equations as the benchmark's float32 reference
writes them (two low-rank paths with norms of their own, one rotary key for
all heads, a score of two products over ``sqrt(d_nope + d_rope)``, values
of another width than the keys'), on both attention paths; one score worked
out by hand; the published interleaved pairs against the program's
de-interleaved columns; and what goes to the kernels (keys of ``d_qk``,
values of ``d_v``, never padded to the keys' width)."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import joyai as ref                # noqa: E402
from family import worst_rel                                # noqa: E402
from horovod_tpu.models import latent_attention as latent   # noqa: E402

DIMS = latent.LatentDims(d_model=64, n_heads=4, q_rank=24, kv_rank=16,
                         d_nope=16, d_rope=8, d_v=16, rope_theta=32000000.0)
SIZES = dict(hidden_size=64, num_attention_heads=4, q_lora_rank=24,
             kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
             v_head_dim=16, rope_theta=32000000, rms_norm_eps=1e-6)
T = 40


def published():
    """A block's seeded parameters in the published column order (norm
    weights away from 1) and a normed input."""
    keys = iter(jax.random.split(jax.random.PRNGKey(50), 8))
    p = latent.init_params(DIMS, jnp.float32, keys)
    for name in ("q_norm", "kv_norm"):
        p[name] = 1.0 + jax.random.uniform(next(keys), p[name].shape,
                                           minval=-0.5, maxval=0.5)
    return p, jax.random.normal(next(keys), (2, T, 64))


@pytest.mark.parametrize("flash", [False, True], ids=["plain", "flash"])
def test_the_block_is_the_equations(flash):
    """Output and the gradients to every matrix, both inner norms and the
    input against the reference's literal equations, the parameters
    permuted from the published order (a gradient comes back in the
    program's order, the reference's permuted alike)."""
    p, h = published()
    weigh = jnp.cos(jnp.arange(64.0))

    def program(p, h):
        return jnp.sum(latent.latent_attention(
            latent.from_published(p, DIMS), h, DIMS, flash) * weigh)

    def equations(p, h):
        return jnp.sum(ref.latent_attention(p, h, SIZES, jnp.einsum) * weigh)

    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(program, argnums=(0, 1)))(p, h)
        want = jax.jit(jax.value_and_grad(equations, argnums=(0, 1)))(p, h)
    assert abs(float(got[0]) - float(want[0])) <= 1e-5 * abs(float(want[0]))
    assert worst_rel(got[1], want[1]) <= 2e-4


def test_one_score_by_hand():
    """Head 2's score of query 7 on key 3, from the published columns with
    the pairs (2j, 2j + 1) turned in NumPy, is what the program's ``q`` and
    ``k`` (a head ``[rope | nope]``, pairs ``(i, i + 4)``) give; the one
    rotary key is every head's, and ``v`` is 16 wide beside keys of 24."""
    p, h = published()
    q, k, v = latent.qkv(latent.from_published(p, DIMS), h, DIMS)
    assert q.shape == k.shape == (2, T, 4, 24) and v.shape == (2, T, 4, 16)
    assert np.array_equal(np.asarray(k[..., 0, :8]), np.asarray(k[..., 3, :8]))
    x = np.asarray(h[1], np.float64)
    w = {n: np.asarray(a, np.float64) for n, a in p.items()}
    norm = lambda y, g: y / np.sqrt((y * y).mean(-1, keepdims=True)
                                    + 1e-6) * g

    def turned(y, pos):
        f = 32000000.0 ** (-np.arange(0, 8, 2) / 8)
        a, b, c, s = y[0::2], y[1::2], np.cos(pos * f), np.sin(pos * f)
        out = np.empty(8)
        out[0::2], out[1::2] = a * c - b * s, b * c + a * s
        return out

    head = (norm(x[7] @ w["wq_a"], w["q_norm"]) @ w["wq_b"])[2 * 24:3 * 24]
    kv_a = x[3] @ w["wkv_a"]
    k_nope = (norm(kv_a[:16], w["kv_norm"]) @ w["wkv_b"])[2 * 32:2 * 32 + 16]
    want = head[:16] @ k_nope + turned(head[16:], 7) @ turned(kv_a[16:], 3)
    got = float(jnp.dot(q[1, 7, 2], k[1, 3, 2],
                        precision=jax.lax.Precision.HIGHEST))
    assert abs(got - want) <= 1e-4 * abs(want)


def test_the_permutation_moves_columns_and_loses_none():
    p, _ = published()
    moved = latent.from_published(p, DIMS)
    assert set(moved) == set(p)
    for name in ("wq_b", "wkv_a"):
        assert moved[name].shape == p[name].shape
        assert np.allclose(np.sort(np.asarray(moved[name]), axis=1),
                           np.sort(np.asarray(p[name]), axis=1))
        assert not np.array_equal(np.asarray(moved[name]),
                                  np.asarray(p[name]))
    # head 1's first rope column is its published column 16 (pair 0's first)
    assert np.array_equal(np.asarray(moved["wq_b"][:, 24]),
                          np.asarray(p["wq_b"][:, 24 + 16]))
    assert np.array_equal(np.asarray(moved["wkv_a"][:, 16 + 4]),
                          np.asarray(p["wkv_a"][:, 16 + 1]))
    for name in ("wq_a", "q_norm", "kv_norm", "wkv_b", "wo"):
        assert moved[name] is p[name]
