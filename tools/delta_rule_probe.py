"""On the chip: the delta rule's kernel pair alone at a cell's geometry,
against the plain formulation.  ``chiprun -- python tools/delta_rule_probe.py
[--out chiprun_out/DELTA_RULE.json]``: ms a call of the forward and of the
forward with the backward (host clock round ``block_until_ready``, the
median of five), and the largest difference from the plain path's values
and gradients over their largest value.  A time here is a chip's or
nothing: on the CPU the kernels are interpreted.

``--shape B,T,Hk,Hv,dk,dv``: ``qwen3next-80b-a3b-4l``'s by default;
``olmo-hybrid-7b-4l``'s is ``--shape 1,16384,30,30,96,192 --token-heads
131072`` (the plain path six heads at a time, as its step runs it on a
CPU).  Widths that are no multiple of 128 run at ``delta_rule.widths``'
(``ran_at`` says which), and ``kernel_*`` then holds XLA's pads, slices
and changes of layout round the kernels: ``alone_*`` is the pair on heads
that come as wide as they run.  A width that ``widths`` refuses (under 32)
is padded here by hand, what the wrapper would do if it took it
(``forced``): the reading that says where the rule's line belongs."""
import argparse
import json
import statistics
import sys
import time

sys.path.insert(0, ".")
import jax
import jax.numpy as jnp
import numpy as np

from horovod_tpu.models.gated_delta import (by_head_groups,
                                            chunked_gated_delta_rule)
from horovod_tpu.ops import delta_rule


def draw(B, T, hk, hv, dk, dv, dtype, key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (B, T, hk, dk))) / np.sqrt(dk)
    k = unit(jax.random.normal(ks[1], (B, T, hk, dk)))
    v = jax.random.normal(ks[2], (B, T, hv, dv))
    g = np.log(0.99) * jax.random.uniform(ks[3], (B, T, hv), minval=0.1,
                                          maxval=1.9)
    beta = 2 * jax.random.uniform(ks[4], (B, T, hv))
    do = jax.random.normal(ks[5], (B, T, hv, dv))
    return tuple(x.astype(dtype) for x in (q, k, v)) + (g, beta,
                                                        do.astype(dtype))


def gap(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def ms(fn, *args, n=5):
    jax.block_until_ready(fn(*args))
    took = []
    for _ in range(n):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        took.append(1e3 * (time.perf_counter() - t))
    return statistics.median(took)


def pair(rule):
    fwd = jax.jit(rule)

    def both(q, k, v, g, beta, do):
        o, pull = jax.vjp(rule, q, k, v, g, beta)
        return (o,) + pull(do)
    return fwd, jax.jit(both)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", default="2,8192,16,32,128,128")
    ap.add_argument("--chunk", type=int, default=64)
    ap.add_argument("--plain", type=int, default=1)
    ap.add_argument("--token-heads", type=int, default=0,
                    help="the plain path a group of heads at a time: "
                         "gated_delta.by_head_groups' (token, head) pairs")
    ap.add_argument("--groups", default="",
                    help="other lengths of the kernels' straight-line "
                         "stretch to time, as 1,4")
    ap.add_argument("--out", default="")
    ap.add_argument("--rehearse", action="store_true",
                    help="run without a TPU (interpreted kernels): the "
                         "gaps mean something, the times nothing")
    a = ap.parse_args()
    if jax.default_backend() != "tpu" and not a.rehearse:
        sys.exit("no TPU: a time from here would not be the chip's "
                 "(--rehearse runs the interpreted kernels at a small "
                 "--shape)")
    B, T, hk, hv, dk, dv = (int(x) for x in a.shape.split(","))
    rep = hv // hk
    ran_at = delta_rule.widths(dk, dv)
    forced = ran_at is None
    if forced:
        ran_at = tuple(-(-d // delta_rule.LANES) * delta_rule.LANES
                       for d in (dk, dv))

    def wider(x, width):
        return jnp.pad(x, ((0, 0),) * 3 + ((0, width - x.shape[3]),))

    def kernel(q, k, v, g, beta, **kw):
        if forced:
            q, k, v = wider(q, ran_at[0]), wider(k, ran_at[0]), wider(
                v, ran_at[1])
        return delta_rule.gated_delta_rule(q, k, v, g, beta, a.chunk,
                                           **kw)[..., :dv]

    rule = chunked_gated_delta_rule
    if a.token_heads:
        rule = by_head_groups(rule, a.token_heads)
    plain = lambda q, k, v, g, beta: rule(
        jnp.repeat(q, rep, 2), jnp.repeat(k, rep, 2), v, g, beta, a.chunk)
    out = {"device": jax.devices()[0].device_kind, "shape": a.shape,
           "ran_at": list(ran_at), "forced": forced,
           "rehearsal": a.rehearse}
    # the compiled kernels against the plain path, a sequence of 1024
    small = draw(1, 1024, hk, hv, dk, dv, jnp.bfloat16, key=1)
    got, want = (pair(r)[1](*small) for r in (kernel, plain))
    out["gaps"] = dict(zip(("o", "dq", "dk", "dv", "dg", "dbeta"),
                           (gap(x, y) for x, y in zip(got, want))))
    args = draw(B, T, hk, hv, dk, dv, jnp.bfloat16)
    fwd, both = pair(kernel)
    out["kernel_fwd_ms"] = ms(fwd, *args[:5])
    out["kernel_both_ms"] = ms(both, *args)
    if ran_at != (dk, dv):
        # the pair alone: the same heads, already as wide as they run
        q, k, v, g, beta, do = args
        alone = (wider(q, ran_at[0]), wider(k, ran_at[0]),
                 wider(v, ran_at[1]), g, beta, wider(do, ran_at[1]))
        fwd, both = pair(lambda *x: delta_rule.gated_delta_rule(*x, a.chunk))
        out["alone_fwd_ms"] = ms(fwd, *alone[:5])
        out["alone_both_ms"] = ms(both, *alone)
    for group in (int(x) for x in a.groups.split(",") if x):
        fwd, both = pair(lambda q, k, v, g, beta: kernel(
            q, k, v, g, beta, group=group))
        out[f"group{group}_fwd_ms"] = ms(fwd, *args[:5])
        out[f"group{group}_both_ms"] = ms(both, *args)
    if a.plain:
        fwd, both = pair(plain)
        out["plain_fwd_ms"] = ms(fwd, *args[:5])
        out["plain_both_ms"] = ms(both, *args)
    print(json.dumps(out))
    if a.out:
        with open(a.out, "w") as f:
            json.dump(out, f)


if __name__ == "__main__":
    main()
