"""On the chip: the two row movements of the dropless expert layer alone,
at the three cells' shapes.  ``chiprun -- python tools/expert_rows_probe.py
[--out chiprun_out/EXPERT_ROWS.json]``: ms a call (host clock round
``block_until_ready``, ten calls enqueued together, the median of five such
tens) of

* "gather a block's rows": ``z[tokens]`` for the ``R`` sorted assignments
  of a block, beside the parent's gather of every assignment made anywhere
  (``S * top_k`` rows) and its sum back (the yardstick: 33 ns a row);
* "add a block's rows at their tokens", four forms: (i) ``.at[tokens]
  .add`` into a float32 accumulator, rows as they come and sorted by token;
  (ii) rows sorted by token, a segmented sum by shifted adds and one gather
  of ``S`` run ends; (iii) a loop over ``j < most held rows a token`` of
  ``S``-row gathers; and (iv) rows sorted by token and one grouped product
  (``lax.ragged_dot_general``, the ragged dimension contracted) of a
  which-token-of-its-tile matrix with the rows, a group a tile of tokens;
* the sorts that each form needs.

Each form's result is held against form (i)'s.  A time here is a chip's or
nothing."""
import argparse
import json
import statistics
import sys
import time

sys.path.insert(0, ".")
import jax
import jax.numpy as jnp
import numpy as np

# S tokens, L the width a routed expert reads, K = top_k, E experts of
# which H are held, R the block's rows
CELLS = {
    "qwen3next": dict(S=16384, L=2048, K=10, E=512, H=64,
                      R=(20480, 32768, 40960)),
    "nemotron3super": dict(S=8192, L=1024, K=22, E=512, H=16,
                           R=(8192, 11264)),
    "laguna_run": dict(S=4096, L=3072, K=10, E=256, H=16, R=(4096,)),
    "laguna_whole": dict(S=16384, L=3072, K=10, E=256, H=16, R=(16384,)),
}


def ms(fn, *args, calls=10, tens=5):
    jax.block_until_ready(fn(*args))
    took = []
    for _ in range(tens):
        t = time.perf_counter()
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        took.append(1e3 * (time.perf_counter() - t) / calls)
    return round(statistics.median(took), 4)


def route(key, S, K, E, H):
    """``(order, inverse, held)`` as ``dropless_moe_ffn`` makes them for
    even routing over ``E`` experts, the first ``H`` held."""
    _, ids = jax.lax.top_k(jax.random.normal(key, (S, E)), K)
    local = ids.reshape(-1)
    keys = jnp.where(local < H, local, H)
    order = jnp.argsort(keys, stable=True)
    return order, jnp.argsort(order), int(jnp.sum(local < H))


def shifted_sum(x, tok, K):
    """Inclusive sums along runs of equal ``tok`` (sorted), runs of at most
    ``K`` rows: ``ceil(log2(K))`` shifted adds."""
    d = 1
    while d < K:
        same = (jnp.roll(tok, d) == tok) & (jnp.arange(tok.shape[0]) >= d)
        x = x + jnp.where(same[:, None], jnp.roll(x, d, axis=0), 0)
        d *= 2
    return x


def forms(S, K, R):
    def add_i(acc, out, tok):
        return acc.at[tok].add(out.astype(jnp.float32))

    def add_i_sorted(acc, out, tok):
        perm = jnp.argsort(tok)
        return acc.at[tok[perm]].add(out[perm].astype(jnp.float32),
                                     indices_are_sorted=True)

    def add_i_presorted(acc, out_s, tok_s):
        return acc.at[tok_s].add(out_s.astype(jnp.float32),
                                 indices_are_sorted=True)

    def add_ii(acc, out, tok):
        perm = jnp.argsort(tok)
        tok_s = tok[perm]
        x = shifted_sum(out[perm].astype(jnp.float32), tok_s, K)
        end = jnp.searchsorted(tok_s, jnp.arange(S, dtype=tok.dtype),
                               side="right") - 1
        hit = (end >= 0) & (tok_s[jnp.maximum(end, 0)] == jnp.arange(S))
        return acc + jnp.where(hit[:, None], x[jnp.maximum(end, 0)], 0)

    def add_iii(acc, out, inverse, lo):
        at = inverse.reshape(S, K) - lo
        at = jnp.sort(jnp.where((at >= 0) & (at < R), at, R), axis=1)
        most = jnp.max(jnp.sum(at < R, axis=1))
        out = jnp.concatenate([out, jnp.zeros_like(out[:1])])

        def one(j, acc):
            return acc + out[jax.lax.dynamic_index_in_dim(
                at, j, axis=1, keepdims=False)].astype(jnp.float32)
        return jax.lax.fori_loop(0, most, one, acc)

    def add_iv(acc, out, tok, tile=256, presorted=False):
        """(iv) rows sorted by token, then one grouped product with the
        ragged dimension contracted: a group is a tile of ``tile`` tokens,
        the left side says which of the tile's tokens a row belongs to."""
        if not presorted:
            perm = jnp.argsort(tok)
            out, tok = out[perm], tok[perm]
        sizes = jnp.sum((tok // tile)[:, None] == jnp.arange(S // tile)[None],
                        axis=0, dtype=jnp.int32)
        which = (tok[:, None] % tile == jnp.arange(tile)[None, :]).astype(
            out.dtype)
        part = jax.lax.ragged_dot_general(
            which, out, sizes, jax.lax.RaggedDotDimensionNumbers(
                dot_dimension_numbers=(([0], [0]), ([], [])),
                lhs_ragged_dimensions=[0], rhs_group_dimensions=[]),
            preferred_element_type=jnp.float32)
        return acc + part.reshape(acc.shape)

    return add_i, add_i_sorted, add_i_presorted, add_ii, add_iii, add_iv


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", default=",".join(CELLS))
    ap.add_argument("--out", default="")
    ap.add_argument("--rehearse", action="store_true",
                    help="run without a TPU at a sixteenth of the tokens: "
                         "the gaps mean something, the times nothing")
    a = ap.parse_args()
    if jax.default_backend() != "tpu" and not a.rehearse:
        sys.exit("no TPU: a time from here would not be the chip's")
    report = {"device": jax.devices()[0].device_kind,
              "rehearsal": a.rehearse, "cells": {}}
    for name in a.cells.split(","):
        c = dict(CELLS[name])
        if a.rehearse:
            c["S"] //= 16
            c["R"] = tuple(r // 16 for r in c["R"][:1])
        S, L, K = c["S"], c["L"], c["K"]
        kz, kr, ko = jax.random.split(jax.random.PRNGKey(0), 3)
        z = jax.random.normal(kz, (S, L), jnp.bfloat16)
        order, inverse, held = route(kr, S, K, c["E"], c["H"])
        row = {"held_rows": held, "rows": S * K}
        every = jax.random.normal(ko, (S * K, L), jnp.bfloat16)
        row["parent_gather_ms"] = ms(jax.jit(lambda z, o: z[o // K]), z,
                                     order)
        row["parent_sum_ms"] = ms(jax.jit(
            lambda y, i: y[i].reshape(S, K, L).sum(axis=1)), every, inverse)
        row["parent_sum_unfused_ms"] = ms(jax.jit(
            lambda y, i: jax.lax.optimization_barrier(y[i]).reshape(
                S, K, L).sum(axis=1)), every, inverse)
        row["argsort_all_ms"] = ms(jax.jit(jnp.argsort), order)
        for R in c["R"]:
            tok = (order[:R] // K).astype(jnp.int32)
            out = jnp.where((jnp.arange(R) < held)[:, None], every[:R], 0)
            acc = jnp.zeros((S, L), jnp.float32)
            r = {}
            r["gather_ms"] = ms(jax.jit(lambda z, t: z[t]), z, tok)
            r["gather_in_bounds_ms"] = ms(jax.jit(
                lambda z, t: z.at[t].get(mode="promise_in_bounds")), z, tok)
            r["gather_tiles_ms"] = ms(jax.jit(
                lambda z, t: z.reshape(S, L // 128, 128)[t].reshape(R, L)),
                z, tok)
            r["gather_f32_ms"] = ms(jax.jit(lambda z, t: z[t]),
                                    z.astype(jnp.float32), tok)
            r["argsort_block_ms"] = ms(jax.jit(jnp.argsort), tok)
            *jitted, add_iv = forms(S, K, R)
            add_i, add_i_sorted, add_i_presorted, add_ii, add_iii = (
                jax.jit(f) for f in jitted)
            perm = jnp.argsort(tok)
            want = add_i(acc, out, tok)
            r["add_i_ms"] = ms(add_i, acc, out, tok)
            r["add_i_sorted_ms"] = ms(add_i_sorted, acc, out, tok)
            r["add_i_presorted_ms"] = ms(add_i_presorted, acc, out[perm],
                                         tok[perm])
            r["add_ii_ms"] = ms(add_ii, acc, out, tok)
            r["add_iii_ms"] = ms(add_iii, acc, out, inverse, 0)
            for tile in (128, 256, 512):
                if S % tile == 0:
                    r[f"add_iv_tile{tile}_ms"] = ms(jax.jit(
                        lambda a, o, t: add_iv(a, o, t, tile)), acc, out, tok)
            r["add_iv_presorted_ms"] = ms(jax.jit(
                lambda a, o, t: add_iv(a, o, t, presorted=True)), acc,
                out[perm], tok[perm])
            r["gap_iv"] = float(jnp.max(jnp.abs(
                jax.jit(add_iv)(acc, out, tok) - want)) / float(
                    jnp.max(jnp.abs(want))))
            scale = float(jnp.max(jnp.abs(want)))
            for form, got in (("i_sorted", add_i_sorted(acc, out, tok)),
                              ("ii", add_ii(acc, out, tok)),
                              ("iii", add_iii(acc, out, inverse, 0))):
                r[f"gap_{form}"] = float(jnp.max(jnp.abs(got - want))
                                         / scale)
            row[f"R{R}"] = r
            print(name, R, json.dumps(r), flush=True)
        report["cells"][name] = row
    print(json.dumps(report))
    if a.out:
        with open(a.out, "w") as f:
            json.dump(report, f)


if __name__ == "__main__":
    main()
