"""On-chip flash-vs-XLA attention sweep: find the crossover + best blocks.

At short sequence the flash rescaling machinery can cost more than it
saves while the [T,T] score tile still fits on-chip; flash exists for the
memory wall at LONG sequence.  This sweep measures where that wall is on
the chip and which block sizes the kernel wants there, so the auto routing
(``flash_enabled`` / ``LlamaConfig.use_flash``) can pick the winner per
shape instead of a blanket platform default.  Not yet run on the current
machine (ROADMAP queue 1 item 4a).

Per (seq, impl) it times a jitted fwd+bwd (grads wrt q,k,v — the training
shape of a decoder step) of causal GQA attention at fixed
token count (B*T = const), bf16 inputs:

    python tools/flash_sweep.py --out FLASH_SWEEP.json
"""

from __future__ import annotations

import argparse
import datetime
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

SEQS = [512, 1024, 2048, 4096, 8192]
BLOCKS = [(128, 128), (256, 256), (512, 512), (128, 512), (256, 1024)]
TOKENS = 64 * 1024          # B = TOKENS // T  (fixed work per measurement)
H, K, D = 8, 4, 64          # toy heads; mistral7b-4l's are 32, 8, 128


def _loss_fn(attn, iters):
    """One jitted dispatch running ``iters`` fwd+bwd steps in a lax.scan.

    The scan carry perturbs q every iteration from the previous step's
    gradients, so no two executions see the same input and one dispatch
    amortizes the host's launch cost over ``iters`` steps.  XLA would DCE
    any grad the carry ignores — dk/dv come from a separate Pallas call
    than dq — so the carry folds an element of all three."""
    grad = jax.grad(lambda q, k, v: attn(q, k, v).astype(jnp.float32)
                    .sum(), argnums=(0, 1, 2))

    @jax.jit
    def many(q, k, v, seed):
        def body(t, i):
            dq, dk, dv = grad(q + t.astype(q.dtype), k, v)
            t_new = ((dq.ravel()[0] + dk.ravel()[0] + dv.ravel()[0])
                     .astype(jnp.float32) * 1e-6 + i.astype(jnp.float32)
                     * 1e-3)
            return t_new, ()
        t, _ = jax.lax.scan(body, seed, jnp.arange(iters))
        return t
    return many


def _time(fn, args, iters=10, warmup=1):
    """The ``seed`` argument makes the warmup and timed calls differ in
    their inputs.  float() fetches the result to host as a second sync
    barrier."""
    for w in range(warmup):
        jax.block_until_ready(fn(*args, jnp.float32(w)))
    t0 = time.perf_counter()
    out = fn(*args, jnp.float32(warmup))
    jax.block_until_ready(out)
    float(out)
    return (time.perf_counter() - t0) / iters * 1e3  # ms per inner step


def sweep(seqs, iters, tokens=TOKENS, causal=True):
    from horovod_tpu.ops.flash_attention import flash_attention
    from horovod_tpu.parallel.ring_attention import local_flash_attention

    rng = np.random.RandomState(0)
    rows = []
    for T in seqs:
        B = max(tokens // T, 1)
        q = jnp.asarray(rng.randn(B, T, H, D), jnp.bfloat16)
        k = jnp.asarray(rng.randn(B, T, K, D), jnp.bfloat16)
        v = jnp.asarray(rng.randn(B, T, K, D), jnp.bfloat16)
        row = {"seq": T, "batch": B, "tokens": B * T,
               "causal": causal, "ms": {}}

        xla = _loss_fn(functools.partial(local_flash_attention,
                                         causal=causal), iters)
        try:
            row["ms"]["xla"] = round(_time(xla, (q, k, v), iters), 3)
        except Exception as exc:  # noqa: BLE001 — OOM at long T is the point
            row["ms"]["xla"] = None
            row.setdefault("errors", {})["xla"] = repr(exc)[:200]

        for bq, bk in BLOCKS:
            if bq > T or bk > T:
                continue
            fl = _loss_fn(functools.partial(
                flash_attention, causal=causal, block_q=bq, block_k=bk),
                iters)
            key = f"flash_{bq}x{bk}"
            try:
                row["ms"][key] = round(_time(fl, (q, k, v), iters), 3)
            except Exception as exc:  # noqa: BLE001
                row["ms"][key] = None
                row.setdefault("errors", {})[key] = repr(exc)[:200]

        timed = [(v, k) for k, v in row["ms"].items() if v is not None]
        best = min(timed) if timed else (None, None)
        row["best"] = best[1]
        row["flash_best_vs_xla"] = (
            round(row["ms"]["xla"] / best[0], 3)
            if row["ms"].get("xla") and best[1]
            and not best[1].startswith("xla") else None)
        rows.append(row)
        print(json.dumps(row))
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="FLASH_SWEEP.json")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--no-causal", action="store_true",
                    help="sweep NON-causal attention (the bert-family "
                         "routing default's evidence)")
    ap.add_argument("--seqs", default=",".join(map(str, SEQS)))
    ap.add_argument("--tokens", type=int, default=TOKENS,
                    help="tokens per measurement (smoke tests shrink this)")
    args = ap.parse_args()
    seqs = [int(s) for s in args.seqs.split(",")]

    dev = jax.devices()[0]
    rows = sweep(seqs, args.iters, args.tokens,
                 causal=not args.no_causal)
    out = {
        "provenance": "tools/flash_sweep.py — jitted fwd+bwd "
                      f"{'causal' if not args.no_causal else 'non-causal'} GQA "
                      f"attention, bf16, H={H} K={K} D={D}, fixed "
                      f"{args.tokens} tokens per shape",
        "captured_utc": datetime.datetime.now(
            datetime.timezone.utc).isoformat(),
        "device": {"kind": dev.device_kind, "platform": dev.platform},
        "rows": rows,
    }
    with open(args.out, "w") as fh:
        json.dump(out, fh, indent=2)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
