"""On-chip sweep of the flash attention kernels at the benchmark's eight
attention geometries (``laguna_s2_1-5l-spmd-1c`` has two: a band of 512 at
72 heads and full layers at 48): each kernel alone, forward + backward, the
tiles.

Per geometry (a decoder cell's heads, head width, sequence and window, one
step's batch, bf16) it times, from one device trace, the forward kernel
alone (``_fwd_impl``: ``flash_fwd``) and the two backward kernels alone
(``_bwd_impl``: ``flash_bwd_dq``, ``flash_bwd_dkv``) as the median device
time of the kernel's events, and on the host clock a jitted forward +
backward of ``flash_attention`` (gradients of q, k and v: what a layer of a
training step runs, without ``jax.checkpoint``'s second forward).  Beside
each kernel: the steps of its schedule a head, the time of a step, the
share of the MXU's peak its products reach, and the same shape non-causal
(every block of the grid a step, a mask on the padded tail only: what a
block costs without the causal mask, and on a kernel that walks the dense
grid what splits a live block from a dead step).  A row
also holds the SHA-256 of ``o``, ``lse``, ``dq``, ``dk`` and ``dv``: two
commits whose digests agree computed the same bits.  The tables of PERF.md
section 6, PRs 41 and 44, re-read in one chip call:

    python tools/flash_sweep.py --out chiprun_out/FLASH_SWEEP.json

``--blocks 256x512,512x1024`` adds tile candidates (forward + backward
each), ``--xla`` XLA's own attention where its ``[T, T]`` scores fit (the
crossover of ``flash_min_seq``), ``--seqs`` other sequence lengths.
"""

from __future__ import annotations

import argparse
import datetime
import functools
import glob
import hashlib
import json
import os
import re
import statistics
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

# A cell's attention layer as one step sees it: batch, sequence, query and
# key-value heads, head width, window (benchmark/configs, benchmark/traffic).
GEOMETRIES = {
    "ouro2_6b-16l-spmd-1c": dict(B=1, T=8192, H=16, K=16, D=128, window=0),
    "olmohybrid-4l-spmd-1c": dict(B=1, T=16384, H=30, K=30, D=128, window=0),
    "mistral7b-4l-spmd-1c": dict(B=1, T=4096, H=32, K=8, D=128, window=4096),
    "nemotron3super-11l-spmd-1c": dict(B=1, T=8192, H=32, K=2, D=128,
                                       window=0),
    "qwen3next-4l-spmd-1c": dict(B=2, T=8192, H=16, K=2, D=256, window=0),
    "jamba2_3b-14l-spmd-1c": dict(B=1, T=8192, H=20, K=1, D=128, window=0),
    "laguna_s2_1-5l-spmd-1c.window": dict(B=1, T=16384, H=72, K=8, D=128,
                                          window=512),
    "laguna_s2_1-5l-spmd-1c.full": dict(B=1, T=16384, H=48, K=8, D=128,
                                        window=0),
}
KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
PRODUCTS = {"flash_fwd": 2, "flash_bwd_dq": 3, "flash_bwd_dkv": 4}
# a device event's kernel: ``flash_fwd.3``, ``jvp_flash_fwd_.4`` under autodiff
EVENT_KERNEL = re.compile(r"%?(?:jvp_)?(\w+?)_?(?:\.\d+)?(?: = |$)")


def blocks_of(T, bq, bk, window, causal=True):
    """(grid, steps) a head: the blocks of the dense grid and the steps of
    the kernels' schedule."""
    from horovod_tpu.ops import flash_attention as fa

    flags = fa.block_schedule(T, T, bq, bk, causal, window)[2]
    return -(-T // bq) * -(-T // bk), len(flags)


def kernel_events(trace_dir):
    """{kernel: [device ms of each event]} from the profiler's trace, read
    as the benchmark reads it: the device operations whose instruction is
    named after the ``pallas_call``."""
    from benchmark import trace_reduce

    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    out = {}
    for ops in trace_reduce.read_planes(path)[0].values():
        for name, start_s, end_s in ops:
            m = EVENT_KERNEL.match(name)
            if m and m.group(1) in KERNELS:
                out.setdefault(m.group(1), []).append((end_s - start_s) * 1e3)
    return out


def traced_ms(calls, reps=3):
    """Run every ``(fn, args)`` ``reps`` times under one device trace (each
    compiled and run once before it) and return :func:`kernel_events`."""
    for fn, args in calls:
        jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            for fn, args in calls:
                for _ in range(reps):
                    jax.block_until_ready(fn(*args))
        return kernel_events(d)


def host_ms(fn, args, reps=5):
    """Wall ms a call of ``reps`` back-to-back calls, after one warm-up."""
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    out = [fn(*args) for _ in range(reps)]
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e3


def inputs(g, seed=0):
    """q, do ``[B*H, T, D]`` and k, v ``[B*K, T, D]`` in bf16, made on the
    device: the layout the kernels take."""
    bh, bk = g["B"] * g["H"], g["B"] * g["K"]
    return tuple(
        jax.random.normal(key, (n, g["T"], g["D"]), jnp.bfloat16)
        for key, n in zip(jax.random.split(jax.random.PRNGKey(seed), 4),
                          (bh, bk, bk, bh)))


def per_kernel(g, bq=512, bk=512, reps=3):
    """Each kernel alone at geometry ``g``, causal (with the cell's window)
    and non-causal: ({kernel: {steps, ms, step_us, mxu_pct, full_ms,
    full_step_us}}, {output: sha256 of the causal call's bytes})."""
    from benchmark import cell
    from horovod_tpu.ops import flash_attention as fa

    peak = cell.peaks_for(jax.devices()[0].device_kind)["bf16_flops_per_s"]
    q, k, v, do = inputs(g)
    rep, scale = g["H"] // g["K"], g["D"] ** -0.5
    heads = g["B"] * g["H"]

    def alone(causal):
        window = g["window"] if causal else 0
        fwd = jax.jit(lambda q, k, v: fa._fwd_impl(
            q, k, v, scale, causal, bq, bk, False, rep, window))
        o, lse = fwd(q, k, v)
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1)
        bwd = jax.jit(functools.partial(
            fa._bwd_impl, scale=scale, causal=causal, block_q=bq, block_k=bk,
            interpret=False, rep=rep, window=window))
        events = traced_ms(
            [(fwd, (q, k, v)), (bwd, (q, k, v, do, lse, delta))], reps)
        return ({name: statistics.median(events[name]) for name in KERNELS},
                (o, lse) + tuple(bwd(q, k, v, do, lse, delta)))

    ms, outputs = alone(True)
    full_ms, _ = alone(False)
    grid, steps = blocks_of(g["T"], bq, bk, g["window"])
    out = {}
    for name in KERNELS:
        least_us = PRODUCTS[name] * 2 * bq * bk * g["D"] / peak * 1e6
        out[name] = {
            "steps": steps, "ms": round(ms[name], 4),
            "step_us": round(ms[name] * 1e3 / (steps * heads), 4),
            "mxu_pct": round(
                100 * least_us * steps * heads / (ms[name] * 1e3), 2),
            "full_ms": round(full_ms[name], 4),
            "full_step_us": round(full_ms[name] * 1e3 / (grid * heads), 4)}
    return out, {name: hashlib.sha256(np.asarray(x).tobytes()).hexdigest()
                 for name, x in zip(("o", "lse", "dq", "dk", "dv"), outputs)}


def fwd_bwd_ms(g, attn, reps=5):
    """Forward + backward of ``attn(q, k, v)`` on ``[B, T, H, D]`` inputs,
    wall ms a call."""
    q, k, v, _ = inputs(g)
    to4 = lambda x, h: x.reshape(  # noqa: E731
        g["B"], h, g["T"], g["D"]).transpose(0, 2, 1, 3)
    grad = jax.jit(jax.grad(
        lambda q, k, v: attn(q, k, v).astype(jnp.float32).sum(),
        argnums=(0, 1, 2)))
    return host_ms(grad, (to4(q, g["H"]), to4(k, g["K"]), to4(v, g["K"])),
                   reps)


def sweep(geometries, blocks, xla=False, reps=3):
    from horovod_tpu.ops.flash_attention import flash_attention
    from horovod_tpu.parallel.ring_attention import local_flash_attention

    rows = []
    for name, g in geometries.items():
        row = {"cell": name, **g, "fwd_bwd_ms": {}, "errors": {}}
        window = g["window"] or None
        candidates = {f"flash_{bq}x{bk}": functools.partial(
            flash_attention, causal=True, window=window, block_q=bq,
            block_k=bk) for bq, bk in blocks if bq <= g["T"] and bk <= g["T"]}
        if xla:
            candidates["xla"] = functools.partial(
                local_flash_attention, causal=True, window=window)
        try:
            row["kernels"], row["outputs_sha256"] = per_kernel(
                g, *blocks[0], reps=reps)
        except Exception as exc:  # noqa: BLE001 — a tile Mosaic refuses
            row["errors"]["kernels"] = repr(exc)[:200]
        for key, attn in candidates.items():
            try:
                row["fwd_bwd_ms"][key] = round(fwd_bwd_ms(g, attn), 3)
            except Exception as exc:  # noqa: BLE001 — OOM at long T
                row["errors"][key] = repr(exc)[:200]
        rows.append(row)
        print(json.dumps(row), flush=True)
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="FLASH_SWEEP.json")
    ap.add_argument("--cells", default=",".join(GEOMETRIES))
    ap.add_argument("--seqs", default="",
                    help="sequence lengths in place of each cell's own")
    ap.add_argument("--blocks", default="512x512",
                    help="tile candidates, the first also for the kernels "
                         "alone")
    ap.add_argument("--xla", action="store_true",
                    help="XLA's attention beside the kernels")
    ap.add_argument("--reps", type=int, default=3)
    args = ap.parse_args()
    blocks = [tuple(int(n) for n in b.split("x"))
              for b in args.blocks.split(",")]
    geometries = {c: GEOMETRIES[c] for c in args.cells.split(",")}
    if args.seqs:
        geometries = {f"{c}@{T}": dict(g, T=int(T))
                      for c, g in geometries.items()
                      for T in args.seqs.split(",")}

    dev = jax.devices()[0]
    out = {
        "provenance": "tools/flash_sweep.py: causal attention, bf16; "
                      "kernels alone by device trace (median event; full_*: "
                      "the same shape non-causal), forward + backward on "
                      "the host clock",
        "captured_utc": datetime.datetime.now(
            datetime.timezone.utc).isoformat(),
        "device": {"kind": dev.device_kind, "platform": dev.platform},
        "rows": sweep(geometries, blocks, args.xla, args.reps),
    }
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as fh:
        json.dump(out, fh, indent=2)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
