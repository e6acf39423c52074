"""Record the inline-dispatch fast path's number (VERDICT r4 weak #3).

The r4 engine made blocking single-controller collectives run the
coordinator cycle INLINE on the submitting thread (``Engine.kick``),
removing two thread handoffs from the small-tensor critical path — but
shipped without a recorded before/after.  This tool captures the
evidence on the hermetic 8-device CPU mesh, no chip required:

- per-size eager-engine vs in-graph-psum dispatch latency (p50 over
  ``--iters`` timed calls, after warmup), and
- the same engine sweep with ``HOROVOD_INLINE_KICK=0`` (the legacy
  wake-the-cycle-thread dispatch), giving the inline-vs-threaded delta.

Each arm runs in a fresh subprocess (env is read once at ``init()``).
Output: ``LATENCY_EVIDENCE.json`` at the repo root — committed so the
number survives next to the mechanism it justifies.  The regression
guard lives in ``tests/test_engine.py::test_inline_kick_latency_guard``.

Usage:  python tools/latency_evidence.py [--iters 50] [--out PATH]
"""

from __future__ import annotations

import argparse
import datetime
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ARM_SRC = r"""
import json, statistics, sys, time
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import lax

from horovod_tpu.compat import shard_map
import horovod_tpu as hvd

iters = int(sys.argv[1])
hvd.init()
n = hvd.size()
m = hvd.mesh()
from horovod_tpu.common import basics
out = {"world": n, "iters": iters,
       "inline_kick": basics._get_state().engine.inline_kick,
       "engine_latency_ms": {}, "psum_latency_ms": {}}

for label, nbytes in (("4KB", 4 << 10), ("64KB", 64 << 10),
                      ("1MB", 1 << 20), ("16MB", 16 << 20)):
    elems = max(1, nbytes // 4)
    x = jax.device_put(np.ones((n, elems), np.float32),
                       NamedSharding(m, P("hvd")))
    for _ in range(5):
        r = hvd.allreduce(x, name="lat_warm", op=hvd.Sum)
    jax.block_until_ready(r)
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        r = hvd.allreduce(x, name="lat", op=hvd.Sum)
        jax.block_until_ready(r)
        ts.append(time.perf_counter() - t0)
    out["engine_latency_ms"][label] = round(
        statistics.median(ts) * 1e3, 3)

    def body(s):
        return lax.psum(s.reshape(s.shape[1:]), "hvd")
    f = jax.jit(shard_map(body, mesh=m, in_specs=P("hvd"), out_specs=P(),
                          check_vma=False))
    y = f(x); jax.block_until_ready(y)
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        y = f(x)
        jax.block_until_ready(y)
        ts.append(time.perf_counter() - t0)
    out["psum_latency_ms"][label] = round(statistics.median(ts) * 1e3, 3)

print("LATENCY " + json.dumps(out))
"""


def run_arm(inline: bool, iters: int) -> dict:
    env = dict(os.environ)
    env["HOROVOD_INLINE_KICK"] = "1" if inline else "0"
    # Hermetic CPU arm: ARM_SRC pins the platform and the 8-device mesh.
    env["PYTHONPATH"] = REPO
    env.pop("JAX_PLATFORMS", None)
    r = subprocess.run([sys.executable, "-c", ARM_SRC, str(iters)],
                       capture_output=True, text=True, timeout=1800,
                       env=env, cwd=REPO)
    for ln in r.stdout.splitlines():
        if ln.startswith("LATENCY "):
            return json.loads(ln[len("LATENCY "):])
    return {"error": f"no LATENCY line (rc={r.returncode})",
            "stderr_tail": r.stderr[-1500:]}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=50)
    ap.add_argument("--out", default=os.path.join(REPO,
                                                  "LATENCY_EVIDENCE.json"))
    args = ap.parse_args()

    doc = {
        "provenance": "tools/latency_evidence.py — p50 over timed calls on "
                      "the hermetic 8-device CPU mesh (one fresh subprocess "
                      "per arm; HOROVOD_INLINE_KICK is read at init)",
        "captured_utc": datetime.datetime.now(
            datetime.timezone.utc).isoformat(),
        "platform": "cpu (8 virtual devices)",
        "inline": run_arm(True, args.iters),
        "threaded": run_arm(False, args.iters),
    }
    inl = doc["inline"].get("engine_latency_ms", {})
    thr = doc["threaded"].get("engine_latency_ms", {})
    doc["inline_vs_threaded_speedup"] = {
        k: round(thr[k] / inl[k], 3)
        for k in inl if k in thr and inl[k] > 0}
    with open(args.out, "w") as fh:
        json.dump(doc, fh, indent=1)
    print(json.dumps(doc, indent=1))


if __name__ == "__main__":
    main()
