#!/usr/bin/env python3
"""Proof that the training path starts and computes right on a TPU chip.

    python chip_smoke.py [--seed N]          one chip, one process
    python chip_smoke.py --chips 4           the cross-chip path only

Every phase prints one JSON line (name, seconds, compile seconds and
persistent-cache hits/misses, what was compared, device bytes).  A phase
that fails raises: the script exits non-zero and never prints the last
line, which is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Without a TPU the script exits non-zero at once (JAX itself would fall back
to the CPU with a warning).  ``--rehearse`` is the one way past that: the
same control flow at tiny sizes on the CPU, Pallas in interpret mode, the
device reported as what it is.

Default run, all data and weights from ``--seed``:

- ``device``         the platform is ``tpu``.
- ``engine``         eager allreduce / grouped_allreduce / allgather /
                     broadcast through the background engine up to one full
                     64 MiB fusion buffer, integer-valued, bitwise vs numpy
                     (cycle thread, fusion, buffer donation, async launch
                     pipeline on the device).
- ``resnet50_spmd``, ``resnet50_eager``
                     ResNet-50, 224², batch 128, bf16, full depth, through
                     ``examples/resnet_synthetic.py``'s ``build`` in both
                     step modes; the two modes' losses agree.
- ``flash_llama``    ``llama.make_train_step`` at ``mistral_7b()`` widths,
                     depth cut to 2 layers (0.70 B parameters, bf16, Adam
                     with bf16 moments), T=4096; the compiled step holds
                     the Pallas kernel; loss and a gradient norm agree with
                     ``use_flash=False`` at T=2048.

``--chips 4`` runs only what exists across chips and what it is compared
with.  One process per chip at a time: the parent NEVER imports jax; it
runs each sub-phase as a child to completion before the next:

- ``single4`` child: one process, four devices — eager collectives on
  ``hvd.stack_per_rank`` inputs bitwise vs numpy, ResNet-50 spmd at global
  batch 512 with its shards on four distinct devices and all-reduces in
  the compiled step.
- ``torovodrun -np 4 python chip_smoke.py --worker rank``: one process per
  chip through the launcher, the example's eager step at per-rank batch
  128 on the same global batch, compared with ``single4`` step by step.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import importlib.util
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")

# Stated tolerances (bf16 compute; the two sides are different XLA
# programs, so reductions reassociate).
RESNET_LOSS_RTOL = 2e-2     # per-step loss, spmd vs eager / 1 vs 4 procs
LLAMA_LOSS_RTOL = 5e-3      # flash vs XLA attention, loss at T=2048
LLAMA_GNORM_RTOL = 3e-2     # ... and the norm of d loss / d wq (layer 0)


@dataclasses.dataclass(frozen=True)
class Sizes:
    engine_bytes: tuple       # allreduce payloads per rank
    fusion_bytes: int         # one grouped allreduce, 4 tensors
    resnet_depth: int
    resnet_image: int
    resnet_classes: int
    resnet_batch: int         # per chip
    resnet_steps: int
    llama_seq: int
    llama_ref_seq: int
    llama_steps: int


REAL = Sizes(engine_bytes=(4 << 10, 1 << 20, 64 << 20), fusion_bytes=64 << 20,
             resnet_depth=50, resnet_image=224, resnet_classes=1000,
             resnet_batch=128, resnet_steps=3,
             llama_seq=4096, llama_ref_seq=2048, llama_steps=3)
TINY = Sizes(engine_bytes=(4 << 10, 256 << 10), fusion_bytes=1 << 20,
             resnet_depth=18, resnet_image=32, resnet_classes=10,
             resnet_batch=4, resnet_steps=2,
             llama_seq=256, llama_ref_seq=128, llama_steps=2)


def emit(**fields):
    print(json.dumps(fields), flush=True)


class Phase(contextlib.AbstractContextManager):
    """Times one phase; on success prints its JSON line, on failure lets
    the exception through (the script then exits non-zero)."""

    compile_s = 0.0
    hits = misses = 0

    def __init__(self, name):
        self.name, self.fields = name, {}

    @classmethod
    def install_listeners(cls):
        import jax.monitoring as mon

        def duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                cls.compile_s += secs

        def event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                cls.hits += 1
            elif name == "/jax/compilation_cache/cache_misses":
                cls.misses += 1

        mon.register_event_duration_secs_listener(duration)
        mon.register_event_listener(event)

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0, self.h0, self.m0 = Phase.compile_s, Phase.hits, Phase.misses
        return self.fields

    def __exit__(self, exc_type, exc, tb):
        if exc_type is None:
            import jax
            stats = jax.local_devices()[0].memory_stats() or {}
            emit(phase=self.name, ok=True,
                 seconds=round(time.perf_counter() - self.t0, 3),
                 compile_seconds=round(Phase.compile_s - self.c0, 3),
                 cache_hits=Phase.hits - self.h0,
                 cache_misses=Phase.misses - self.m0,
                 peak_bytes_in_use=stats.get("peak_bytes_in_use"),
                 **self.fields)
        return False


def memory_bytes(compiled):
    m = compiled.memory_analysis()
    return {k: int(getattr(m, k + "_size_in_bytes"))
            for k in ("argument", "output", "temp", "alias",
                      "generated_code")}


def close(a, b, rtol):
    return abs(a - b) <= rtol * max(abs(a), abs(b))


def load_example():
    spec = importlib.util.spec_from_file_location(
        "resnet_synthetic", os.path.join(REPO, "examples",
                                         "resnet_synthetic.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------------ engine
def engine_phase(hvd, sizes, seed, fields, cross_chip):
    """Eager collectives through the background engine, bitwise vs numpy."""
    import jax
    import numpy as np
    from horovod_tpu.ops import eager

    n = hvd.size()
    rng = np.random.RandomState(seed)
    eng = eager._engine()
    c0 = (eng.cycle_count, eng.pipeline_dispatches, eng.fast_lane_dispatches)

    def per_rank(nbytes, cols=None):
        """One integer-valued float32 tensor per rank (sums stay exact).
        Rows divide by the world so reducescatter / alltoall apply."""
        elems = max(nbytes // 4, n * n)
        shape = (elems,) if cols is None else (elems // cols, cols)
        return [rng.randint(-64, 64, size=shape).astype(np.float32)
                for _ in range(n)]

    compared = []
    for nbytes in sizes.engine_bytes:
        vals = per_rank(nbytes)
        # A host array: the layer owns the device copy and DONATES it to
        # the fused program (a no-op on the CPU, real on the chip).
        for op, want in ((hvd.Sum, np.sum(vals, axis=0)),
                         (hvd.Average, np.sum(vals, axis=0) / n)):
            got = np.asarray(hvd.allreduce(np.stack(vals), op=op))
            assert np.array_equal(got, want), ("allreduce", nbytes, op)
        compared.append(f"allreduce {nbytes}B sum+average")

    # A caller-owned device array aliases the engine's input: it must NOT
    # be donated — read it back after the collective.
    vals = per_rank(1 << 20)
    mine = hvd.stack_per_rank(vals)
    got = np.asarray(hvd.allreduce(mine, op=hvd.Sum))
    assert np.array_equal(got, np.sum(vals, axis=0))
    assert np.array_equal(np.asarray(mine), np.stack(vals)), \
        "caller-owned input was clobbered (use after donate)"
    compared.append("caller-owned input intact after allreduce")

    # One full fusion buffer in one atomic group.
    group = [per_rank(sizes.fusion_bytes // 4) for _ in range(4)]
    outs = hvd.grouped_allreduce([np.stack(v) for v in group], op=hvd.Sum)
    for v, out in zip(group, outs):
        assert np.array_equal(np.asarray(out), np.sum(v, axis=0))
    compared.append(f"grouped_allreduce 4x{sizes.fusion_bytes // 4}B")

    # Many small tensors in flight at once: the cycle thread fuses them
    # and the launch pipeline runs ahead of the settles.
    small = [per_rank(64 << 10) for _ in range(24)]
    handles = [hvd.allreduce_async(np.stack(v), op=hvd.Sum) for v in small]
    for v, h in zip(small, handles):
        assert np.array_equal(np.asarray(hvd.synchronize(h)),
                              np.sum(v, axis=0))
    compared.append("24 async allreduces of 64KiB")

    vals = per_rank(1 << 20, cols=256)
    got = np.asarray(hvd.allgather(np.stack(vals)))
    assert np.array_equal(got, np.concatenate(vals, axis=0))
    root = n - 1
    got = np.asarray(hvd.broadcast(np.stack(vals), root_rank=root))
    assert np.array_equal(got, vals[root])
    compared.append("allgather, broadcast 1MiB")

    if cross_chip:
        x = hvd.stack_per_rank(vals)
        devs = {s.device.id for s in x.addressable_shards}
        assert len(devs) == n, f"stack_per_rank put {n} ranks on {devs}"
        rows = vals[0].shape[0] // n
        got = np.asarray(hvd.reducescatter(np.stack(vals), op=hvd.Sum))
        want = np.sum(vals, axis=0).reshape((n, rows) + vals[0].shape[1:])
        assert np.array_equal(got, want), "reducescatter"
        got = np.asarray(hvd.alltoall(np.stack(vals)))
        want = np.stack([np.concatenate(
            [v[r * rows:(r + 1) * rows] for v in vals]) for r in range(n)])
        assert np.array_equal(got, want), "alltoall"
        compared.append("reducescatter, alltoall 1MiB")

    fields.update(
        world=n, compared="bitwise vs numpy: " + "; ".join(compared),
        cycles=eng.cycle_count - c0[0],
        fused_dispatches=eng.pipeline_dispatches - c0[1],
        fast_lane_dispatches=eng.fast_lane_dispatches - c0[2],
        donation=jax.default_backend() != "cpu")
    assert fields["fused_dispatches"] > 0, "no fused program was dispatched"


# ------------------------------------------------------------------ resnet
def resnet_data(sizes, batch, seed):
    from horovod_tpu.models import resnet
    return resnet.synthetic_batch(batch, image_size=sizes.resnet_image,
                                  num_classes=sizes.resnet_classes, seed=seed)


def param_digest(params):
    """sha256 over the bytes of a parameter pytree, leaves in tree order."""
    import hashlib

    import jax
    import numpy as np
    h = hashlib.sha256()
    for x in jax.tree_util.tree_leaves(params):
        h.update(np.asarray(x).tobytes())
    return h.hexdigest()[:16]


def resnet_run(hvd, sizes, seed, mode, images, labels, fields,
               init_seed=None):
    """A few steps of the example's ``build`` in ``mode``; returns the
    per-step losses (this process's own in eager mode)."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    example = load_example()
    step, params, stats, opt_state = example.build(
        depth=sizes.resnet_depth, num_classes=sizes.resnet_classes,
        step_mode=mode, seed=seed if init_seed is None else init_seed)
    fields["params_digest"] = before = param_digest(params)
    if mode == "spmd":
        mesh = hvd.mesh()
        batch = NamedSharding(mesh, P("hvd"))
        images = jax.device_put(images, batch)
        labels = jax.device_put(labels, batch)
        devs = {s.device.id for s in images.addressable_shards}
        assert len(devs) == mesh.size, \
            f"batch shards sit on {devs}, mesh has {mesh.size} devices"
        t0 = time.perf_counter()
        step = step.lower(params, stats, opt_state, images, labels).compile()
        fields["aot_compile_seconds"] = round(time.perf_counter() - t0, 3)
        fields["memory_analysis"] = memory_bytes(step)
        text = step.as_text()
        fields["all_reduce_ops"] = (text.count("all-reduce(")
                                    + text.count("all-reduce-start("))
        fields["shard_devices"] = sorted(devs)
        if mesh.size > 1:
            assert fields["all_reduce_ops"] > 0, \
                "no all-reduce in the compiled multi-chip step"
    losses, times = [], []
    for _ in range(sizes.resnet_steps):
        t0 = time.perf_counter()
        params, stats, opt_state, loss = step(params, stats, opt_state,
                                              images, labels)
        losses.append(float(loss))
        times.append(round(time.perf_counter() - t0, 3))
    assert all(np.isfinite(losses)), losses
    assert param_digest(params) != before, "parameters did not change"
    fields.update(mode=mode, losses=losses, step_seconds=times,
                  batch_per_chip=sizes.resnet_batch,
                  image=sizes.resnet_image, depth=sizes.resnet_depth)
    return losses


def assert_losses_agree(a, b, what):
    worst = max(abs(x - y) / max(abs(x), abs(y)) for x, y in zip(a, b))
    assert worst <= RESNET_LOSS_RTOL, (what, a, b, worst)
    return f"{what}: max rel diff {worst:.2e} <= {RESNET_LOSS_RTOL}"


# ------------------------------------------------------------------- llama
def llama_phase(hvd, sizes, seed, fields, rehearse):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.compat import shard_map
    from horovod_tpu.models import llama

    if rehearse:
        # Interpret-mode Pallas is slow: the preset's structure (GQA,
        # window) at toy widths, kernel forced on (auto is TPU-only).
        base = llama.tiny(n_heads=4, n_kv_heads=2, d_model=128, d_ff=256,
                          vocab_size=512, sliding_window=sizes.llama_seq,
                          dtype=jnp.float32)
        flash = True
    else:
        base, flash = llama.mistral_7b(), None      # None: the auto route
    cfg = dataclasses.replace(
        base, n_layers=2, max_seq=sizes.llama_seq, dp_axis=None,
        tp_axis=None, sp_axis=None, use_flash=flash)
    params = llama.init_params(cfg, jax.random.PRNGKey(seed))
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    opt = hvd.DistributedOptimizer(optax.adam(1e-4), op=hvd.Average,
                                   axis_name="hvd")
    mesh = hvd.mesh()
    rng = np.random.RandomState(seed)
    batch = NamedSharding(mesh, P("hvd"))

    def data(T):
        toks = rng.randint(0, cfg.vocab_size, (mesh.size, T + 1))
        return (jax.device_put(toks[:, :-1].astype(np.int32), batch),
                jax.device_put(toks[:, 1:].astype(np.int32), batch))

    # Reference first, while params are not yet donated: loss and one
    # gradient norm, kernel vs XLA attention, where XLA still compiles.
    rtoks, rtgts = data(sizes.llama_ref_seq)

    def loss_and_gnorm(use_flash):
        c = dataclasses.replace(cfg, use_flash=use_flash,
                                max_seq=sizes.llama_ref_seq)

        def f(p):
            loss, g = jax.value_and_grad(llama.loss_fn)(p, rtoks[:1],
                                                        rtgts[:1], c)
            wq = g["layers"][0]["wq"].astype(jnp.float32)
            return loss, jnp.sqrt(jnp.sum(wq * wq))
        compiled = jax.jit(f).lower(params).compile()
        loss, gnorm = compiled(params)
        return float(loss), float(gnorm), "tpu_custom_call" in \
            compiled.as_text()

    k_loss, k_gnorm, k_kernel = loss_and_gnorm(flash)
    x_loss, x_gnorm, x_kernel = loss_and_gnorm(False)
    assert not x_kernel, "use_flash=False still compiled the kernel"
    assert np.isfinite([k_loss, k_gnorm, x_loss, x_gnorm]).all()
    assert close(k_loss, x_loss, LLAMA_LOSS_RTOL), (k_loss, x_loss)
    assert close(k_gnorm, x_gnorm, LLAMA_GNORM_RTOL), (k_gnorm, x_gnorm)

    opt_state = opt.init(params)
    before = float(jnp.sum(jnp.abs(
        params["layers"][0]["wq"].astype(jnp.float32))))
    toks, tgts = data(sizes.llama_seq)
    step = jax.jit(shard_map(
        llama.make_train_step(cfg, opt), mesh=mesh,
        in_specs=(P(), P(), P("hvd"), P("hvd")),
        out_specs=(P(), P(), P()), check_vma=False), donate_argnums=(0, 1))
    t0 = time.perf_counter()
    step = step.lower(params, opt_state, toks, tgts).compile()
    fields["aot_compile_seconds"] = round(time.perf_counter() - t0, 3)
    has_kernel = "tpu_custom_call" in step.as_text()
    if not rehearse:
        assert k_kernel and has_kernel, \
            "the compiled step holds no Pallas kernel (tpu_custom_call): " \
            "attention was routed to the jnp reference"
    losses, times = [], []
    for _ in range(sizes.llama_steps):
        t0 = time.perf_counter()
        params, opt_state, loss = step(params, opt_state, toks, tgts)
        losses.append(float(loss))
        times.append(round(time.perf_counter() - t0, 3))
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], ("loss did not fall on a repeated "
                                    "batch", losses)
    after = float(jnp.sum(jnp.abs(
        params["layers"][0]["wq"].astype(jnp.float32))))
    assert after != before, "parameters did not change"
    fields.update(
        config=f"d_model={cfg.d_model} heads={cfg.n_heads}/{cfg.n_kv_heads}"
               f" head_dim={cfg.head_dim} d_ff={cfg.d_ff} "
               f"window={cfg.sliding_window} layers={cfg.n_layers} "
               f"dtype={jnp.dtype(cfg.dtype).name} optimizer=adam",
        n_params=int(n_params), seq=sizes.llama_seq,
        tpu_custom_call=has_kernel, memory_analysis=memory_bytes(step),
        losses=losses, step_seconds=times,
        compared=f"T={sizes.llama_ref_seq} kernel vs use_flash=False: loss "
                 f"{k_loss:.5f} vs {x_loss:.5f} (rtol {LLAMA_LOSS_RTOL}), "
                 f"|dL/dwq0| {k_gnorm:.5f} vs {x_gnorm:.5f} "
                 f"(rtol {LLAMA_GNORM_RTOL})")


# --------------------------------------------------------- in-process runs
def start(args, virtual_devices=None, distributed=False):
    """Import jax, check the device, ``hvd.init()``; returns (hvd, device).
    A launcher's worker inits first: the process world must form before
    anything asks jax for its devices."""
    import jax
    if args.rehearse:
        jax.config.update("jax_platforms", "cpu")
        if virtual_devices:
            jax.config.update("jax_num_cpu_devices", virtual_devices)
    import horovod_tpu as hvd
    Phase.install_listeners()
    if distributed:
        hvd.init()
    with Phase("device") as f:
        dev = jax.devices()[0]
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(jax.devices())}
        f.update(device, jax=jax.__version__, rehearse=args.rehearse)
        if dev.platform != "tpu" and not args.rehearse:
            sys.exit(f"chip_smoke: no TPU: jax reports {device}")
    hvd.init()
    if args.rehearse:       # hvd.init() places the cache on a chip only
        from horovod_tpu.common import compile_cache
        compile_cache.enable()
    return hvd, device


def cache_report():
    from horovod_tpu.common import compile_cache
    path = compile_cache.cache_dir()
    entries = len(os.listdir(path)) if os.path.isdir(path) else 0
    return {"compile_cache_dir": path, "compile_cache_entries": entries}


def run_one_chip(args):
    sizes = TINY if args.rehearse else REAL
    hvd, device = start(args)
    assert hvd.size() == device["count"] == 1 or args.rehearse, device
    emit(phase="cache", **cache_report())
    with Phase("engine") as f:
        engine_phase(hvd, sizes, args.seed, f, cross_chip=False)
    images, labels = resnet_data(sizes, sizes.resnet_batch * hvd.size(),
                                 args.seed)
    with Phase("resnet50_spmd") as f:
        spmd = resnet_run(hvd, sizes, args.seed, "spmd", images, labels, f)
    with Phase("resnet50_eager") as f:
        eager = resnet_run(hvd, sizes, args.seed, "eager", images, labels, f)
        f["compared"] = assert_losses_agree(spmd, eager, "spmd vs eager")
    with Phase("flash_llama") as f:
        llama_phase(hvd, sizes, args.seed, f, args.rehearse)
    hvd.shutdown()
    emit(phase="cache", **cache_report())
    print(json.dumps({"ok": True, "device": device}), flush=True)


def run_single4(args):
    """Child of ``--chips 4``: one process, four devices."""
    sizes = TINY if args.rehearse else REAL
    hvd, device = start(args, virtual_devices=4)
    assert hvd.size() == device["count"] == 4, device
    with Phase("engine4") as f:
        engine_phase(hvd, sizes, args.seed, f, cross_chip=True)
    images, labels = resnet_data(sizes, sizes.resnet_batch * 4, args.seed)
    with Phase("resnet50_spmd4") as f:
        losses = resnet_run(hvd, sizes, args.seed, "spmd", images, labels, f)
        digest = f["params_digest"]
    hvd.shutdown()
    with open(os.path.join(OUT_DIR, "single4.json"), "w") as fh:
        json.dump({"device": device, "losses": losses,
                   "params_digest": digest}, fh)


def run_rank(args):
    """Worker of ``torovodrun -np 4``: one process, ONE chip, the r-th
    quarter of ``single4``'s global batch through the eager step."""
    import jax
    import numpy as np
    sizes = TINY if args.rehearse else REAL
    hvd, device = start(args, distributed=True)
    r, n, b = hvd.rank(), hvd.size(), sizes.resnet_batch
    local = jax.local_devices()
    assert jax.local_device_count() == 1 and jax.device_count() == 4 \
        and n == 4, (jax.local_device_count(), jax.device_count(), n)
    ids = [int(i) for i in hvd.to_local(hvd.allgather(
        np.asarray([local[0].id], np.int32)))]
    assert sorted(ids) == sorted(d.id for d in jax.devices()) \
        and len(set(ids)) == 4, f"workers do not own distinct chips: {ids}"
    images, labels = resnet_data(sizes, b * n, args.seed)
    with Phase(f"resnet50_eager_rank{r}") as f:
        # Weights start rank-dependent; broadcast_parameters (inside
        # build) must leave every rank with rank 0's, i.e. ``--seed``'s.
        own = resnet_run(hvd, sizes, args.seed, "eager",
                         images[r * b:(r + 1) * b], labels[r * b:(r + 1) * b],
                         f, init_seed=args.seed + r)
        mean = [float(x) for x in hvd.to_local(hvd.allreduce(
            np.asarray(own, np.float32), op=hvd.Average))]
        digests = hvd.allgather_object(f["params_digest"])
        assert len(set(digests)) == 1, \
            f"ranks differ after broadcast_parameters: {digests}"
        f.update(rank=r, launcher_rank=os.environ.get("HOROVOD_RANK"),
                 device_id=local[0].id, device_ids_by_rank=ids,
                 local_device_count=jax.local_device_count(),
                 device_count=jax.device_count(),
                 tpu_visible_chips=os.environ.get("TPU_VISIBLE_CHIPS"),
                 rank_mean_losses=mean)
    hvd.shutdown()
    if r == 0:
        with open(os.path.join(OUT_DIR, "ranks.json"), "w") as fh:
            json.dump({"losses": mean, "params_digest": digests[0],
                       "device_ids_by_rank": ids}, fh)


# ------------------------------------------------ --chips 4 (jax-free parent)
def run_child(cmd, env, timeout):
    """Run to completion; on timeout kill the child's whole process group
    (a launcher's workers included) and fail."""
    proc = subprocess.Popen(cmd, env=env, cwd=REPO, start_new_session=True)
    try:
        rc = proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        os.killpg(proc.pid, 9)
        proc.wait()
        sys.exit(f"chip_smoke: timed out after {timeout}s: {cmd}")
    if rc != 0:
        sys.exit(f"chip_smoke: exit {rc}: {cmd}")


def run_four_chips(args):
    assert "jax" not in sys.modules, "the --chips 4 parent must stay off jax"
    os.makedirs(OUT_DIR, exist_ok=True)
    for name in ("single4.json", "ranks.json"):
        with contextlib.suppress(FileNotFoundError):
            os.unlink(os.path.join(OUT_DIR, name))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                  if p])
    if args.rehearse:
        env["JAX_PLATFORMS"] = "cpu"
    common = ["--seed", str(args.seed)] + (["--rehearse"] * args.rehearse)
    me = [sys.executable, os.path.abspath(__file__)]
    t0 = time.perf_counter()
    run_child(me + ["--worker", "single4"] + common, env, timeout=900)
    t1 = time.perf_counter()
    run_child([sys.executable, "-m", "horovod_tpu.runner.launch", "-np", "4"]
              + me + ["--worker", "rank"] + common, env, timeout=900)
    t2 = time.perf_counter()
    with open(os.path.join(OUT_DIR, "single4.json")) as fh:
        single = json.load(fh)
    with open(os.path.join(OUT_DIR, "ranks.json")) as fh:
        ranks = json.load(fh)
    assert ranks["params_digest"] == single["params_digest"], \
        ("broadcast_parameters did not leave rank 0's weights",
         ranks["params_digest"], single["params_digest"])
    emit(phase="one_process_per_chip_vs_single_process", ok=True,
         single4_seconds=round(t1 - t0, 3),
         torovodrun_seconds=round(t2 - t1, 3),
         single_losses=single["losses"], rank_mean_losses=ranks["losses"],
         device_ids_by_rank=ranks["device_ids_by_rank"],
         compared=assert_losses_agree(
             single["losses"], ranks["losses"],
             "4 devices in one process vs torovodrun -np 4"))
    assert single["device"]["count"] == 4, single["device"]
    print(json.dumps({"ok": True, "device": single["device"]}), flush=True)


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    p.add_argument("--rehearse", action="store_true",
                   help="tiny sizes on the CPU (no chip needed, no result "
                        "claimed for one)")
    p.add_argument("--worker", choices=("single4", "rank"),
                   help="internal: a child of --chips 4")
    args = p.parse_args()
    if args.worker == "single4":
        run_single4(args)
    elif args.worker == "rank":
        run_rank(args)
    elif args.chips == 4:
        run_four_chips(args)
    else:
        run_one_chip(args)


if __name__ == "__main__":
    main()
