"""JAX binding: DistributedOptimizer / DistributedGradientTape /
broadcast_parameters.

Parity targets in the reference (SURVEY.md §2b P2/P4, §3.2/§3.5):

- ``hvd.DistributedOptimizer`` (``horovod/torch/optimizer.py``,
  ``horovod/tensorflow/__init__.py``): wraps an optimizer so gradients are
  averaged across ranks before the update, with ``backward_passes_per_step``
  local aggregation and optional compression.
- ``hvd.DistributedGradientTape`` (``horovod/tensorflow/__init__.py``):
  wraps gradient computation itself.
- ``broadcast_parameters`` / ``broadcast_optimizer_state``
  (``horovod/torch/functions.py``): rank-0 state sync at start.

TPU-first design: the JAX optimizer is an **optax gradient transformation**.
Inside a jitted, shard_map'ped train step the allreduce is an in-graph
``lax.psum`` over the data-parallel mesh axis — XLA fuses and schedules it
over ICI, which is the whole point of the rebuild (SURVEY.md §7 step 3).
Outside any mesh context it degrades to the identity (world of 1), so the
same training script runs unmodified on one chip.
"""

from __future__ import annotations

import itertools
from typing import Any, Callable, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from ..compat import axis_size as compat_axis_size

from .compression import Compression
from .. import trace
from ..ops import collectives as C
from ..ops import eager
from ..common.process_sets import ProcessSet
from ..utils.logging import get_logger

log = get_logger()


def _any_tracer(tree) -> bool:
    return any(isinstance(l, jax.core.Tracer)
               for l in jax.tree_util.tree_leaves(tree))


def _axis_in_scope(axis_name) -> bool:
    """True when `axis_name` is bound by an enclosing shard_map/pmap trace."""
    try:
        compat_axis_size(axis_name)
        return True
    except NameError:
        return False
    except Exception:
        return False


def allreduce_gradients(grads, op: C.ReduceOp = C.ReduceOp.AVERAGE,
                        axis_name: str = C.DEFAULT_AXIS,
                        compression=Compression.none,
                        process_set: Optional[ProcessSet] = None):
    """Tree-allreduce a gradient pytree.

    Two modes, matching how the training step was written:

    - **In-graph** (inside a ``shard_map``/``pmap`` that binds ``axis_name``):
      one fused ``lax.psum`` over all leaves (XLA combines them into a single
      collective — the compiler-native tensor fusion, reference N7).
    - **Eager, per-process** (torovodrun-launched, called outside any mesh
      context): one fused grouped allreduce through the collective engine —
      the reference's hook→background-thread path (SURVEY §3.2).  Where
      every leaf is a ``jax.Array`` on this process's one chip, the reduce
      is elementwise (anything but Adasum) and the compression none or a
      ``wire_mode`` cast, the group travels as **one flat buffer a dtype**:
      packed by one program, one engine item a dtype, and taken apart into
      the tree again by one program (``DistributedOptimizer.update`` skips
      that one: its compiled inner update slices the buffers itself).
      Anything else goes a leaf an engine item, as every group did.  The
      same elements go through the same ``psum`` either way: the results
      are bitwise equal.

    Either way compress → reduce → decompress mirrors the reference's hook
    pipeline.  Calling this from a plain ``jax.jit`` trace in a multi-process
    world is an error (a bare jit binds no mesh axis, so the reduce would
    silently be the identity and replicas would diverge) — compute gradients
    under jit but reduce/update eagerly, or use a ``shard_map`` step.
    """
    return _allreduce_gradients(grads, op, axis_name, compression,
                                process_set, keep_flat=False)


def _allreduce_gradients(grads, op, axis_name, compression, process_set,
                         keep_flat: bool):
    """``allreduce_gradients``; with ``keep_flat`` a group that travelled
    flat comes back as the ``eager.FlatGroup`` it is (for a consumer that
    unpacks inside its own program) and not as the tree."""
    if process_set is not None:
        axis_name = process_set.axis_name
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    if _axis_in_scope(axis_name):
        comp = [compression.compress(g) for g in leaves]
        reduced = C.grouped_allreduce([c[0] for c in comp], op=op,
                                      axis_name=axis_name)
        out = [compression.decompress(r, c[1]) for r, c in zip(reduced, comp)]
        return jax.tree_util.tree_unflatten(treedef, out)

    from ..ops.engine import CollectiveType
    if not eager.per_process_mode():
        return grads  # single-controller SPMD: params/grads already global
    if _any_tracer(leaves):
        raise RuntimeError(
            "allreduce_gradients was traced under jax.jit without a bound "
            f"mesh axis {axis_name!r} in a multi-process world: the reduce "
            "would silently be a no-op and replicas would diverge. Either "
            "compute gradients inside jit but call allreduce_gradients / "
            "DistributedOptimizer.update eagerly (outside jit), or write the "
            "train step with shard_map over the device mesh so the axis is "
            "bound (see models.mnist.make_sharded_train_step).")
    # Eager engine path: fused, device-resident, negotiated across processes.
    # Reverse-registration priority: leaf 0 (the earliest-registered layer,
    # the one the next forward pass touches first) drains first even though
    # backprop produces its gradient last — ByteScheduler-style priority
    # scheduling through the engine's priority queue.  Pytree flatten order
    # is identical on every rank, so the stamps agree.
    prios = [len(leaves) - i for i in range(len(leaves))]
    # Cast-style compression (``wire_mode``) rides INSIDE the fused program
    # (cast-down before the psum, cast-up after): results come back in the
    # gradients' own dtype with half the wire bytes and no extra launches.
    # Any other compressor wraps the exchange on this thread.
    wire = getattr(compression, "wire_mode", None)
    comp = None
    # One flat buffer a dtype where the group's elements can be reduced in
    # any grouping (Adasum reduces a tensor at a time; a compressor that
    # is not a cast compresses one) and every leaf is on this process's
    # chip already.
    flat = (op != C.ReduceOp.ADASUM
            and (wire is not None or compression is Compression.none)
            and eager._all_held(leaves, process_set))

    def stage():
        nonlocal comp
        if flat:
            return leaves
        arrs = [jnp.asarray(g) for g in leaves]
        if wire is None:
            comp = [compression.compress(a) for a in arrs]
            arrs = [c[0] for c in comp]
        return arrs

    gid, arrs, handles = _stage_submit(
        stage, "allreduce_gradients", "grouped_allreduce",
        CollectiveType.ALLREDUCE, process_set, prios, pack=flat,
        reduce_op=op, compression=eager._wire_mode(wire))
    reduced = _wait(gid, handles)
    with trace.span("hvd/update/unpack") as sp:
        # The fused program returns each member replicated, in its own
        # shape and dtype: the buffer this chip holds is the result.
        local = [eager._local_shard(r) for r in reduced]
        via_host = {i for i, shard in enumerate(local) if shard is None}
        for i in via_host:
            local[i] = eager.local_array(reduced[i])
        host = len(via_host)
        if flat:
            layout = eager._flat_layout(arrs)
            host = sum(k in via_host for k, _, _ in layout)     # in leaves
            out = eager.FlatGroup(local, layout, treedef)
            if not keep_flat:
                out = eager._unpack_group(out)
        else:
            local = [_as_leaf(r, a.shape, a.dtype)
                     for r, a in zip(local, arrs)]
            if comp is not None:
                local = [compression.decompress(r, c[1])
                         for r, c in zip(local, comp)]
            out = jax.tree_util.tree_unflatten(treedef, local)
        if sp is not None:
            sp.set(n=len(arrs), bytes=_nbytes(local), host=host)
    return out


# ---- program spans of the eager update (docs/timeline.md).  Each phase of
# one update on the calling thread is a span of ``horovod_tpu.trace``; with
# tracing disarmed a site is one ``is None`` check.

_update_steps = itertools.count()   # host-side count of traced updates


def _update_span(grads=None, axis_name=None):
    """``hvd/update`` around one eager, per-process update; the shared
    no-op while tracing is disarmed and — ``grads`` given — wherever the
    update is not on that branch (traced under ``jit`` / ``shard_map``,
    single-controller), so a step program under trace enters no span.
    ``group`` is the first group the update submits."""
    if trace.installed() is None:
        return trace.OFF
    if grads is not None and (
            _axis_in_scope(axis_name) or not eager.per_process_mode()
            or _any_tracer(grads)):
        return trace.OFF
    return trace.span("hvd/update", step=next(_update_steps),
                      group=eager._group_counter.upcoming)


def _inner_span(up):
    """``hvd/update/inner`` (the wrapped optimizer's update), under an
    open ``hvd/update`` only."""
    return trace.OFF if up is None else trace.span("hvd/update/inner")


# What JAX raises when a function being traced needs a value: a
# transformation that branches on one cannot be compiled.
# (``TracerBoolConversionError`` is a ``ConcretizationTypeError``.)
_NEEDS_A_VALUE = (jax.errors.ConcretizationTypeError,
                  jax.errors.TracerArrayConversionError,
                  jax.errors.TracerIntegerConversionError)


class _InnerUpdate:
    """The wrapped optimizer's ``update`` for one ``DistributedOptimizer``:
    one ``jax.jit`` of it, built at wrap time (jit's cache keys on tree
    structure, shapes and dtypes), entered whenever gradients, state and
    parameters are all concrete, so that an eager step runs the whole
    transformation as one program instead of three dispatches a leaf.
    Under a trace (``jit`` / ``shard_map`` step programs) the call is
    ``optimizer.update`` itself and the step's HLO is what it was.  No
    donation: callers may hold the old state.  A compiled update is not
    bitwise the op-by-op one (XLA contracts ``g + mu * t``), so every
    eager path goes through here and they agree with each other
    (docs/performance.md "The eager path").

    Gradients that travelled flat (``eager.FlatGroup``: one buffer a
    dtype, layout and tree structure static) are sliced into the tree
    inside the program, where XLA fuses the slices into the optimizer's
    elementwise passes: still one trace a signature, and no result buffer
    a leaf in between.

    A transformation that cannot be traced (it branches on a value) takes
    the direct call from its first concretization error on, flat gradients
    unpacked for it by ``eager._unpack_group``'s one program; any other
    exception propagates."""

    def __init__(self, optimizer: optax.GradientTransformation):
        self._direct = optimizer.update

        def hvd_inner_update(grads, state, params):
            trace.inner_update["traces"] += 1       # Python: once a trace
            if isinstance(grads, eager.FlatGroup):
                grads = grads.tree()
            return optimizer.update(grads, state, params)

        self._compiled: Optional[Callable] = jax.jit(hvd_inner_update)

    def __call__(self, grads, state, params, span=trace.OFF):
        """``(updates, new inner state)``, under ``span`` (``hvd/update/
        inner`` or the no-op), which gets ``compiled=1|0``."""
        with span as sp:
            compiled = self._compiled is not None \
                and not _any_tracer((grads, state, params))
            if compiled:
                try:
                    out = self._compiled(grads, state, params)
                    trace.inner_update["compiled"] += 1
                except _NEEDS_A_VALUE as e:
                    log.warning(
                        "DistributedOptimizer: the wrapped optimizer's "
                        "update cannot be compiled (%s); this wrapper runs "
                        "it op by op from here on", type(e).__name__)
                    self._compiled, compiled = None, False
            if not compiled:
                if isinstance(grads, eager.FlatGroup):
                    grads = eager._unpack_group(grads)
                out = self._direct(grads, state, params)
            if sp is not None:
                sp.set(compiled=int(compiled))
        return out


def _nbytes(arrs) -> int:
    return sum(int(a.nbytes) for a in arrs)


def _as_leaf(a, shape, dtype, size: Optional[int] = None):
    """Device array ``a`` as a leaf: its first ``size`` elements (a padded
    flat gather), in ``shape`` and ``dtype``.  Each step runs only where
    it changes something: one that does not still costs a call a leaf."""
    if size is not None and a.size != size:
        a = a.reshape(-1)[:size]
    if a.shape != tuple(shape):
        a = a.reshape(shape)
    if a.dtype != jnp.dtype(dtype):
        a = a.astype(dtype)
    return a


def _stage_submit(make, name: str, prefix: str, ctype, process_set,
                  priorities, kick: bool = True, pack: bool = False,
                  **extra):
    """One group into the engine: ``hvd/update/stage`` (``make()`` readies
    the tensors — compress, ravel, pad — and ``eager._stage_group`` puts
    them into the engine's stacked layout, by one program over the group
    where they are on this process's chip: the span's ``compiled`` counts
    those), then ``hvd/update/submit`` (``enqueue_group`` + ``kick``).
    With ``pack`` (the caller has seen that the group may travel flat)
    ``eager._stage_packed`` makes one engine item a dtype of it, under the
    group's highest priority.  The span says which: ``packed`` the tensors
    that went in inside a flat buffer (``n`` or 0), ``buffers`` the engine
    items the group became (one a dtype, or ``n``).
    Returns ``(group id, the tensors, handles)``: a handle an item."""
    with trace.span("hvd/update/stage") as sp:
        tensors = make()
        if pack:
            gid, items = eager._stage_packed(
                tensors, name, prefix, ctype, process_set, max(priorities),
                **extra)
            compiled = len(tensors)
        else:
            gid, items, compiled = eager._stage_group(
                tensors, name, prefix, ctype, process_set, priorities,
                **extra)
        if sp is not None:
            sp.set(n=len(tensors), bytes=_nbytes(tensors), compiled=compiled,
                   packed=compiled if pack else 0, buffers=len(items))
    eng = eager._engine()
    with trace.span("hvd/update/submit", group=gid):
        handles = eng.enqueue_group(items)
        if kick:
            eng.kick()
    return gid, tensors, handles


def _wait(gid: int, handles) -> List:
    """``hvd/update/wait``: blocked on the engine, first to last handle
    of one group."""
    with trace.span("hvd/update/wait", group=gid):
        return [eager.synchronize(h) for h in handles]


def _wait_by_leaf(gid: int, handles: dict) -> dict:
    """``_wait`` for ``{leaf index: handle}``: ``{leaf index: result}``."""
    return dict(zip(handles, _wait(gid, handles.values())))


class _DistOptState(NamedTuple):
    inner_state: Any
    acc: Any                 # gradient accumulator (backward_passes_per_step)
    counter: jnp.ndarray


# --------------------------------------------------------------------------
# ZeRO-sharded data plane (ISSUE 15): DistributedOptimizer(sharded=True)
# --------------------------------------------------------------------------

class _ShardPlan(NamedTuple):
    """Static sharding plan, fixed at init: a pure function of (leaf
    shapes/dtypes, world, the pipeline-chunk knob), so every rank derives
    the identical bucket structure — bucket membership shapes the wire
    names and digests, which negotiation checks for consistency."""
    world: int
    rank: int
    shapes: Tuple[Tuple[int, ...], ...]     # logical per-leaf shapes
    dtypes: Tuple[str, ...]
    sizes: Tuple[int, ...]                  # logical element counts
    pads: Tuple[int, ...]                   # pad+slice convention pads
    pers: Tuple[int, ...]                   # shard length per leaf
    buckets: Tuple[Tuple[int, ...], ...]    # leaf indices per bucket


class ShardedOptimizerState:
    """Eager ZeRO state: one inner optax state per bucket, every array
    leaf holding only this rank's 1/world shard (HBM/host cost scales
    1/world).  Deliberately NOT a pytree — it lives between eager update
    calls only; the elastic integration goes through
    :meth:`hvd_sharded_saveable` / :func:`load_sharded_saveable`."""

    def __init__(self, inner_states: List, plan: _ShardPlan,
                 process_set: Optional[ProcessSet] = None):
        self.inner_states = list(inner_states)
        self.plan = plan
        # The set the plan's world/rank are relative to: the gather in
        # hvd_sharded_saveable must negotiate over exactly these ranks
        # (a subset-set state gathered over the global world would hang
        # the ranks outside the subset and stack in the wrong order).
        self.process_set = process_set

    def opt_state_bytes(self) -> int:
        """Bytes of optimizer state resident on THIS rank (the 1/N claim
        ``tests/data/worker_sharded.py`` asserts)."""
        total = 0
        for s in self.inner_states:
            for leaf in jax.tree_util.tree_leaves(s):
                if hasattr(leaf, "nbytes"):
                    total += int(leaf.nbytes)
        return total

    def hvd_sharded_saveable(self, process_set: Optional[ProcessSet] = None):
        """Rank-invariant host representation for elastic commits: every
        sharded array leaf is allgathered to its full padded flat form, so
        all ranks serialize the identical blob (the state plane's shard
        digests require it) and a (re-)joining rank re-slices exactly its
        own 1/N with :func:`load_sharded_saveable`.  ``process_set=None``
        gathers over the set the state was initialized with."""
        if process_set is None:
            process_set = self.process_set
        if self.plan.world > 1 and not eager.per_process_mode():
            # Emitting this rank's bare shards stamped world=N would be a
            # valid-LOOKING saveable that load_sharded_saveable silently
            # re-slices into 1/N of 1/N — corrupt state.  Fail loudly: a
            # multi-process sharded state can only gather while the
            # engine is live.
            raise RuntimeError(
                "cannot save a DistributedOptimizer(sharded=True) state "
                f"sharded over {self.plan.world} ranks without the live "
                "collective engine (commit before shutdown, not after)")
        gathered = []
        for b, st in enumerate(self.inner_states):
            leaves, treedef = jax.tree_util.tree_flatten(st)
            arrs = [(i, l) for i, l in enumerate(leaves)
                    if getattr(l, "ndim", 0) >= 1]
            if arrs and self.plan.world > 1:
                full = eager.grouped_allgather(
                    [jnp.asarray(l) for _, l in arrs],
                    name=f"sharded_state_gather.b{b}",
                    process_set=process_set, sharded=True)
                for (i, _), f in zip(arrs, full):
                    leaves[i] = np.asarray(eager.to_local(f))
            out = [np.asarray(jax.device_get(l)) for l in leaves]
            gathered.append(jax.tree_util.tree_unflatten(treedef, out))
        return {"__hvd_sharded_opt__": 1, "world": self.plan.world,
                "plan": self.plan._replace(rank=-1)._asdict(),
                "inner_states": gathered}


class FullShardedState(ShardedOptimizerState):
    """Eager ZeRO-3 (FSDP) state: like :class:`ShardedOptimizerState`,
    plus the resident **parameter** shards — ``param_shards[b]`` is the
    tuple of flat 1/world leaves of bucket ``b``, THE authoritative
    parameters (no replicated copy exists between steps).  The training
    loop rematerializes full parameters per step with
    :meth:`gather_params`, whose per-bucket allgathers ride the engine's
    PREFETCH lane ``HOROVOD_PREFETCH_DEPTH`` buckets ahead, so bucket
    k+1's gather overlaps bucket k's consumption.  With FSDP the
    resident shard IS the PR 14 checkpoint shard — commit/restore move
    1/N bytes by construction."""

    def __init__(self, inner_states: List, plan: _ShardPlan,
                 process_set: Optional[ProcessSet] = None,
                 param_shards: Optional[List] = None, treedef=None):
        super().__init__(inner_states, plan, process_set)
        self.param_shards = list(param_shards or [])
        self.treedef = treedef          # params pytree structure; re-stamped
                                        # from grads after a shard-native load

    def params_bytes(self) -> int:
        """Bytes of parameters resident on THIS rank (≈ full/world)."""
        return sum(int(s.nbytes) for shards in self.param_shards
                   for s in shards if hasattr(s, "nbytes"))

    def resident_bytes(self) -> int:
        """Parameters + optimizer state resident on THIS rank — the ≈ 1/N
        claim ``tests/data/worker_fsdp.py`` asserts (small-leaf padding
        slack allowed)."""
        return self.params_bytes() + self.opt_state_bytes()

    def gather_params(self, depth: Optional[int] = None):
        """Rematerialize the full parameter pytree — the FSDP prefetch
        pipeline.  Buckets ``0..depth-1`` dispatch their allgathers up
        front; then, for each bucket k in order, bucket ``k+depth``'s
        gather is dispatched BEFORE bucket k is synchronized — overlap by
        construction, no timing races.  Each gather group is marked
        ``prefetch=True`` (PREFETCH backlog lane: after FAST, before
        FUSED, budget-exempt) and ``sharded="full"`` (own digest token).
        Gathered buffers belong to the caller and are dropped after the
        step — peak HBM stays shard + the depth-bounded window."""
        plan = self.plan
        nb = len(plan.buckets)
        nl = len(plan.shapes)
        if depth is None:
            depth = _prefetch_depth()
        depth = max(1, int(depth))
        eng = eager._engine()
        handles: List[Optional[dict]] = [None] * nb

        def dispatch(b: int):
            idxs = plan.buckets[b]
            live = [i for i in idxs if plan.pers[i] > 0]
            shards = [jnp.asarray(s) for s, i in
                      zip(self.param_shards[b], idxs) if plan.pers[i] > 0]
            hs = eager.grouped_allgather_async(
                shards, name=f"fsdp_prefetch.b{b}",
                process_set=self.process_set,
                priorities=[nl - i for i in live],
                sharded="full", prefetch=True) if live else []
            handles[b] = dict(zip(live, hs))
            if b > 0:
                # Dispatched while an earlier bucket's gather is still
                # outstanding — the overlap evidence the acceptance
                # criterion asks for, counted deterministically.
                eng.prefetch_overlapped = \
                    getattr(eng, "prefetch_overlapped", 0) + 1

        for b in range(min(depth, nb)):
            dispatch(b)
        if nb:
            eng.kick()
        out: List[Any] = [None] * nl
        for b in range(nb):
            if b + depth < nb:
                dispatch(b + depth)     # before bucket b synchronizes
                eng.kick()
            for i, h in handles[b].items():
                out[i] = _as_leaf(
                    eager.local_array(eager.synchronize(h)),
                    plan.shapes[i], plan.dtypes[i], plan.sizes[i])
        for i in range(nl):
            if out[i] is None:
                out[i] = jnp.zeros(plan.shapes[i], plan.dtypes[i])
        if self.treedef is None:
            return out
        return jax.tree_util.tree_unflatten(self.treedef, out)

    def hvd_sharded_saveable(self, process_set: Optional[ProcessSet] = None):
        """Rank-invariant saveable: the PR 15 form plus the gathered
        parameter shards, under the ``__hvd_full_sharded__`` marker."""
        base = super().hvd_sharded_saveable(process_set)
        if process_set is None:
            process_set = self.process_set
        gathered = []
        for b, shards in enumerate(self.param_shards):
            idxs = self.plan.buckets[b]
            live = [(j, s) for j, s in enumerate(shards)
                    if self.plan.pers[idxs[j]] > 0]
            outs = [np.asarray(jax.device_get(s)) for s in shards]
            if live and self.plan.world > 1:
                full = eager.grouped_allgather(
                    [jnp.asarray(s) for _, s in live],
                    name=f"fsdp_param_gather.b{b}",
                    process_set=process_set, sharded="full")
                for (j, _), f in zip(live, full):
                    outs[j] = np.asarray(eager.to_local(f))
            gathered.append(outs)
        base["__hvd_full_sharded__"] = 1
        base["param_shards"] = gathered
        return base


def _prefetch_depth() -> int:
    """The HOROVOD_PREFETCH_DEPTH knob (default 2): how many buckets of
    gathered parameters may be in flight ahead of consumption."""
    from ..common import basics
    cfg = basics._get_state().config
    if cfg is None:
        return 2
    return max(1, int(getattr(cfg, "prefetch_depth", 2) or 2))


def is_sharded_saveable(value) -> bool:
    """True for the marker dict :meth:`hvd_sharded_saveable` produces."""
    return isinstance(value, dict) and value.get("__hvd_sharded_opt__") == 1


def load_sharded_saveable(saved, rank: int, world: int):
    """Rebuild THIS rank's :class:`ShardedOptimizerState` from a recovered
    rank-invariant saveable: each gathered flat leaf ``[world*per]`` is
    re-sliced to the joining rank's own 1/N (``[rank*per, (rank+1)*per)``)
    — the shard-native restore the state plane's peer fetch feeds.
    Returns ``None`` when the committed world size differs (a resized
    fleet re-inits optimizer state instead of guessing a re-shard)."""
    if not is_sharded_saveable(saved) or int(saved["world"]) != int(world) \
            or world < 1:
        return None
    plan = _ShardPlan(**dict(saved["plan"], rank=int(rank)))

    def reslice(leaf):
        arr = np.asarray(leaf)
        if arr.ndim < 1 or arr.size % world:
            return jnp.asarray(arr) if arr.ndim else arr
        per = arr.size // world
        return jnp.asarray(arr.reshape(-1)[rank * per:(rank + 1) * per])

    inner_states = [jax.tree_util.tree_map(reslice, st)
                    for st in saved["inner_states"]]
    if saved.get("__hvd_full_sharded__") == 1:
        # FSDP saveable (ISSUE 18): the gathered parameter shards reslice
        # exactly like the optimizer-state leaves (padded flats are always
        # world-divisible).  The treedef is re-stamped from the first
        # update's gradient tree; gather_params before then returns the
        # flat leaf list.
        param_shards = [tuple(reslice(s) for s in shards)
                        for shards in saved["param_shards"]]
        return FullShardedState(inner_states, plan,
                                param_shards=param_shards)
    return ShardedOptimizerState(inner_states, plan)


def _make_shard_plan(leaves, world: int, rank: int,
                     chunk_bytes: int) -> _ShardPlan:
    from ..parallel.zero import shard_info
    shapes, dtypes, sizes, pads, pers, isizes = [], [], [], [], [], []
    for l in leaves:
        shape = tuple(getattr(l, "shape", ()))
        n = int(np.prod(shape)) if shape else 1
        pad, per = shard_info(n, world)
        dt = jnp.asarray(l).dtype
        shapes.append(shape)
        dtypes.append(str(dt))
        isizes.append(int(dt.itemsize))
        sizes.append(n)
        pads.append(pad)
        pers.append(per)
    # Bucket assignment (HOROVOD_PIPELINE_CHUNK): greedy packing in
    # registration order up to ~chunk bytes of padded payload per bucket,
    # so the scatter of bucket b+1 overlaps the shard update + gather of
    # bucket b.  Knob 0/off = one bucket (the whole tree updates at once;
    # cross-leaf inner transforms then see the full shard tree).
    buckets: List[Tuple[int, ...]] = []
    if chunk_bytes and chunk_bytes > 0:
        cur: List[int] = []
        cur_bytes = 0
        for i in range(len(leaves)):
            b = (sizes[i] + pads[i]) * isizes[i]
            if cur and cur_bytes + b > chunk_bytes:
                buckets.append(tuple(cur))
                cur, cur_bytes = [], 0
            cur.append(i)
            cur_bytes += b
        if cur:
            buckets.append(tuple(cur))
    else:
        buckets = [tuple(range(len(leaves)))] if leaves else []
    return _ShardPlan(world=world, rank=rank, shapes=tuple(shapes),
                      dtypes=tuple(dtypes), sizes=tuple(sizes),
                      pads=tuple(pads), pers=tuple(pers),
                      buckets=tuple(buckets))


def _sharded_world_rank(process_set: Optional[ProcessSet]):
    """(world, this process's rank within the set) for the eager sharded
    path.  One device per process is required: a multi-device process
    would own several shards, and the shard-local inner update below is
    written for exactly one."""
    from ..common import basics
    st = basics._get_state()
    ps = st.process_set_table.get(
        0 if process_set is None or process_set.process_set_id is None
        else process_set.process_set_id)
    mine = [i for i, d in enumerate(ps.mesh.devices.flat)
            if d.process_index == jax.process_index()]
    if len(mine) != 1:
        raise NotImplementedError(
            f"DistributedOptimizer(sharded=True) eager path needs exactly "
            f"one device per process; this process drives {len(mine)}. "
            f"Use the in-graph path (shard_map + parallel.zero."
            f"sharded_optimizer) for multi-device processes.")
    return ps.size(), mine[0]


def _device_shard(x, pad: int, per: int, rank: int):
    flat = jnp.ravel(x)
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat[rank * per:(rank + 1) * per]


def _sharded_eager_init(optimizer, params, process_set, chunk_bytes):
    from ..parallel.zero import shard_slice_host
    leaves, _treedef = jax.tree_util.tree_flatten(params)
    world, rank = _sharded_world_rank(process_set)
    plan = _make_shard_plan(leaves, world, rank, chunk_bytes)
    inner_states = []
    for idxs in plan.buckets:
        shard_params = tuple(
            jnp.asarray(shard_slice_host(jax.device_get(leaves[i]),
                                         rank, world))
            for i in idxs)
        inner_states.append(optimizer.init(shard_params))
    return ShardedOptimizerState(inner_states, plan, process_set)


def _sharded_eager_update(inner: _InnerUpdate, grads,
                          state: ShardedOptimizerState, params,
                          op: C.ReduceOp,
                          process_set: Optional[ProcessSet]):
    """The ZeRO pipeline through the engine: per-bucket reduce-scatter of
    fused gradients (each rank receives its 1/N shard — half the wire
    bytes of an allreduce of the same payload), the inner optimizer
    update applied on the shard only, then an allgather of the updated
    parameter deltas.  Every bucket's scatter is in flight before the
    first bucket's update runs, so with HOROVOD_PIPELINE_CHUNK set the
    scatter → update → gather stages overlap across buckets (the engine's
    in-flight window + priority backlog do the interleaving)."""
    from ..ops.engine import CollectiveType
    plan = state.plan
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    if tuple(tuple(getattr(l, "shape", ())) for l in leaves) != plan.shapes:
        raise ValueError(
            "gradient tree shapes changed since DistributedOptimizer"
            "(sharded=True) state was initialized; re-init the optimizer "
            "state for the new parameter tree")
    if op not in (C.ReduceOp.AVERAGE, C.ReduceOp.SUM):
        raise ValueError(f"sharded=True supports SUM/AVERAGE, not {op!r}")
    rank, world = plan.rank, plan.world
    nl = len(leaves)

    # Phase 1: every bucket's reduce-scatter goes out BEFORE any update
    # runs — the engine fuses each bucket atomically and the in-flight
    # window keeps later buckets' scatters on the wire while earlier
    # buckets update.  Reverse-registration priorities: the first
    # parameters the next forward pass needs lead each cycle.
    rs = [_stage_scatter(leaves, plan, b, f"sharded_rs.b{b}", op,
                         process_set, True)
          for b in range(len(plan.buckets))]
    eager._engine().kick()

    p_leaves = jax.tree_util.tree_flatten(params)[0] \
        if params is not None else None
    ag: List = []
    new_inner: List = []
    for b, idxs in enumerate(plan.buckets):
        g_shards = _wait_shards(plan, idxs, *rs[b])
        p_shards = None
        if p_leaves is not None:
            p_shards = tuple(
                _device_shard(jnp.asarray(p_leaves[i]), plan.pads[i],
                              plan.pers[i], rank) for i in idxs)
        updates_b, inner_b = inner(g_shards, state.inner_states[b],
                                   p_shards, trace.span("hvd/update/inner"))
        new_inner.append(inner_b)
        # Phase 3 (overlapped): this bucket's updated deltas start their
        # allgather while later buckets are still scattering/updating.
        live = [i for i in idxs if plan.pers[i] > 0]
        gid, handles = -1, []
        if live:
            gid, _, handles = _stage_submit(
                lambda: [jnp.asarray(u) for u, i in zip(updates_b, idxs)
                         if i in live],
                f"sharded_ag.b{b}", "grouped_allgather",
                CollectiveType.ALLGATHER, process_set,
                [nl - i for i in live], sharded=True, prefetch=False)
        ag.append((gid, dict(zip(live, handles))))

    out: List[Any] = [None] * nl
    for b, idxs in enumerate(plan.buckets):
        full = _wait_by_leaf(*ag[b])
        with trace.span("hvd/update/unpack"):
            for i in idxs:
                if plan.pers[i] == 0:
                    out[i] = jnp.zeros(plan.shapes[i], plan.dtypes[i])
                    continue
                out[i] = _as_leaf(eager.local_array(full[i]),
                                  plan.shapes[i], plan.dtypes[i],
                                  plan.sizes[i])
    updates = jax.tree_util.tree_unflatten(treedef, out)
    return updates, ShardedOptimizerState(new_inner, plan, process_set)


def _stage_scatter(leaves, plan: _ShardPlan, b: int, name: str, op,
                   process_set, sharded):
    """Stage and submit bucket ``b``'s gradient reduce-scatter (no kick:
    the caller wakes the engine once every bucket is queued).  Returns
    ``(group id, {leaf index: handle})``."""
    from ..ops.engine import CollectiveType
    live = [i for i in plan.buckets[b] if plan.pers[i] > 0]  # empty: skip
    if not live:
        return -1, {}
    def padded():
        out = []
        for i in live:
            flat = jnp.ravel(jnp.asarray(leaves[i]))
            if plan.pads[i]:
                flat = jnp.pad(flat, (0, plan.pads[i]))
            out.append(flat)
        return out

    gid, _, handles = _stage_submit(
        padded, name, "grouped_reducescatter", CollectiveType.REDUCESCATTER,
        process_set, [len(leaves) - i for i in live], kick=False,
        reduce_op=op, sharded=sharded)
    return gid, dict(zip(live, handles))


def _wait_shards(plan: _ShardPlan, idxs, gid: int, handles: dict):
    """Bucket ``idxs``' reduced gradient shards, flat and in the plan's
    dtypes, once its reduce-scatter (``handles`` by leaf) has settled."""
    res = _wait_by_leaf(gid, handles)
    with trace.span("hvd/update/unpack"):
        return tuple(
            _as_leaf(eager.local_array(res[i]), (plan.pers[i],),
                     plan.dtypes[i]) if plan.pers[i] > 0
            else jnp.zeros((0,), plan.dtypes[i])
            for i in idxs)


def _full_sharded_eager_init(optimizer, params, process_set, chunk_bytes):
    """FSDP init: slice parameters into this rank's per-bucket shards and
    init the inner optimizer ON the shards.  The full (replicated)
    ``params`` tree the caller passed may be dropped afterwards — the
    shards are the resident truth from here on."""
    from ..parallel.zero import shard_slice_host
    leaves, treedef = jax.tree_util.tree_flatten(params)
    world, rank = _sharded_world_rank(process_set)
    plan = _make_shard_plan(leaves, world, rank, chunk_bytes)
    inner_states, param_shards = [], []
    for idxs in plan.buckets:
        shards = tuple(
            jnp.asarray(shard_slice_host(jax.device_get(leaves[i]),
                                         rank, world))
            for i in idxs)
        inner_states.append(optimizer.init(shards))
        param_shards.append(shards)
    return FullShardedState(inner_states, plan, process_set,
                            param_shards, treedef)


def _full_sharded_eager_update(inner: _InnerUpdate, grads,
                               state: FullShardedState,
                               op: C.ReduceOp,
                               process_set: Optional[ProcessSet]):
    """The FSDP backward half: per-bucket **reduce-scatter straight into
    the owning 1/N shard** (no replicated gradient ever exists — the
    engine's scatter output IS the shard), shard-local inner update with
    the RESIDENT parameter shards, and the shards advance in place.

    Returns ``(None, new_state)``: there is no replicated update tree to
    apply because there are no replicated parameters — the forward half
    (:meth:`FullShardedState.gather_params`) rematerializes them next
    step through the prefetch lane.  Wire per step is therefore
    RS(grads) + AG(params) — byte-equal to the PR 15 sharded path's
    RS + delta-AG."""
    plan = state.plan
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    if tuple(tuple(getattr(l, "shape", ())) for l in leaves) != plan.shapes:
        raise ValueError(
            'gradient tree shapes changed since DistributedOptimizer'
            '(sharded="full") state was initialized; re-init the optimizer '
            'state for the new parameter tree')
    if op not in (C.ReduceOp.AVERAGE, C.ReduceOp.SUM):
        raise ValueError(f'sharded="full" supports SUM/AVERAGE, not {op!r}')

    # Phase 1: every bucket's reduce-scatter goes out before any update
    # runs (same overlap structure as the PR 15 pipeline), stamped with
    # reverse-registration priorities and the "full" digest token.
    rs = [_stage_scatter(leaves, plan, b, f"fsdp_rs.b{b}", op, process_set,
                         "full")
          for b in range(len(plan.buckets))]
    eager._engine().kick()

    # Phase 2: shard-local update against the resident shards; the shards
    # advance here and nothing is gathered — next step's gather_params
    # does that through the prefetch lane.
    new_inner: List = []
    new_shards: List = []
    for b, idxs in enumerate(plan.buckets):
        g_shards = _wait_shards(plan, idxs, *rs[b])
        p_shards = state.param_shards[b]
        updates_b, inner_b = inner(g_shards, state.inner_states[b],
                                   p_shards, trace.span("hvd/update/inner"))
        # Applied outside the compiled update, as the replicated path's
        # caller applies it: ``updates`` is rounded before it is added.
        shards_b = tuple(optax.apply_updates(p_shards, updates_b))
        new_inner.append(inner_b)
        new_shards.append(shards_b)
    td = state.treedef if state.treedef is not None else treedef
    return None, FullShardedState(new_inner, plan, process_set,
                                  new_shards, td)


def _make_sharded(optimizer: optax.GradientTransformation,
                  op: C.ReduceOp, axis_name: str,
                  process_set: Optional[ProcessSet],
                  full: bool = False
                  ) -> optax.GradientTransformation:
    """The three sharded modes behind ``DistributedOptimizer(sharded=
    True)`` — and, with ``full=True``, behind ``sharded="full"`` —
    dispatched like ``allreduce_gradients`` dispatches: on whether
    ``axis_name`` is bound (in-graph shard_map), the process is one rank
    of a torovodrun world (eager engine pipeline), or neither
    (single-controller degrade to the plain optimizer).  The state type
    records which mode AND which stage initialized it, so init and
    update can never silently mix modes."""
    from ..parallel import zero
    inner = _InnerUpdate(optimizer)

    def _chunk_bytes() -> int:
        from ..common import basics
        st = basics._get_state()
        if st.engine is not None:
            return int(st.engine.pipeline_chunk_bytes)
        return int(st.config.pipeline_chunk_bytes) if st.config else 0

    def init_fn(params):
        if _axis_in_scope(axis_name):
            wrap = zero.full_sharded_optimizer if full \
                else zero.sharded_optimizer
            return wrap(optimizer, axis_name=axis_name,
                        average=op == C.ReduceOp.AVERAGE).init(params)
        if eager.per_process_mode():
            if full:
                return _full_sharded_eager_init(optimizer, params,
                                                process_set, _chunk_bytes())
            return _sharded_eager_init(optimizer, params, process_set,
                                       _chunk_bytes())
        return optimizer.init(params)      # world of one: nothing to shard

    def update_fn(grads, state, params=None):
        if isinstance(state, zero._FullZeroState):
            return zero.full_sharded_optimizer(
                optimizer, axis_name=axis_name,
                average=op == C.ReduceOp.AVERAGE).update(grads, state,
                                                         params)
        if isinstance(state, zero._ZeroState):
            return zero.sharded_optimizer(
                optimizer, axis_name=axis_name,
                average=op == C.ReduceOp.AVERAGE).update(grads, state,
                                                         params)
        if isinstance(state, FullShardedState):
            with _update_span():
                return _full_sharded_eager_update(inner, grads, state,
                                                  op, process_set)
        if isinstance(state, ShardedOptimizerState):
            with _update_span():
                return _sharded_eager_update(inner, grads, state, params,
                                             op, process_set)
        if _axis_in_scope(axis_name) and compat_axis_size(axis_name) > 1:
            # Mixed modes: a plain state initialized OUTSIDE the mesh axis
            # updating INSIDE shard_map.  The plain fallback below would
            # apply raw per-shard gradients with no reduction — silent
            # replica divergence — so fail loudly instead (the replicated
            # path reduces at update time and doesn't have this trap).
            raise RuntimeError(
                "DistributedOptimizer(sharded=True): opt.init(...) ran "
                "outside the mesh axis but opt.update(...) is running "
                "inside shard_map over it.  Initialize inside the same "
                "shard_map context (or build the state with "
                "parallel.zero.init_sharded_state and pass its specs) so "
                "the state is the sharded 1/world layout")
        return inner(grads, state, params)

    return optax.GradientTransformation(init_fn, update_fn)


def DistributedOptimizer(optimizer: optax.GradientTransformation,
                         named_parameters=None,
                         compression=Compression.none,
                         op: C.ReduceOp = C.ReduceOp.AVERAGE,
                         backward_passes_per_step: int = 1,
                         axis_name: str = C.DEFAULT_AXIS,
                         process_set: Optional[ProcessSet] = None,
                         check=False,
                         sharded=None,
                         ) -> optax.GradientTransformation:
    """Wrap an optax optimizer with cross-rank gradient averaging.

    Usage (inside a shard_map/pjit train step over the ``hvd`` axis):

        opt = hvd.DistributedOptimizer(optax.adam(1e-3))
        updates, opt_state = opt.update(grads, opt_state, params)

    ``backward_passes_per_step > 1`` reproduces the reference's gradient
    aggregation (``horovod/tensorflow/gradient_aggregation.py``): gradients
    accumulate locally and the (single) allreduce happens every k-th step.
    ``named_parameters`` is accepted for API parity and unused (pytrees are
    self-describing).

    ``check=True`` lints the calling script for deadlock-prone collective
    patterns at wrap time (``check="strict"`` raises on errors) — see
    ``horovod_tpu.analysis`` and docs/analysis.md.

    ``sharded=True`` (ISSUE 15, the ZeRO decomposition — Rajbhandari et
    al.): optimizer state lives 1/world per rank, gradients ride a
    **reduce-scatter** (each rank receives only its shard — half the wire
    bytes of an allreduce of the same payload), the inner update runs on
    the shard, and the updated deltas **allgather** back.  Parameters
    after K steps are bitwise-identical to ``sharded=False`` for
    elementwise optimizers (sgd/adam/...; reduction order is pinned the
    same way fused allreduce pins it — see docs/performance.md "Sharded
    optimizer (ZeRO)").  In-graph (inside shard_map over ``axis_name``)
    this wraps ``parallel.zero.sharded_optimizer``; eagerly
    (torovodrun-launched) it pipelines per-bucket scatter → shard update
    → gather through the collective engine, bucket size set by
    ``HOROVOD_PIPELINE_CHUNK``.  Single-controller SPMD outside any mesh
    axis degrades to the plain optimizer (a world of one has nothing to
    shard), like ``allreduce_gradients`` degrades to the identity.
    Default ``sharded=None`` reads ``HOROVOD_SHARDED_OPTIMIZER``.

    ``sharded="full"`` (ISSUE 18, ZeRO-3 / FSDP): parameters themselves
    live 1/world per rank.  Gradients **reduce-scatter straight into the
    owning shard** (no replicated gradient ever exists), the inner update
    runs shard-local, and ``update`` returns ``(None, state)`` — the
    training loop rematerializes full parameters each step with
    ``state.gather_params()``, whose per-bucket allgathers ride the
    engine's PREFETCH lane ``HOROVOD_PREFETCH_DEPTH`` buckets ahead of
    consumption.  Parameters after K steps are bitwise-identical to the
    replicated path; wire bytes per step (RS + AG) equal ``sharded=True``;
    resident parameter+gradient+optimizer bytes drop to ≈ 1/world.
    In-graph this wraps ``parallel.zero.full_sharded_optimizer`` (state
    carries the resident shards; see also ``zero.gather_full_params`` and
    ``zero.init_full_sharded_state``).  Default ``sharded=None`` reads
    ``HOROVOD_SHARDED_PARAMS`` first (→ ``"full"``), then
    ``HOROVOD_SHARDED_OPTIMIZER`` (→ ``True``).
    """
    del named_parameters
    if check:
        from ..analysis.hooks import run_check_hook
        run_check_hook(check)
    if process_set is not None:
        axis_name = process_set.axis_name
    k = backward_passes_per_step
    if sharded is None:
        from ..common import basics
        cfg = basics._get_state().config
        if cfg is not None and getattr(cfg, "sharded_params", False):
            sharded = "full"
        else:
            sharded = bool(cfg is not None
                           and getattr(cfg, "sharded_optimizer", False))
    if sharded not in (False, True, "full"):
        raise ValueError(
            f"sharded= must be False, True, or 'full'; got {sharded!r}")
    if sharded:
        label = 'sharded="full"' if sharded == "full" else "sharded=True"
        if k != 1:
            raise NotImplementedError(
                f"DistributedOptimizer({label}) does not compose with "
                "backward_passes_per_step > 1 yet: accumulate locally and "
                "call update every k-th step instead")
        wire = getattr(compression, "wire_mode", None)
        if wire is not None:
            raise NotImplementedError(
                f"DistributedOptimizer({label}) does not support wire "
                "compression yet: the gather leg carries parameter deltas "
                "whose precision is the training result, not a gradient")
        return _make_sharded(optimizer, op, axis_name, process_set,
                             full=sharded == "full")

    run_inner = _InnerUpdate(optimizer)

    def init_fn(params):
        inner = optimizer.init(params)
        if k == 1:
            return _DistOptState(inner, (), jnp.zeros((), jnp.int32))
        acc = jax.tree_util.tree_map(jnp.zeros_like, params)
        return _DistOptState(inner, acc, jnp.zeros((), jnp.int32))

    def _reduce(grads):
        # flat where the group travelled flat: ``run_inner`` unpacks
        return _allreduce_gradients(grads, op, axis_name, compression,
                                    process_set, keep_flat=True)

    def update_fn(grads, state: _DistOptState, params=None):
        with _update_span(grads, axis_name) as up:
            return _update(grads, state, params, up)

    def _update(grads, state: _DistOptState, params, up):
        if k == 1:
            reduced = _reduce(grads)
            updates, inner = run_inner(reduced, state.inner_state, params,
                                       _inner_span(up))
            return updates, _DistOptState(inner, (), state.counter + 1)

        acc = jax.tree_util.tree_map(lambda a, g: a + g, state.acc, grads)
        counter = state.counter + 1
        apply_now = (counter % k) == 0

        def _do_apply_concrete(acc_, inner_):
            mean_acc = jax.tree_util.tree_map(lambda a: a / k, acc_)
            reduced = _reduce(mean_acc)
            updates, new_inner = run_inner(reduced, inner_, params,
                                           _inner_span(up))
            zeroed = jax.tree_util.tree_map(jnp.zeros_like, acc_)
            return updates, new_inner, zeroed

        # Eager per-process calls must NOT go through lax.cond: it traces
        # both branches, which would trace the engine allreduce.  With a
        # concrete counter a plain Python branch is exact.
        if not isinstance(apply_now, jax.core.Tracer):
            if bool(apply_now):
                updates, inner, acc = _do_apply_concrete(acc, state.inner_state)
            else:
                updates = jax.tree_util.tree_map(jnp.zeros_like, acc)
                inner = state.inner_state
            return updates, _DistOptState(inner, acc, counter)

        def do_apply(operand):
            acc_, inner_ = operand
            mean_acc = jax.tree_util.tree_map(lambda a: a / k, acc_)
            updates, new_inner = optimizer.update(_reduce(mean_acc), inner_,
                                                  params)
            zeroed = jax.tree_util.tree_map(jnp.zeros_like, acc_)
            return updates, new_inner, zeroed

        def skip(operand):
            acc_, inner_ = operand
            updates = jax.tree_util.tree_map(jnp.zeros_like, acc_)
            return updates, inner_, acc_

        updates, inner, acc = lax.cond(apply_now, do_apply, skip,
                                       (acc, state.inner_state))
        return updates, _DistOptState(inner, acc, counter)

    return optax.GradientTransformation(init_fn, update_fn)


def DistributedGradientTape(grad_fn: Callable,
                            compression=Compression.none,
                            op: C.ReduceOp = C.ReduceOp.AVERAGE,
                            axis_name: str = C.DEFAULT_AXIS,
                            process_set: Optional[ProcessSet] = None) -> Callable:
    """Wrap a gradient function so its output gradients are allreduced.

    The JAX rendering of ``hvd.DistributedGradientTape`` (reference
    ``horovod/tensorflow/__init__.py`` §3.5): pass ``jax.grad(loss_fn)`` or
    ``jax.value_and_grad(loss_fn)``; the wrapper averages whatever gradient
    pytree comes back.  Works with ``value_and_grad`` by reducing only the
    gradient half of the result.
    """
    def wrapped(*args, **kwargs):
        out = grad_fn(*args, **kwargs)
        if isinstance(out, tuple) and len(out) == 2:
            value, grads = out
            return value, allreduce_gradients(
                grads, op=op, axis_name=axis_name, compression=compression,
                process_set=process_set)
        return allreduce_gradients(out, op=op, axis_name=axis_name,
                                   compression=compression,
                                   process_set=process_set)
    return wrapped


def broadcast_parameters(params, root_rank: int = 0,
                         process_set: Optional[ProcessSet] = None):
    """Synchronize a parameter pytree from ``root_rank`` to all ranks.

    Reference: ``horovod/torch/functions.py broadcast_parameters``.  In
    single-controller SPMD there is exactly one copy of the params (a global
    ``jax.Array``), so all "ranks" are synchronized by construction and this
    is the identity.  In multi-process mode each process holds its own copy
    and the byte-level broadcast runs through the coordinator.
    """
    leaves = jax.tree_util.tree_leaves(params)
    # the start-up record's span (trace/core.py): every call, armed or not
    with trace.startup_span(
            "hvd/broadcast_parameters", n=len(leaves), root=root_rank,
            bytes=sum(getattr(x, "nbytes", 0) for x in leaves)):
        if jax.process_count() == 1:
            return params
        out = eager.broadcast_pytree(params, root_rank=root_rank,
                                     process_set=process_set)
        return jax.tree_util.tree_map(jnp.asarray, out)


def broadcast_optimizer_state(opt_state, root_rank: int = 0,
                              process_set: Optional[ProcessSet] = None):
    """Reference: ``horovod/torch/functions.py broadcast_optimizer_state``."""
    return broadcast_parameters(opt_state, root_rank=root_rank,
                                process_set=process_set)
