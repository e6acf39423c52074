"""Eager (out-of-graph) collective API — the ``hvd.*`` op surface.

Parity with the reference's Python op layer (``horovod/torch/mpi_ops.py``,
``horovod/tensorflow/mpi_ops.py`` — SURVEY.md §2b P2/P4): blocking and
``_async`` variants of allreduce / grouped_allreduce / allgather / broadcast /
alltoall / reducescatter, plus ``synchronize``/``poll``, ``barrier`` and
``join``.  Requests flow through the background coordinator
(``ops/engine.py``) exactly like the reference's enqueue path (SURVEY.md
§3.2), so fusion/caching/timeline apply.

Tensor convention (see engine docstring): per-rank logical shape S is carried
as a stacked global array ``[world, *S]`` sharded over the world axis.
``stack_per_rank`` / ``replicated`` build these from host data.
"""

from __future__ import annotations

import itertools
import math
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from . import collectives as C
from .engine import CollectiveType
from .. import trace
from ..common import basics
from ..common.process_sets import ProcessSet

class _GroupIds:
    """``itertools.count`` that also shows the id to come: the span layer
    labels an eager update with the first group it will submit, before it
    stages anything (a label, so a racing thread may put it off by one)."""

    def __init__(self):
        self._ids = itertools.count(0)
        self.upcoming = 0

    def __next__(self) -> int:
        gid = next(self._ids)
        self.upcoming = gid + 1
        return gid


_name_counter = itertools.count(0)
_group_counter = _GroupIds()

# Auto-generated collective names are part of the negotiation wire protocol:
# they must be identical on every rank.  init() resets all counters (every
# rank re-inits together on an elastic reset, so call-order counters
# realign); other modules with wire-visible counters register here.
_counter_reset_hooks: List = []


def register_name_counter_reset(fn):
    _counter_reset_hooks.append(fn)


def reset_name_counters():
    global _name_counter, _group_counter
    _name_counter = itertools.count(0)
    _group_counter = _GroupIds()
    for fn in _counter_reset_hooks:
        fn()


def _engine():
    st = basics._get_state()
    if not st.initialized or st.engine is None:
        raise basics.NotInitializedError()
    return st.engine


def _ps(process_set: Optional[ProcessSet]) -> int:
    if process_set is None:
        return 0
    if process_set.process_set_id is None:
        raise ValueError("process_set has not been registered via add_process_set()")
    return process_set.process_set_id


def _auto_name(prefix: str, name: Optional[str]) -> str:
    return name if name else f"{prefix}.noname.{next(_name_counter)}"


def _wire_mode(compression) -> Optional[str]:
    """Normalize a ``compression=`` argument to an engine wire-dtype mode.

    Accepts ``None``/``"none"`` (off), ``"bf16"``/``"bfloat16"`` and
    ``"fp16"``/``"float16"``.  The framework bindings map their Compressor
    classes to these strings themselves (see jax/torch/tensorflow
    optimizers), so the cast pair fuses INTO the jitted collective program
    instead of running as separate host/device launches."""
    if compression is None:
        return None
    if hasattr(compression, "wire_mode"):
        # A Compressor class from any binding (the upstream calling
        # convention: compression=hvd.Compression.fp16).  Cast-style ones
        # carry their wire mode; NoneCompressor maps to off.
        return _wire_mode(compression.wire_mode)
    if isinstance(compression, str):
        c = compression.strip().lower()
        if c in ("", "none"):
            return None
        if c in ("fp16", "float16"):
            return "fp16"
        if c in ("bf16", "bfloat16"):
            return "bf16"
    raise ValueError(
        f"unsupported compression {compression!r}: expected None, 'none', "
        f"'fp16', 'bf16', or a Compression.* cast compressor")


def per_process_mode() -> bool:
    """True when this process contributes as ONE rank (torovodrun-launched,
    including an elastic world that currently has a single process) rather
    than controlling the whole world (single-controller SPMD)."""
    st = basics._get_state()
    topo = st.topology
    if topo is not None and topo.num_processes > 1:
        return True
    cfg = st.config
    return cfg is not None and cfg.controller_addr != ""


def _local_devices(ps) -> List:
    """The devices of the set's mesh that this process drives."""
    return [d for d in ps.mesh.devices.flat
            if d.process_index == jax.process_index()]


def _as_stacked(x, ps_id: int):
    """Coerce ONE input to a stacked [world, *S] jax.Array on the set's
    mesh: the single-tensor calls' staging, and what a group's staging
    (:func:`_stack_members`) gives every member it cannot put through its
    one program.

    Single-process mode: ``x`` is the full stacked [world, *S] host/device
    array.  Multi-process mode (launched by torovodrun): ``x`` is this
    process's LOCAL contribution — [*S] with one device per process, or
    [local_size, *S] with several — and the global array is assembled from
    per-device shards (``jax.make_array_from_single_device_arrays``), the
    TPU-native analogue of the reference's per-rank tensor submission
    (SURVEY.md §3.2).

    Device arrays stay device-resident: no ``np.asarray`` round-trip (the
    reference's fusion buffer exists to avoid exactly these host copies —
    SURVEY.md N7, §7 hard-part #2).

    Returns ``(array, owned)`` — ``owned`` is True when the array is a fresh
    temporary this layer created (safe for the engine to donate into the
    fused XLA program); False when it aliases the caller's array.
    """
    st = basics._get_state()
    ps = st.process_set_table.get(ps_id)
    world = ps.size()
    if isinstance(x, (np.ndarray, list, tuple, int, float)) or np.isscalar(x):
        x = np.asarray(x)
    sharding = NamedSharding(ps.mesh, P(ps.axis_name))
    if per_process_mode():
        if isinstance(x, jax.Array) and not x.is_fully_addressable:
            raise ValueError(
                "Multi-process eager collectives take this process's LOCAL "
                "contribution (a host array or local device array), not a "
                "global jax.Array; use hvd.to_local() on previous results "
                "before resubmitting them.")
        local_devs = _local_devices(ps)
        n_local = len(local_devs)
        device_resident = isinstance(x, jax.Array)
        if not device_resident:
            x = np.asarray(x)
        if n_local > 1:
            if x.shape[0] != n_local:
                raise ValueError(
                    f"Multi-device process: pass [local_size={n_local}, ...] "
                    f"local contributions; got {tuple(x.shape)}")
            per_dev = [x[i:i + 1] for i in range(n_local)]
        else:
            per_dev = [x[None] if not device_resident
                       else jnp.expand_dims(x, 0)]
        global_shape = (world,) + tuple(per_dev[0].shape[1:])
        shards = [jax.device_put(p, d) for p, d in zip(per_dev, local_devs)]
        return jax.make_array_from_single_device_arrays(
            global_shape, sharding, shards), True
    if hasattr(x, "shape") and (len(x.shape) == 0 or x.shape[0] != world):
        raise ValueError(
            f"Eager collectives take stacked per-rank tensors of shape "
            f"[world={world}, ...]; got shape {tuple(x.shape)}. Use "
            f"stack_per_rank()/replicated() to build one.")
    if isinstance(x, jax.Array):
        # Equivalent-sharding device_put ALIASES the input buffers rather
        # than copying, so donation would delete the caller's array — treat
        # any equivalently-sharded input as caller-owned.
        try:
            aliases = x.sharding.is_equivalent_to(sharding, x.ndim)
        except Exception:
            aliases = x.sharding == sharding
        if aliases:
            return (x if x.sharding == sharding
                    else jax.device_put(x, sharding)), False
    return jax.device_put(x, sharding), True


def _own_chip(ps) -> Optional[set]:
    """``{the one device of the set's mesh this process drives}`` on the
    per-process branch, else ``None``: what a group's one staging program
    (:func:`_stack_leaves`, :func:`_pack_leaves`) may take its members
    from."""
    if per_process_mode():
        local_devs = _local_devices(ps)
        if len(local_devs) == 1:
            return set(local_devs)
    return None


def _held_on(t, chip: Optional[set]) -> bool:
    return chip is not None and isinstance(t, jax.Array) \
        and t.devices() == chip


def _all_held(tensors, process_set: Optional[ProcessSet]) -> bool:
    """True where there are tensors and each is :func:`_held_on` this
    process's chip of the set's mesh: as far as its members go, the group
    may travel flat (:func:`_stage_packed`)."""
    chip = _own_chip(basics._get_state().process_set_table.get(
        _ps(process_set)))
    return bool(tensors) and all(_held_on(t, chip) for t in tensors)


def _stacked_from_shards(ps, shards) -> List:
    """Each ``[1, *S]`` array on this process's chip as the process's
    shard of a stacked ``[world, *S]`` array over the set's mesh."""
    sharding = NamedSharding(ps.mesh, P(ps.axis_name))
    world = ps.size()
    return [jax.make_array_from_single_device_arrays(
        (world,) + shard.shape[1:], sharding, [shard]) for shard in shards]


@jax.jit
def _stack_leaves(xs):
    """Every leaf ``x`` as ``x[None]``: a group's members in the stacked
    layout's local shard, by one program with a result a member.  A local,
    single-device program (no mesh, so no other rank has to launch it),
    built once here: jit's own cache keys it on shapes and dtypes.  No
    donation: the caller may hold its gradients."""
    trace.stage_group["traces"] += 1        # Python: once a trace
    return [x[None] for x in xs]


def _stack_members(tensors, ps_id: int):
    """``_as_stacked`` for every member of a group: ``([(array, owned),
    ...], compiled)``.  Members that are device arrays on this process's
    one chip (the per-process branch, one device a process) go through
    :func:`_stack_leaves` together and are wrapped a member — same shape,
    dtype, sharding and values as ``_as_stacked`` gives, for one dispatch a
    group where that takes three a member.  Anything else (host values, a
    process driving several devices, the single-controller branch's
    already stacked arrays) takes ``_as_stacked``; a group may mix the
    two.  ``compiled`` counts the members the program took."""
    ps = basics._get_state().process_set_table.get(ps_id)
    out, held = [None] * len(tensors), _own_chip(ps)
    together = [i for i, t in enumerate(tensors) if _held_on(t, held)]
    if together:
        shards = _stack_leaves([tensors[i] for i in together])
        for i, arr in zip(together, _stacked_from_shards(ps, shards)):
            out[i] = arr, True
        trace.stage_group["compiled"] += len(together)
    for i, t in enumerate(tensors):
        if out[i] is None:
            out[i] = _as_stacked(t, ps_id)
    return out, len(together)


# ---- a group as one flat buffer a dtype (the eager gradient path of
# ``jax/optimizer.py``): packed by one program with a result a dtype,
# reduced as one engine item a dtype, taken apart inside the program that
# consumes it.

def _flat_layout(leaves) -> tuple:
    """Where each leaf lies in the flat buffers: ``(k, offset, shape)`` a
    leaf, ``k`` its dtype's place in order of first appearance and
    ``offset`` counted in elements of buffer ``k``.  From the leaves'
    shapes and dtypes alone, in flatten order, so every rank agrees."""
    order, ends, layout = {}, [], []
    for x in leaves:
        k = order.setdefault(x.dtype, len(order))
        if k == len(ends):
            ends.append(0)
        layout.append((k, ends[k], tuple(x.shape)))
        ends[k] += x.size
    return tuple(layout)


@jax.jit
def _pack_leaves(xs):
    """The leaves of each dtype raveled, concatenated and given the
    stacked layout's leading 1: one ``[1, total]`` result a dtype, laid
    out as :func:`_flat_layout` says.  Local and single-device like
    :func:`_stack_leaves`, built once here, no donation: the caller may
    hold its gradients."""
    trace.stage_group["traces"] += 1        # Python: once a trace
    parts: List[list] = []
    for x, (k, _, _) in zip(xs, _flat_layout(xs)):
        if k == len(parts):
            parts.append([])
        parts[k].append(x.reshape(-1))
    return [jnp.concatenate(p)[None] for p in parts]


@jax.tree_util.register_pytree_node_class
class FlatGroup:
    """A tree of arrays held as one flat 1-D buffer a dtype: ``buffers``
    the pytree's children, ``layout`` (:func:`_flat_layout`) and the
    tree's ``treedef`` its static part, so a ``jax.jit`` that is handed
    one traces once a tree signature and can slice the leaves out inside
    its own program, where XLA fuses the slices into their consumers."""

    def __init__(self, buffers, layout, treedef):
        self.buffers, self.layout, self.treedef = buffers, layout, treedef

    def tree_flatten(self):
        return tuple(self.buffers), (self.layout, self.treedef)

    @classmethod
    def tree_unflatten(cls, aux, buffers):
        return cls(buffers, *aux)

    def tree(self):
        """The tree: every leaf a static slice of its buffer, reshaped.
        An operation a leaf, so call it under a trace (:func:`_unpack_group`
        is the program for a caller that is not in one)."""
        leaves = [
            lax.slice_in_dim(self.buffers[k], off,
                             off + math.prod(shape)).reshape(shape)
            for k, off, shape in self.layout]
        return jax.tree_util.tree_unflatten(self.treedef, leaves)


@jax.jit
def _unpack_group(flat: FlatGroup):
    """``flat.tree()`` as one program, a result a leaf: for a consumer
    that needs the tree itself (the public ``allreduce_gradients``, an
    inner update that cannot be compiled)."""
    return flat.tree()


def _stage_packed(tensors, name, prefix, ctype, process_set, priority,
                  **extra):
    """:func:`_stage_group` for a group that travels flat: ``tensors``
    (every one :func:`_held_on` this process's chip: the caller's test)
    through :func:`_pack_leaves`, one engine item a dtype named
    ``<base>.flat.<k>``, all under one fresh group id and the one
    ``priority``.  The items are this layer's own copies, so the fused
    program may take them over (``donate``).  Returns ``(group id,
    items)``."""
    ps_id = _ps(process_set)
    ps = basics._get_state().process_set_table.get(ps_id)
    gid = next(_group_counter)
    base = _auto_name(prefix, name)
    items = [dict(name=f"{base}.flat.{k}", ctype=ctype, tensor=arr,
                  process_set_id=ps_id, group_id=gid, donate=True,
                  priority=int(priority), **extra)
             for k, arr in enumerate(
                 _stacked_from_shards(ps, _pack_leaves(tensors)))]
    for count in ("compiled", "packed"):
        trace.stage_group[count] += len(tensors)
    return gid, items


def to_global(tensor, process_set: Optional[ProcessSet] = None):
    """Assemble the stacked global ``[world, *S]`` array for this input.

    Single-process: accepts the full stacked array (host or device) and
    returns it placed on the world mesh.  Multi-process: accepts this
    process's LOCAL contribution (``[*S]``, or ``[local_size, *S]`` for a
    multi-device process) and returns the global array — the public
    counterpart of :func:`to_local` for feeding jitted/shard_map programs
    directly.
    """
    return _as_stacked(tensor, _ps(process_set))[0]


def to_local(result):
    """This process's view of a collective result.

    Replicated results (allreduce/broadcast/allgather) come back whole;
    stacked sharded results (alltoall/reducescatter) come back as this
    rank's slice(s).  Single-process mode returns the full array.
    """
    if not isinstance(result, jax.Array):
        return np.asarray(result)
    if jax.process_count() == 1 or result.is_fully_addressable:
        return np.asarray(result)
    # Dedupe by shard index: replicated results place the SAME full array on
    # every local device — concatenating duplicates would silently corrupt.
    by_index = {}
    for s in result.addressable_shards:
        by_index.setdefault(_index_key(s.index), s)
    shards = [by_index[k] for k in sorted(by_index)]
    datas = [np.asarray(s.data) for s in shards]
    if len(datas) == 1:
        return datas[0]
    return np.concatenate(datas, axis=0)


def _index_key(index):
    return tuple((sl.start if sl.start is not None else 0,
                  sl.stop if sl.stop is not None else -1)
                 for sl in index)


def _local_shard(result):
    """The one device buffer this process holds of ``result``, or ``None``
    where there is no such buffer: not a ``jax.Array``, or addressable
    shards of several distinct indices (a process that drives several
    devices and got a stacked sharded result)."""
    if not isinstance(result, jax.Array):
        return None
    shards = result.addressable_shards
    if len({_index_key(s.index) for s in shards}) != 1:
        return None
    return shards[0].data


def local_array(result):
    """:func:`to_local` without leaving the device: this process's view of
    a collective result as a ``jax.Array``.

    With one process to a chip every result is a single buffer already in
    this chip's memory (replicated: the whole; stacked sharded: this
    rank's ``[1, ...]`` slice), and that buffer is returned as a committed
    single-device array: no copy, nothing waits for the device.  Otherwise
    the values come through the host (``to_local``).  Same values either
    way; ask :func:`to_local` for a NumPy array."""
    shard = _local_shard(result)
    return jnp.asarray(to_local(result)) if shard is None else shard


def stack_per_rank(values: Sequence, process_set: Optional[ProcessSet] = None):
    """Stack one value per rank into the collective input representation.

    Single-process: the full [world, *S] stacked array.  Multi-process: this
    process's slice (each process only holds its own ranks' contributions).
    """
    st = basics._get_state()
    ps = st.process_set_table.get(_ps(process_set))
    vals = [np.asarray(v) for v in values]
    if len(vals) != ps.size():
        raise ValueError(f"Expected {ps.size()} per-rank values, got {len(vals)}")
    stacked = np.stack(vals)
    if per_process_mode():
        my = [i for i, d in enumerate(ps.mesh.devices.flat)
              if d.process_index == jax.process_index()]
        local = stacked[my]
        return local[0] if len(my) == 1 else local
    return jax.device_put(stacked, NamedSharding(ps.mesh, P(ps.axis_name)))


def replicated(value, process_set: Optional[ProcessSet] = None):
    """Every rank contributes the same value."""
    st = basics._get_state()
    ps = st.process_set_table.get(_ps(process_set))
    v = np.asarray(value)
    return stack_per_rank([v] * ps.size(), process_set)


# ------------------------------------------------------------------ allreduce
def allreduce_async(tensor, name: Optional[str] = None,
                    op: C.ReduceOp = C.ReduceOp.AVERAGE,
                    prescale_factor: Optional[float] = None,
                    postscale_factor: Optional[float] = None,
                    process_set: Optional[ProcessSet] = None,
                    compression=None, priority: int = 0,
                    hierarchical: Optional[bool] = None) -> int:
    """``compression="bf16"``/``"fp16"`` casts floating tensors to the wire
    dtype inside the fused program (before the reduce) and back after —
    half the ICI bytes, zero extra launches, result in the input dtype.

    ``priority``: higher drains first from the coordinator queue (stable
    within equal priority).  Must be stamped identically on every rank —
    the DistributedOptimizer bindings use reverse registration order so
    first-needed gradients lead each cycle.

    ``hierarchical``: per-call override of the two-level ICI/DCN schedule
    (docs/performance.md "Hierarchical collectives") — True forces it,
    False forces flat, None (default) defers to
    HOROVOD_HIERARCHICAL_ALLREDUCE + the HOROVOD_HIER_THRESHOLD payload
    crossover.  Must be a rank-invariant constant (it forks the fused
    program shape; analyzer rule HVD110), but flipping it is free on the
    control plane — it rides the fusion key, never the digest."""
    ps_id = _ps(process_set)
    arr, owned = _as_stacked(tensor, ps_id)
    return _engine().enqueue(
        _auto_name("allreduce", name), CollectiveType.ALLREDUCE,
        arr, reduce_op=op, process_set_id=ps_id,
        prescale_factor=prescale_factor, postscale_factor=postscale_factor,
        donate=owned, compression=_wire_mode(compression),
        priority=priority, hierarchical=hierarchical)


def _sync_now(handle):
    """Blocking-op epilogue: kick the engine (inline cycle in
    single-controller mode — the small-tensor latency fast path) and wait."""
    _engine().kick()
    return synchronize(handle)


def allreduce(tensor, name: Optional[str] = None,
              op: C.ReduceOp = C.ReduceOp.AVERAGE,
              prescale_factor: Optional[float] = None,
              postscale_factor: Optional[float] = None,
              process_set: Optional[ProcessSet] = None,
              compression=None, priority: int = 0,
              hierarchical: Optional[bool] = None):
    return _sync_now(allreduce_async(
        tensor, name, op, prescale_factor, postscale_factor, process_set,
        compression, priority, hierarchical))


def grouped_allreduce_async(tensors: Sequence, name: Optional[str] = None,
                            op: C.ReduceOp = C.ReduceOp.AVERAGE,
                            prescale_factor: Optional[float] = None,
                            postscale_factor: Optional[float] = None,
                            process_set: Optional[ProcessSet] = None,
                            compression=None,
                            priorities: Optional[Sequence[int]] = None,
                            hierarchical: Optional[bool] = None
                            ) -> List[int]:
    """Enqueue a group that fuses/executes atomically (reference: N13).

    ``priorities`` (one int per tensor, same on every rank): drain
    priority per member — the group still executes atomically, but its
    position among OTHER clusters in the cycle follows its members'
    priorities."""
    return _grouped_async(tensors, name, "grouped_allreduce",
                          CollectiveType.ALLREDUCE, process_set, priorities,
                          reduce_op=op, prescale_factor=prescale_factor,
                          postscale_factor=postscale_factor,
                          compression=_wire_mode(compression),
                          hierarchical=hierarchical)


def grouped_allreduce(tensors: Sequence, name: Optional[str] = None,
                      op: C.ReduceOp = C.ReduceOp.AVERAGE,
                      prescale_factor: Optional[float] = None,
                      postscale_factor: Optional[float] = None,
                      process_set: Optional[ProcessSet] = None,
                      compression=None,
                      priorities: Optional[Sequence[int]] = None,
                      hierarchical: Optional[bool] = None):
    handles = grouped_allreduce_async(
        tensors, name, op, prescale_factor, postscale_factor, process_set,
        compression, priorities, hierarchical)
    _engine().kick()
    return [synchronize(h) for h in handles]


# ------------------------------------------------------------------ allgather
def allgather_async(tensor, name: Optional[str] = None,
                    process_set: Optional[ProcessSet] = None) -> int:
    ps_id = _ps(process_set)
    arr, owned = _as_stacked(tensor, ps_id)
    return _engine().enqueue(_auto_name("allgather", name),
                             CollectiveType.ALLGATHER,
                             arr, process_set_id=ps_id, donate=owned)


def allgather(tensor, name: Optional[str] = None,
              process_set: Optional[ProcessSet] = None):
    return _sync_now(allgather_async(tensor, name, process_set))


def _stage_group(tensors, name, prefix, ctype, process_set,
                 priorities=None, **extra):
    """Stage one atomic group (reference N13): every tensor into the
    engine's stacked layout (:func:`_stack_members`: one program over
    the members already on this process's chip), under one fresh group
    id.  Returns ``(group id, items, compiled)``, ``compiled`` the number
    of members that one program took; one ``enqueue_group(items)`` then
    pushes them atomically, so all members negotiate in the same round on
    every rank — which both preserves fusion atomicity and lets a
    negotiation error on one member abort the whole group.  (The eager
    optimizer paths call the two halves themselves: each is a program
    span of its own, ``hvd/update/stage`` and ``hvd/update/submit``.)

    ``priorities`` (one int per tensor, identical on every rank): drain
    priority per member — the group still executes atomically, but its
    position among OTHER clusters in the cycle follows its members'
    priorities.  The optimizers stamp reverse-registration order so
    first-needed parameters lead."""
    ps_id = _ps(process_set)
    gid = next(_group_counter)
    base = _auto_name(prefix, name)
    if priorities is not None and len(priorities) != len(tensors):
        raise ValueError(
            f"priorities must have one entry per tensor: got "
            f"{len(priorities)} for {len(tensors)} tensors")
    stacked, compiled = _stack_members(tensors, ps_id)
    items = []
    for i, (arr, owned) in enumerate(stacked):
        items.append(dict(name=f"{base}.{i}", ctype=ctype, tensor=arr,
                          process_set_id=ps_id, group_id=gid, donate=owned,
                          priority=int(priorities[i])
                          if priorities is not None else 0,
                          **extra))
    return gid, items, compiled


def _grouped_async(tensors, name, prefix, ctype, process_set,
                   priorities=None, **extra):
    """Stage and enqueue one atomic group; returns its handles."""
    return _engine().enqueue_group(_stage_group(
        tensors, name, prefix, ctype, process_set, priorities, **extra)[1])


def grouped_allgather_async(tensors: Sequence, name: Optional[str] = None,
                            process_set: Optional[ProcessSet] = None,
                            priorities: Optional[Sequence[int]] = None,
                            sharded=False,
                            prefetch: bool = False) -> List[int]:
    """Reference: ``hvd.grouped_allgather`` (upstream v0.28).

    ``sharded=True`` marks the group as part of a ZeRO-sharded program
    (the allgather leg of reduce-scatter → shard update → allgather): the
    flag rides the fusion key AND the negotiation digest, so a sharded
    program can never cross-serve an unsharded collective of the same
    shapes (and divergence of the flag across ranks fails negotiation
    fast instead of executing mismatched programs).  ``sharded="full"``
    (ISSUE 18) is the FSDP plane's value — same properties, distinct
    digest token, so full-sharded programs can't cross-serve PR 15 ones.

    ``prefetch=True`` routes the group onto the engine's PREFETCH backlog
    lane (after FAST, before FUSED, budget-exempt): the FSDP optimizer
    marks the allgathers that rematerialize the next bucket's parameters
    so they launch ahead of — without reordering — the gradient stream.
    Fusion-key-only (not digest); must be rank-invariant (HVD110)."""
    return _grouped_async(tensors, name, "grouped_allgather",
                          CollectiveType.ALLGATHER, process_set,
                          priorities=priorities, sharded=sharded,
                          prefetch=prefetch)


def grouped_allgather(tensors: Sequence, name: Optional[str] = None,
                      process_set: Optional[ProcessSet] = None,
                      priorities: Optional[Sequence[int]] = None,
                      sharded=False, prefetch: bool = False):
    handles = grouped_allgather_async(tensors, name, process_set,
                                      priorities, sharded, prefetch)
    _engine().kick()
    return [synchronize(h) for h in handles]


def grouped_reducescatter_async(tensors: Sequence,
                                name: Optional[str] = None,
                                op: C.ReduceOp = C.ReduceOp.SUM,
                                process_set: Optional[ProcessSet] = None,
                                priorities: Optional[Sequence[int]] = None,
                                sharded=False) -> List[int]:
    """Reference: ``hvd.grouped_reducescatter`` (upstream v0.28).  See
    :func:`grouped_allgather_async` for ``priorities``/``sharded``
    (``sharded="full"`` marks the FSDP gradient reduce-scatter legs)."""
    return _grouped_async(tensors, name, "grouped_reducescatter",
                          CollectiveType.REDUCESCATTER, process_set,
                          reduce_op=op, priorities=priorities,
                          sharded=sharded)


def grouped_reducescatter(tensors: Sequence, name: Optional[str] = None,
                          op: C.ReduceOp = C.ReduceOp.SUM,
                          process_set: Optional[ProcessSet] = None,
                          priorities: Optional[Sequence[int]] = None,
                          sharded=False):
    handles = grouped_reducescatter_async(tensors, name, op, process_set,
                                          priorities, sharded)
    _engine().kick()
    return [synchronize(h) for h in handles]


# ------------------------------------------------------------------ broadcast
def broadcast_async(tensor, root_rank: int = 0, name: Optional[str] = None,
                    process_set: Optional[ProcessSet] = None) -> int:
    ps_id = _ps(process_set)
    arr, owned = _as_stacked(tensor, ps_id)
    return _engine().enqueue(_auto_name("broadcast", name),
                             CollectiveType.BROADCAST,
                             arr, root_rank=root_rank,
                             process_set_id=ps_id, donate=owned)


def broadcast(tensor, root_rank: int = 0, name: Optional[str] = None,
              process_set: Optional[ProcessSet] = None):
    return _sync_now(broadcast_async(tensor, root_rank, name, process_set))


def broadcast_pytree(tree, root_rank: int = 0,
                     process_set: Optional[ProcessSet] = None):
    """Broadcast every array leaf of a pytree from ``root_rank``; leaves come
    back as host arrays with their original dtype/shape.

    One async handle per leaf so the engine fuses them into few collectives
    (reference: ``broadcast_parameters``'s grouped broadcast)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    arrays = [np.asarray(l) for l in leaves]
    handles = [broadcast_async(
        a if per_process_mode() else replicated(a, process_set),
        root_rank=root_rank, name=f"bcast_pytree.{i}",
        process_set=process_set)
        for i, a in enumerate(arrays)]
    _engine().kick()     # one inline cycle fuses all leaves
    out = [np.asarray(to_local(synchronize(h))) for h in handles]
    out = [o.astype(a.dtype).reshape(a.shape) for o, a in zip(out, arrays)]
    return jax.tree_util.tree_unflatten(treedef, out)


def allgather_object(obj, name: Optional[str] = None,
                     process_set: Optional[ProcessSet] = None,
                     per_rank: Optional[bool] = None):
    """Pickle-allgather arbitrary per-rank objects (reference:
    ``horovod/torch/mpi_ops.py allgather_object``): returns the list of
    every rank's object, identical on all ranks.

    Multi-process mode: ``obj`` is THIS rank's object — or, for a process
    driving several local devices, a list with one object per local rank
    (like ``stack_per_rank``/the ragged alltoall).  Single-controller
    mode: a list with one object per rank, or a single object to
    replicate.

    ``per_rank`` disambiguates list payloads (where type-sniffing is
    otherwise the only signal): ``True`` means ``obj`` IS the list of
    per-rank objects this caller speaks for (``world`` entries in
    single-controller mode, ``n_local`` in a multi-device process);
    ``False`` means ``obj`` is ONE object contributed verbatim for every
    rank this caller speaks for — even when it happens to be a list of
    the magic length.  The default ``None`` keeps the legacy sniff.
    Portable scripts can pass ``per_rank=False`` under every launch mode.
    """
    import pickle
    st = basics._get_state()
    ps = st.process_set_table.get(_ps(process_set))
    world = ps.size()
    base = _auto_name("allgather_obj", name)
    if per_process_mode():
        n_local = len([d for d in ps.mesh.devices.flat
                       if d.process_index == jax.process_index()])
        if n_local > 1:
            if per_rank is False:
                objs = [obj] * n_local
            else:
                objs = list(obj) if isinstance(obj, (list, tuple)) else None
                if objs is None or len(objs) != n_local:
                    raise ValueError(
                        f"Multi-device process: pass a list of {n_local} "
                        f"per-local-rank objects (or per_rank=False to "
                        f"contribute one object for all local ranks)")
            payloads = [np.frombuffer(pickle.dumps(o), np.uint8)
                        for o in objs]
        else:
            if per_rank is True:
                if not isinstance(obj, (list, tuple)) or len(obj) != 1:
                    raise ValueError(
                        "per_rank=True in a single-device process: pass "
                        "a 1-list holding this rank's object")
                obj = obj[0]
            payloads = [np.frombuffer(pickle.dumps(obj), np.uint8)]
    else:
        if per_rank is True:
            if not isinstance(obj, (list, tuple)) or len(obj) != world:
                raise ValueError(
                    f"per_rank=True: expected a list of {world} per-rank "
                    f"objects, got "
                    f"{type(obj).__name__}"
                    + (f" of length {len(obj)}"
                       if isinstance(obj, (list, tuple)) else ""))
            objs = list(obj)
        elif per_rank is False:
            objs = [obj] * world
        else:
            objs = list(obj) if isinstance(obj, (list, tuple)) \
                else [obj] * world
            if len(objs) != world:
                raise ValueError(
                    f"Expected {world} per-rank objects, got {len(objs)} "
                    f"(pass per_rank=False to replicate a list payload "
                    f"verbatim)")
        payloads = [np.frombuffer(pickle.dumps(o), np.uint8) for o in objs]

    # Size prologue, then pad to max and ride ONE even allgather — the
    # same static-shape recipe as the ragged alltoall.  In multi-process
    # mode the local contribution is [*S] for one device or
    # [n_local, *S] rows for several, matching _as_stacked.
    multi_row = not per_process_mode() or len(payloads) > 1
    if multi_row:
        sz_in = np.stack([np.array([len(p)], np.int64) for p in payloads])
    else:
        sz_in = np.array([len(payloads[0])], np.int64)
    sizes = np.asarray(to_local(allgather(
        sz_in, name=f"{base}.sizes", process_set=process_set))).reshape(-1)
    m = max(1, int(sizes.max()))
    if multi_row:
        buf = np.zeros((len(payloads), m), np.uint8)
        for i, p in enumerate(payloads):
            buf[i, :len(p)] = p
    else:
        buf = np.zeros((m,), np.uint8)
        buf[:len(payloads[0])] = payloads[0]
    out = np.asarray(to_local(allgather(
        buf, name=f"{base}.payload", process_set=process_set)))
    out = out.reshape(world, m)
    return [pickle.loads(out[r, :int(sizes[r])].tobytes())
            for r in range(world)]


def broadcast_object(obj, root_rank: int = 0, name: Optional[str] = None,
                     process_set: Optional[ProcessSet] = None):
    """Pickle-broadcast an arbitrary Python object (reference:
    ``horovod/torch/functions.py broadcast_object``).

    In single-controller mode every rank already holds the object; the
    byte-level broadcast still runs so numerics/latency match multi-process.
    """
    import pickle
    st = basics._get_state()
    ps = st.process_set_table.get(_ps(process_set))
    payload = np.frombuffer(pickle.dumps(obj), dtype=np.uint8)
    n = np.array([len(payload)], dtype=np.int64)
    sizes = broadcast(stack_per_rank([n] * ps.size(), process_set),
                      root_rank=root_rank, name=_auto_name("bcast_obj_size", name))
    size = int(to_local(sizes)[0])
    buf = np.zeros(size, dtype=np.uint8)
    k = min(len(payload), size)
    buf[:k] = payload[:k]
    out = broadcast(stack_per_rank([buf] * ps.size(), process_set),
                    root_rank=root_rank, name=_auto_name("bcast_obj", name))
    return pickle.loads(to_local(out).tobytes())


# ------------------------------------------------------------------ alltoall
def alltoall_async(tensor, splits=None, name: Optional[str] = None,
                   process_set: Optional[ProcessSet] = None):
    """Async alltoall.  The even form returns an engine handle; the ragged
    form (``splits=...``) returns a two-stage continuation handle — the
    size-exchange allgather is already in flight when this returns, the
    padded payload alltoall is enqueued as soon as it lands (``poll`` or
    ``synchronize`` advance it), mirroring the reference where the whole
    exchange is async in the background thread."""
    if splits is not None:
        return _RaggedAlltoallHandle(tensor, splits,
                                     _auto_name("alltoallv", name),
                                     process_set)
    ps_id = _ps(process_set)
    arr, owned = _as_stacked(tensor, ps_id)
    return _engine().enqueue(_auto_name("alltoall", name),
                             CollectiveType.ALLTOALL,
                             arr, process_set_id=ps_id, donate=owned)


def alltoall(tensor, splits=None, name: Optional[str] = None,
             process_set: Optional[ProcessSet] = None):
    """Even alltoall returns the gathered rows; with ``splits`` (the ragged
    form, reference ``hvd.alltoall(tensor, splits)``) returns
    ``(output, received_splits)``."""
    return _sync_now(alltoall_async(tensor, splits, name, process_set))


def _pad_chunks(x, row, world: int, m: int):
    """[n_r, *inner] rows split per ``row`` → zero-padded [world*m, *inner]."""
    x = np.asarray(x)
    inner = x.shape[1:]
    out = np.zeros((world, m) + inner, x.dtype)
    off = 0
    for j in range(world):
        s = int(row[j])
        out[j, :s] = x[off:off + s]
        off += s
    if off != x.shape[0]:
        raise ValueError(
            f"splits sum to {off} but tensor has {x.shape[0]} rows")
    return out.reshape((world * m,) + inner)


class _RaggedAlltoallHandle:
    """Async continuation for uneven alltoall: size-exchange prologue,
    pad-to-max, ONE even engine alltoall, slice (reference:
    ``hvd.alltoall`` with splits / ``recv_splits`` — SURVEY.md §2c DLRM
    config #5; async capability per the reference's mpi_ops.cc alltoall).

    The send matrix is exchanged first (tiny allgather, already in flight
    when the constructor returns), making every per-destination chunk size
    static; the payload then rides the normal negotiated/fused
    even-alltoall with chunks padded to the max size, and receivers slice
    out the real rows.  Static shapes keep the compiled program cacheable
    across steps (DLRM splits are step-invariant).  ``poll``/``synchronize``
    advance the two-stage state machine; the result is
    ``(output, received_splits)`` — per-rank lists in single-controller
    mode (outputs are ragged and cannot stack).
    """

    def __init__(self, tensor, splits, base, process_set):
        self._ps_obj = process_set
        self._base = base
        ps_id = _ps(process_set)
        st = basics._get_state()
        ps = st.process_set_table.get(ps_id)
        self._world = world = ps.size()
        self._per_process = per_process_mode()
        self._result = None
        self._done = False

        if self._per_process:
            my_ranks = [i for i, d in enumerate(ps.mesh.devices.flat)
                        if d.process_index == jax.process_index()]
            self._my_ranks = my_ranks
            n_local = len(my_ranks)
            self._sp = np.asarray(splits, dtype=np.int64).reshape(
                n_local, world)
            if n_local > 1:
                # Per-local-rank rows are ragged too: a list of arrays.
                self._locals = [np.asarray(t) for t in tensor]
                if len(self._locals) != n_local:
                    raise ValueError(f"Multi-device process: pass a list of "
                                     f"{n_local} per-rank tensors")
            else:
                self._locals = [np.asarray(tensor)]
            # Size-exchange prologue: every rank's [world] splits row.
            sp_in = self._sp if n_local > 1 else self._sp[0]
            self._h_sizes = allgather_async(
                sp_in, name=f"{base}.splits", process_set=process_set)
            self._h_payload = None
        else:
            # Single-controller mode: ``splits`` is already the full
            # [world, world] matrix — no size exchange; payload goes out
            # immediately.
            tensors = (list(tensor) if isinstance(tensor, (list, tuple))
                       else [np.asarray(tensor)[r] for r in range(world)])
            if len(tensors) != world:
                raise ValueError(f"Expected {world} per-rank tensors, got "
                                 f"{len(tensors)}")
            self._send = np.asarray(splits, dtype=np.int64).reshape(
                world, world)
            self._m = max(1, int(self._send.max()))
            padded = np.stack(
                [_pad_chunks(tensors[r], self._send[r], world, self._m)
                 for r in range(world)])
            self._h_sizes = None
            self._h_payload = alltoall_async(
                padded, name=f"{base}.payload", process_set=process_set)

    def _start_payload(self, sizes_result):
        world, n_local = self._world, len(self._my_ranks)
        self._send = np.asarray(to_local(sizes_result)).reshape(world, world)
        self._m = max(1, int(self._send.max()))
        inner = self._locals[0].shape[1:]
        self._inner = inner
        padded = np.stack([_pad_chunks(self._locals[i], self._sp[i],
                                       world, self._m)
                           for i in range(n_local)])
        payload = padded if n_local > 1 else padded[0]
        self._locals = None  # staged into the engine; free the host copy
        self._h_payload = alltoall_async(
            payload, name=f"{self._base}.payload", process_set=self._ps_obj)

    def _finish(self, res):
        world, m = self._world, self._m
        if not self._per_process:
            res = np.asarray(res)
            outs = [np.concatenate(
                [res[j, r * m: r * m + int(self._send[r, j])]
                 for r in range(world)], axis=0) for j in range(world)]
            self._result = (outs, self._send.T.copy())
        else:
            n_local = len(self._my_ranks)
            res = np.asarray(to_local(res)).reshape(
                (n_local, world * m) + self._inner)
            outs, rsplits = [], []
            for i, g in enumerate(self._my_ranks):
                rows = [res[i, r * m: r * m + int(self._send[r, g])]
                        for r in range(world)]
                outs.append(np.concatenate(rows, axis=0))
                rsplits.append(self._send[:, g].copy())
            if n_local == 1:
                self._result = (outs[0], rsplits[0])
            else:
                self._result = (outs, np.stack(rsplits))
        self._done = True

    def poll(self) -> bool:
        if self._done:
            return True
        eng = _engine()
        if self._h_payload is None:
            if not eng.poll(self._h_sizes):
                return False
            self._start_payload(eng.synchronize(self._h_sizes))
        if eng.poll(self._h_payload):
            self._finish(eng.synchronize(self._h_payload))
            return True
        return False

    def synchronize(self):
        if not self._done:
            eng = _engine()
            if self._h_payload is None:
                eng.kick()
                self._start_payload(eng.synchronize(self._h_sizes))
            eng.kick()
            self._finish(eng.synchronize(self._h_payload))
        return self._result




# -------------------------------------------------------------- reducescatter
def reducescatter_async(tensor, name: Optional[str] = None,
                        op: C.ReduceOp = C.ReduceOp.SUM,
                        process_set: Optional[ProcessSet] = None) -> int:
    ps_id = _ps(process_set)
    arr, owned = _as_stacked(tensor, ps_id)
    return _engine().enqueue(_auto_name("reducescatter", name),
                             CollectiveType.REDUCESCATTER,
                             arr, reduce_op=op,
                             process_set_id=ps_id, donate=owned)


def reducescatter(tensor, name: Optional[str] = None,
                  op: C.ReduceOp = C.ReduceOp.SUM,
                  process_set: Optional[ProcessSet] = None):
    return _sync_now(reducescatter_async(tensor, name, op, process_set))


# ------------------------------------------------------------------- control
def synchronize(handle):
    """Wait for handle(s); returns result(s) (reference: mpi_ops.synchronize)."""
    if isinstance(handle, (list, tuple)):
        return [synchronize(h) for h in handle]
    if isinstance(handle, _RaggedAlltoallHandle):
        return handle.synchronize()
    return _engine().synchronize(handle)


def poll(handle) -> bool:
    if isinstance(handle, _RaggedAlltoallHandle):
        return handle.poll()
    return _engine().poll(handle)


def barrier(process_set: Optional[ProcessSet] = None):
    """Block until all ranks reach the barrier (reference: hvd.barrier)."""
    ps_id = _ps(process_set)
    eng = _engine()
    h = eng.enqueue(_auto_name("barrier", None), CollectiveType.BARRIER,
                    None, process_set_id=ps_id)
    eng.kick()
    return eng.synchronize(h)


def join(timeout: Optional[float] = None) -> int:
    """Signal this rank is done submitting work (reference: hvd.join).

    Multi-process mode: this rank keeps participating in peers' world-level
    collectives with synthesized ZERO contributions (uneven final batches —
    the reference's join use case) until every rank has joined; returns the
    last rank to join.  In single-controller mode every rank joins
    simultaneously, so this drains the queue and returns size()-1.

    Contract: always returns the last joining rank (an ``int >= 0``) —
    never a sentinel.  If ``timeout`` expires before every rank joined,
    raises :class:`~horovod_tpu.common.exceptions.JoinTimeoutError` (a
    ``TimeoutError`` subclass); the join stays pending and may be waited
    on again.
    """
    eng = _engine()
    ctrl = eng.controller
    if ctrl is None:
        barrier()
        return basics.size() - 1
    ctrl.request_join()
    eng._wake.set()
    return ctrl.join_wait(timeout)
