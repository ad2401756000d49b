"""Collective communication over the TPU mesh.

TPU-native replacement for the reference's ProcessGroup stack
(reference: paddle/fluid/distributed/collective/process_group.h:47 virtual
AllReduce/AllGather/AllToAll/...; process_group_nccl.cc NCCL rings;
phi/core/distributed/nccl_comm_context.h:40 per-ring comm contexts;
python surface python/paddle/distributed/communication/).

Design: a ``Group`` is backed by one or more *mesh axis names* of a
``jax.sharding.Mesh`` instead of an NCCL communicator. Inside an SPMD
region (the training step traced under ``jax.shard_map`` — entered via
``spmd_region``/the Fleet engine), each collective lowers to the XLA
collective HLO (psum/all_gather/ppermute/all_to_all) on those axes,
riding ICI. Outside an SPMD region with world_size==1 the collectives
are identities, matching the reference's single-card behavior.

The "channel id"/ring-id bookkeeping of NCCL disappears: XLA assigns
channel ids at compile time from the axis structure.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
import jax.numpy as jnp
from jax import lax

from ..autograd import engine as _engine
from ..core.dispatch import def_op
from ..core.enforce import PreconditionNotMetError, enforce
from ..tensor import Tensor

__all__ = [
    "ReduceOp", "Group", "ProcessGroup", "init_parallel_env", "new_group",
    "get_group", "get_rank", "get_world_size", "all_reduce", "all_gather",
    "all_gather_object", "broadcast_object_list", "all_to_all",
    "reduce_scatter", "broadcast",
    "reduce", "scatter", "send", "recv", "isend", "irecv", "barrier",
    "spmd_region", "in_spmd_region", "split_group", "stream",
    "all_reduce_mean_value", "wait", "ppermute", "axis_index",
    "gather_object",
]


class ReduceOp:
    SUM = 0
    MAX = 1
    MIN = 2
    PROD = 3
    AVG = 4


class Group:
    """A communication group = a set of mesh axis names.

    ``nranks`` is the product of the axis sizes. ``rank`` is only
    meaningful inside an SPMD region where it is a *traced* value
    (lax.axis_index) — Python-level code must branch with lax.cond/where,
    never `if rank == k:` (XLA semantics; see SURVEY.md §7 hard parts).
    """

    _next_gid = 0

    def __init__(self, axis_names: Tuple[str, ...], nranks: int,
                 name: str = "", pg=None):
        self.axis_names = tuple(axis_names)
        self.nranks = nranks
        self.name = name or "+".join(axis_names) or "world"
        self.id = Group._next_gid
        Group._next_gid += 1
        self.process_group = pg

    @property
    def world_size(self) -> int:
        return self.nranks

    @property
    def rank(self):
        if in_spmd_region() and self.axis_names:
            return axis_index(self.axis_names)
        return 0

    def get_group_rank(self, global_rank):
        return global_rank

    def __repr__(self):
        return f"Group(axes={self.axis_names}, nranks={self.nranks})"


# ProcessGroup alias keeps the reference's C++-facing name alive for users.
ProcessGroup = Group


class _World:
    def __init__(self):
        self.mesh: Optional[jax.sharding.Mesh] = None
        self.groups: Dict[int, Group] = {}
        self.default_group: Optional[Group] = None
        self.initialized = False
        self.rank = 0
        self.world_size = 1
        # Bumped whenever the world mesh is REBUILT (split_group axis
        # factoring). Engines/compiled steps snapshot the epoch at build
        # time and refuse to run against a newer mesh — shardings compiled
        # against deleted axis names must not silently execute.
        self.mesh_epoch = 0


_world = _World()
_spmd = threading.local()


def _mesh_devices(n: Optional[int] = None):
    devs = jax.devices()
    return devs if n is None else devs[:n]


def init_parallel_env(mesh: Optional[jax.sharding.Mesh] = None,
                      strategy=None) -> Group:
    """(reference: python/paddle/distributed/parallel.py:943-1101 —
    TCPStore rendezvous → ProcessGroup creation. TPU-native: the same
    TCPStore bootstraps ``jax.distributed.initialize`` (runtime.py), after
    which ``jax.devices()`` is the GLOBAL device list; the world mesh is
    built over it and in-graph collectives cross processes.)"""
    from . import runtime as _rt

    _rt.ensure_initialized()
    if _world.initialized and mesh is None:
        return _world.default_group
    if mesh is None:
        devs = np.array(_mesh_devices())
        mesh = jax.sharding.Mesh(devs, ("world",))
    _world.mesh = mesh
    _world.world_size = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
    _world.rank = _process_rank()
    g = Group(tuple(mesh.axis_names), _world.world_size, name="world")
    _world.default_group = g
    _world.groups[0] = g
    _world.initialized = True
    return g


def _process_rank() -> int:
    try:
        return jax.process_index()
    except Exception:
        return 0


def is_initialized() -> bool:
    return _world.initialized


def get_world_mesh() -> Optional[jax.sharding.Mesh]:
    return _world.mesh


def mesh_epoch() -> int:
    """Current world-mesh generation (see _World.mesh_epoch)."""
    return _world.mesh_epoch


def get_rank(group: Optional[Group] = None):
    if in_spmd_region():
        g = group or _world.default_group
        if g is not None and g.axis_names:
            return axis_index(g.axis_names)
    return _world.rank


def get_world_size(group: Optional[Group] = None) -> int:
    if group is not None:
        return group.nranks
    return _world.world_size


def get_group(gid: int = 0) -> Group:
    return _world.groups.get(gid, _world.default_group)


def new_group(ranks=None, backend=None, timeout=None,
              axis_names: Optional[Sequence[str]] = None,
              nranks: Optional[int] = None, name: str = "") -> Group:
    """Create a subgroup. TPU-native: subgroups are mesh axes; ``ranks``
    lists are accepted for API parity (the topology layer translates rank
    lists into axes when building the hybrid mesh)."""
    if axis_names is not None:
        mesh = _world.mesh
        n = nranks or int(np.prod([mesh.shape[a] for a in axis_names])) \
            if mesh is not None else (nranks or 1)
        g = Group(tuple(axis_names), n, name=name)
    else:
        n = len(ranks) if ranks else _world.world_size
        g = Group((), n, name=name or f"ranks{ranks}")
        g._ranks = list(ranks) if ranks else list(range(n))
    _world.groups[g.id] = g
    return g


def split_group(parent: Group, every: int) -> Group:
    """Split ``parent`` into contiguous subgroups of size ``every``.

    TPU-native: a mesh axis of size ``n = k*every`` factors into
    ``(outer k, inner every)``; the subgroup is the *inner* axis. When
    the world mesh owns the parent axis we reshape it into two axes and
    return a Group over the inner one (reference analog:
    python/paddle/distributed/communication/group.py split by rank list).
    """
    enforce(parent.nranks % every == 0,
            f"split_group: {parent.nranks} ranks not divisible by {every}")
    if parent.nranks == every:
        return parent
    mesh = _world.mesh
    if mesh is not None and len(parent.axis_names) == 1 \
            and parent.axis_names[0] in mesh.axis_names:
        ax = parent.axis_names[0]
        outer = parent.nranks // every
        inner_name, outer_name = f"{ax}_in{every}", f"{ax}_out{every}"
        if inner_name not in mesh.axis_names:
            # rebuild the world mesh with the parent axis factored
            # (outer-major, so linearised (outer, inner) order == the
            # original axis order) and rewrite EVERY existing group that
            # referenced the old axis onto the (outer, inner) pair —
            # psum over both sub-axes is exactly psum over the original
            # axis, so their collectives keep the same semantics.
            axes, sizes = [], []
            for a in mesh.axis_names:
                if a == ax:
                    axes += [outer_name, inner_name]
                    sizes += [outer, every]
                else:
                    axes.append(a)
                    sizes.append(mesh.shape[a])
            _world.mesh = jax.sharding.Mesh(
                mesh.devices.reshape(sizes), tuple(axes))
            _world.mesh_epoch += 1  # invalidate engines built on old axes
            for g in _world.groups.values():
                if ax in g.axis_names:
                    g.axis_names = tuple(
                        sub for a in g.axis_names
                        for sub in ((outer_name, inner_name) if a == ax
                                    else (a,)))
        g = Group((inner_name,), every, name=f"{parent.name}/{every}")
        _world.groups[g.id] = g
        return g
    # no owning mesh axis: host-side subgroup — members are the
    # contiguous block of `every` ranks containing THIS process
    from . import runtime as _rt

    lo = (_rt.process_rank() // every) * every
    g = Group((), every, name=f"{parent.name}/{every}")
    g._ranks = list(range(lo, lo + every))
    _world.groups[g.id] = g
    return g


# ---------------------------------------------------------------------------
# SPMD region context
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def spmd_region(mesh: Optional[jax.sharding.Mesh] = None):
    """Marks that the code is being traced inside jax.shard_map, so
    collectives emit XLA collective ops with axis names."""
    prev = getattr(_spmd, "depth", 0)
    _spmd.depth = prev + 1
    try:
        yield
    finally:
        _spmd.depth = prev


def in_spmd_region() -> bool:
    return getattr(_spmd, "depth", 0) > 0


def axis_size(axis_name) -> int:
    """Static size of a named mesh axis inside an SPMD region."""
    return lax.axis_size(axis_name)


def axis_index(axis_names: Tuple[str, ...]):
    """Linearised rank within the (possibly multi-axis) group."""
    idx = lax.axis_index(axis_names[0])
    for a in axis_names[1:]:
        idx = idx * axis_size(a) + lax.axis_index(a)
    return idx


# ---------------------------------------------------------------------------
# Traced-collective shim (the comm ledger's interposition point).
#
# EVERY in-graph collective in the tree funnels through these t_*
# wrappers instead of calling lax.* directly, so that
# observability/commledger.py sees each one at TRACE time (op kind,
# axes, local shape/dtype, group size) and the exposed-comm profiler
# can ablate an axis's collectives into shape-preserving local ops.
# With no capture and no ablation active they ARE the lax call — the
# fast path adds one predicate per traced call site and nothing to the
# compiled program.
# ---------------------------------------------------------------------------


def _flat_axes(axes) -> Tuple[str, ...]:
    if isinstance(axes, str):
        return (axes,)
    flat: List[str] = []
    for a in axes:
        flat.extend(a if isinstance(a, (tuple, list)) else (a,))
    return tuple(flat)


def _group_size(axes: Tuple[str, ...]) -> int:
    p = 1
    for a in axes:
        p *= int(axis_size(a))
    return p


def _note_shim(op: str, axes, x, args: Tuple = ()):
    """If the ledger is active: note the collective and answer
    (group size, is-this-axis-group-ablated). Trace-time host
    bookkeeping only — adds nothing to the compiled program."""
    from ..observability import commledger as cl

    if not cl.active():
        return None, False
    flat = _flat_axes(axes)
    p = _group_size(flat)
    cl.note(op, flat, tuple(getattr(x, "shape", ())),
            getattr(x, "dtype", "float32"), p, args)
    return p, cl.ablating("+".join(flat))


def t_psum(x, axes):
    p, abl = _note_shim("psum", axes, x)
    return x if abl else lax.psum(x, axes)


def t_pmean(x, axes):
    # wire-identical to psum (ledger kind "psum"); ablated = identity
    p, abl = _note_shim("psum", axes, x)
    return x if abl else lax.pmean(x, axes)


def t_pmax(x, axes):
    p, abl = _note_shim("pmax", axes, x)
    return x if abl else lax.pmax(x, axes)


def t_pmin(x, axes):
    p, abl = _note_shim("pmin", axes, x)
    return x if abl else lax.pmin(x, axes)


def _abl_gather(x, p, axis):
    """Ablated all_gather: p local copies (shape-preserving stand-in)."""
    return jnp.concatenate([x] * p, axis=axis)


def _abl_scatter(x, p, dim):
    """Ablated reduce_scatter: keep the leading 1/p local chunk."""
    return lax.slice_in_dim(x, 0, x.shape[dim] // p, axis=dim)


def _abl_a2a(x, p, split_axis, concat_axis):
    """Ablated all_to_all: local reshuffle with the same output shape."""
    if split_axis == concat_axis:
        return x
    y = lax.slice_in_dim(x, 0, x.shape[split_axis] // p, axis=split_axis)
    return jnp.concatenate([y] * p, axis=concat_axis)


def t_all_gather(x, axes, axis=0, tiled=True):
    p, abl = _note_shim("all_gather", axes, x, (int(axis),))
    return _abl_gather(x, p, axis) if (abl and tiled) else \
        lax.all_gather(x, axes, axis=axis, tiled=tiled)


def t_psum_scatter(x, axes, scatter_dimension=0, tiled=True):
    p, abl = _note_shim("reduce_scatter", axes, x,
                        (int(scatter_dimension),))
    return _abl_scatter(x, p, scatter_dimension) if (abl and tiled) else \
        lax.psum_scatter(x, axes, scatter_dimension=scatter_dimension,
                         tiled=tiled)


def t_all_to_all(x, axes, split_axis=0, concat_axis=0, tiled=True):
    p, abl = _note_shim("all_to_all", axes, x,
                        (int(split_axis), int(concat_axis)))
    return _abl_a2a(x, p, split_axis, concat_axis) if (abl and tiled) \
        else lax.all_to_all(x, axes, split_axis=split_axis,
                            concat_axis=concat_axis, tiled=tiled)


def t_ppermute(x, axes, perm):
    perm = tuple(tuple(pr) for pr in perm)
    flat = _flat_axes(axes)
    _, abl = _note_shim("ppermute", flat, x, (perm,))
    return x if abl else lax.ppermute(
        x, flat[0] if len(flat) == 1 else flat, perm=list(perm))


# ---------------------------------------------------------------------------
# Collective kernels (registered ops so autograd records them; analog of
# phi collective kernels phi/kernels/gpu/all_reduce_kernel.cu etc.)
# ---------------------------------------------------------------------------


def _psum_like(x, op: int, axes):
    if op == ReduceOp.SUM:
        return t_psum(x, axes)
    if op == ReduceOp.MAX:
        return t_pmax(x, axes)
    if op == ReduceOp.MIN:
        return t_pmin(x, axes)
    if op == ReduceOp.AVG:
        return t_pmean(x, axes)
    if op == ReduceOp.PROD:
        # sign/zero-correct product: magnitude via exp∘psum∘log of |x|,
        # sign via negative-count parity, zero if any member holds a zero
        zero = t_pmax((x == 0).astype(x.dtype), axes)
        negs = t_psum((x < 0).astype(jnp.int32), axes)
        sign = jnp.where(negs % 2 == 0, jnp.ones_like(x), -jnp.ones_like(x))
        safe = jnp.where(x == 0, jnp.ones_like(x), jnp.abs(x))
        mag = jnp.exp(t_psum(jnp.log(safe), axes))
        return jnp.where(zero > 0, jnp.zeros_like(x), sign * mag)
    raise ValueError(f"bad reduce op {op}")


@def_op("c_allreduce")
def _c_allreduce(x, op=0, axes=()):
    return _psum_like(x, op, axes)


@def_op("c_allgather")
def _c_allgather(x, axes=(), axis=0):
    return t_all_gather(x, axes, axis=axis, tiled=True)


@def_op("c_reducescatter")
def _c_reducescatter(x, axes=(), axis=0):
    return t_psum_scatter(x, axes, scatter_dimension=axis, tiled=True)


@def_op("c_alltoall")
def _c_alltoall(x, axes=(), split_axis=0, concat_axis=0):
    return t_all_to_all(x, axes, split_axis=split_axis,
                        concat_axis=concat_axis, tiled=True)


@def_op("c_broadcast")
def _c_broadcast(x, axes=(), src=0):
    # broadcast = select src's value on every member
    idx = axis_index(axes)
    masked = jnp.where(idx == src, x, jnp.zeros_like(x))
    return t_psum(masked, axes)


@def_op("c_ppermute")
def _c_ppermute(x, axes=(), perm=()):
    return t_ppermute(x, axes, perm)


# ---------------------------------------------------------------------------
# Public API (python/paddle/distributed/communication parity)
# ---------------------------------------------------------------------------


def _group_axes(group: Optional[Group]):
    g = group or _world.default_group
    if g is None or not g.axis_names:
        # a rank-list group with >1 members but no mesh axis cannot lower
        # to an XLA collective — silently becoming an identity would be a
        # correctness trap, so fail loudly inside traced SPMD code
        if (in_spmd_region() and g is not None and g.nranks > 1
                and getattr(g, "_ranks", None)):
            raise PreconditionNotMetError(
                f"group {g.name!r} was created from a rank list without a "
                f"mesh axis; inside an SPMD region collectives need mesh "
                f"axes — create the group via the hybrid topology "
                f"(fleet.init) or new_group(axis_names=...)")
        return None
    return g.axis_names


def _noop(tensor):
    return tensor


def all_reduce(tensor: Tensor, op: int = ReduceOp.SUM,
               group: Optional[Group] = None, sync_op: bool = True):
    axes = _group_axes(group)
    if not in_spmd_region() or axes is None:
        return tensor  # world of 1 (or outside SPMD): identity
    out = _c_allreduce(tensor, op=op, axes=axes)
    tensor._value = out._value
    tensor._grad_node = out._grad_node
    tensor._out_idx = out._out_idx
    tensor.stop_gradient = out.stop_gradient
    return tensor


def all_reduce_mean_value(tensor: Tensor, group: Optional[Group] = None):
    axes = _group_axes(group)
    if not in_spmd_region() or axes is None:
        return tensor
    return _c_allreduce(tensor, op=ReduceOp.AVG, axes=axes)


def all_gather(tensor_list: Optional[List], tensor: Tensor = None,
               group: Optional[Group] = None, sync_op: bool = True, axis=0):
    """paddle signature: all_gather(tensor_list, tensor). Returns stacked
    result; also fills tensor_list if given."""
    if tensor is None:
        tensor, tensor_list = tensor_list, None
    axes = _group_axes(group)
    if not in_spmd_region() or axes is None:
        if tensor_list is not None:
            tensor_list.append(tensor)
        return tensor
    out = _c_allgather(tensor, axes=axes, axis=axis)
    if tensor_list is not None:
        n = (group or _world.default_group).nranks
        from ..ops.manipulation import split as _split

        tensor_list.extend(_split(out, n, axis=axis))
    return out


def reduce_scatter(tensor: Tensor, tensor_or_tensor_list=None, op=ReduceOp.SUM,
                   group: Optional[Group] = None, sync_op=True, axis=0):
    axes = _group_axes(group)
    src = tensor_or_tensor_list if tensor_or_tensor_list is not None else tensor
    if isinstance(src, (list, tuple)):
        from ..ops.manipulation import concat as _concat

        src = _concat(list(src), axis=axis)
    if not in_spmd_region() or axes is None:
        return src
    return _c_reducescatter(src, axes=axes, axis=axis)


def all_to_all(out_tensor_list, in_tensor_list=None,
               group: Optional[Group] = None, sync_op: bool = True):
    """List-form paddle API; also accepts a single stacked tensor."""
    single = not isinstance(out_tensor_list, list) or in_tensor_list is None
    if in_tensor_list is None:
        x = out_tensor_list
    else:
        from ..ops.manipulation import concat as _concat

        x = _concat(list(in_tensor_list), axis=0) if isinstance(
            in_tensor_list, (list, tuple)) else in_tensor_list
    axes = _group_axes(group)
    if in_spmd_region() and axes is not None:
        out = _c_alltoall(x, axes=axes, split_axis=0, concat_axis=0)
    else:
        out = x
    if isinstance(out_tensor_list, list) and in_tensor_list is not None:
        n = (group or _world.default_group).nranks
        from ..ops.manipulation import split as _split

        out_tensor_list.clear()
        out_tensor_list.extend(_split(out, n, axis=0))
    return out


def alltoall(in_tensor_list, out_tensor_list=None, group=None, sync_op=True):
    return all_to_all(out_tensor_list if out_tensor_list is not None
                      else in_tensor_list,
                      in_tensor_list if out_tensor_list is not None else None,
                      group=group, sync_op=sync_op)


def broadcast(tensor: Tensor, src: int = 0, group: Optional[Group] = None,
              sync_op: bool = True):
    axes = _group_axes(group)
    if not in_spmd_region() or axes is None:
        return tensor
    out = _c_broadcast(tensor, axes=axes, src=int(src))
    tensor._value = out._value
    tensor._grad_node = out._grad_node
    tensor._out_idx = out._out_idx
    return tensor


def reduce(tensor: Tensor, dst: int = 0, op=ReduceOp.SUM,
           group: Optional[Group] = None, sync_op: bool = True):
    # SPMD model has no single-destination buffers; reduce == allreduce
    # with non-dst members free to ignore (XLA DCE removes unused copies).
    return all_reduce(tensor, op=op, group=group)


def scatter(tensor: Tensor, tensor_list=None, src: int = 0,
            group: Optional[Group] = None, sync_op: bool = True):
    axes = _group_axes(group)
    if not in_spmd_region() or axes is None:
        if tensor_list:
            tensor._value = tensor_list[0]._value
        return tensor
    from ..ops.manipulation import concat as _concat, split as _split

    stacked = _concat(list(tensor_list), axis=0) if tensor_list else tensor
    stacked = _c_broadcast(stacked, axes=axes, src=int(src))
    n = (group or _world.default_group).nranks
    idx = axis_index(axes)
    chunk = stacked.shape[0] // n
    out = _dynamic_chunk(stacked, idx, chunk=chunk)
    tensor._value = out._value
    return tensor


@def_op("c_dynamic_chunk")
def _dynamic_chunk(x, idx, chunk=1):
    return lax.dynamic_slice_in_dim(x, idx * chunk, chunk, axis=0)


def ppermute(tensor: Tensor, perm: List[Tuple[int, int]],
             group: Optional[Group] = None):
    """Collective-permute: the TPU-native p2p primitive (ICI neighbor
    exchange). This is what pipeline send/recv lowers to (reference
    analog: fleet pp_utils/p2p_communication.py over NCCL send/recv)."""
    axes = _group_axes(group)
    if not in_spmd_region() or axes is None:
        return tensor
    return _c_ppermute(tensor, axes=axes, perm=tuple(tuple(p) for p in perm))


def send(tensor: Tensor, dst: int = 0, group: Optional[Group] = None,
         sync_op: bool = True):
    """Point-to-point send.

    Inside an SPMD region p2p is a *collective* — use
    :func:`ppermute` (which lowers to XLA collective-permute on ICI,
    the pipeline engine's p2p primitive). Eagerly (outside shard_map)
    this is a host-side transfer over the TCPStore/DCN — the role the
    reference's gloo send fills (process_group_gloo.cc).
    """
    if in_spmd_region():
        raise PreconditionNotMetError(
            "inside an SPMD region p2p is collective: express the "
            "send/recv pair as paddle_tpu.distributed.ppermute(tensor, "
            "perm=[(src, dst)])")
    from . import runtime as _rt

    val = np.asarray(tensor._value if isinstance(tensor, Tensor) else tensor)
    if not _rt.is_multiprocess():
        # world of 1: the only process is rank 0, so only a self-send can
        # ever be matched — reject anything else instead of buffering a
        # message no recv key will find
        enforce(int(dst) == 0,
                f"send(dst={dst}) in a single-process world: only "
                f"self-send (dst=0) is possible")
        _loopback.setdefault((0, 0), []).append(val)
        return _SendRecvTask(tensor)
    _rt.send_object(val, dst)
    return _SendRecvTask(tensor)


def recv(tensor: Tensor, src: int = 0, group: Optional[Group] = None,
         sync_op: bool = True):
    if in_spmd_region():
        raise PreconditionNotMetError(
            "inside an SPMD region p2p is collective: express the "
            "send/recv pair as paddle_tpu.distributed.ppermute(tensor, "
            "perm=[(src, dst)])")
    from . import runtime as _rt

    if not _rt.is_multiprocess():
        enforce(int(src) == 0,
                f"recv(src={src}) in a single-process world: only "
                f"self-recv (src=0) is possible")
        q = _loopback.get((0, 0))
        enforce(q, f"recv(src={src}): no matching send buffered "
                   f"(single-process loopback)")
        val = q.pop(0)
    else:
        val = _rt.recv_object(src)
    arr = jnp.asarray(val)
    if isinstance(tensor, Tensor):
        tensor._value = arr.astype(tensor._value.dtype).reshape(
            tensor._value.shape)
    return _SendRecvTask(tensor)


# single-process (src,dst) -> FIFO of pending sends, so a send/recv pair
# in a world of 1 still transfers the bytes instead of silently no-opping
_loopback: Dict[Tuple[int, int], List] = {}


class _SendRecvTask:
    """Completed-task handle (API parity with ProcessGroup::Task)."""

    def __init__(self, tensor):
        self.tensor = tensor

    def wait(self):
        return self.tensor

    def is_completed(self):
        return True


def isend(tensor, dst=0, group=None):
    return send(tensor, dst, group, sync_op=False)


def irecv(tensor, src=0, group=None):
    return recv(tensor, src, group, sync_op=False)


def barrier(group: Optional[Group] = None):
    if not in_spmd_region():
        from . import runtime as _rt

        # device flush + cross-process host barrier (reference: gloo
        # barrier in process_group_gloo.cc; here the TCPStore counter)
        jnp.zeros(()).block_until_ready()
        _rt.host_barrier("dist_barrier")
        return
    return None


def wait(tensor, group=None, use_calc_stream=True):
    return tensor


def all_gather_object(object_list, obj, group=None):
    """Gather picklable objects from every process (reference:
    python/paddle/distributed/communication/all_gather.py object path —
    gloo-backed; here pickled blobs through the TCPStore over DCN)."""
    from . import runtime as _rt

    object_list.extend(_rt.all_gather_object_host(obj))
    return object_list


def gather_object(obj, dst: int = 0, group=None):
    """Gather picklable objects on ``dst`` only (others get None) —
    the O(world)-at-root counterpart of all_gather_object."""
    from . import runtime as _rt

    return _rt.gather_object_host(obj, dst=dst)


def broadcast_object_list(object_list, src: int = 0, group=None):
    from . import runtime as _rt

    # one blob + one barrier for the whole list (not per element)
    object_list[:] = _rt.broadcast_object_host(list(object_list), src=src)
    return object_list


class stream:
    """paddle.distributed.stream.* parity namespace (the reference exposes
    stream-variant collectives; on TPU XLA owns streams so these are the
    same ops)."""

    all_reduce = staticmethod(all_reduce)
    all_gather = staticmethod(all_gather)
    reduce_scatter = staticmethod(reduce_scatter)
    alltoall = staticmethod(alltoall)
    broadcast = staticmethod(broadcast)
    reduce = staticmethod(reduce)


class P2POp:
    """One batched point-to-point operation (reference:
    communication/batch_isend_irecv.py P2POp): op is ``isend`` or
    ``irecv``, bound to a tensor and a peer rank."""

    def __init__(self, op, tensor, peer, group=None):
        enforce(op in (isend, irecv),
                "P2POp op must be paddle.distributed.isend or irecv")
        self.op = op
        self.tensor = tensor
        self.peer = peer
        self.group = group


def batch_isend_irecv(p2p_op_list):
    """Issue a batch of isend/irecv (reference:
    communication/batch_isend_irecv.py). On TPU the sends/receives are
    XLA-ordered host-transport ops, so 'batching' is issuing them in
    list order; returns one task per op."""
    enforce(len(p2p_op_list) > 0, "batch_isend_irecv needs >= 1 P2POp")
    tasks = []
    for p in p2p_op_list:
        enforce(isinstance(p, P2POp),
                "batch_isend_irecv takes a list of P2POp")
        tasks.append(p.op(p.tensor, p.peer, p.group))
    return tasks
