"""Functional collectives + communication groups.

Reference parity: python/paddle/distributed/collective.py (all_reduce :618,
all_gather :840, alltoall :1769, broadcast, reduce, scatter, barrier :285,
new_group :343) backed by ProcessGroupNCCL / c_* ops (SURVEY.md §2.4).

TPU-native design — single-controller SPMD changes the data model: there is
one python program driving every chip, so "each rank's local tensor" is
represented **rank-stacked**: a tensor whose leading axis indexes ranks of
the group, sharded over the group's mesh axis (one slice per chip).  Each
collective is a `shard_map` whose body runs the matching `jax.lax`
collective (psum/all_gather/all_to_all/ppermute) — exactly the HLO XLA would
emit on ICI.  The same functions work inside `to_static`/jit traces.

Under true multi-host execution (`jax.distributed.initialize`), the same
stacked arrays are global arrays spanning hosts and nothing here changes —
that is the point of the single-controller model.
"""
from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..core.tensor import Tensor
from ..core.dispatch import apply_op
from . import mesh as mesh_mod


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


_REDUCERS = {
    ReduceOp.SUM: jax.lax.psum,
    ReduceOp.MAX: jax.lax.pmax,
    ReduceOp.MIN: jax.lax.pmin,
}


class Group:
    """A communication group = a 1-axis device mesh (reference:
    ProcessGroup / ring-id; here literally a mesh axis named 'group')."""

    AXIS = "group"

    def __init__(self, ranks: Sequence[int], gid: int = 0):
        self.ranks = list(ranks)
        self.id = gid
        devs = jax.devices()
        self._devices = [devs[r] for r in self.ranks]
        self.mesh = Mesh(np.array(self._devices), (self.AXIS,))

    @property
    def nranks(self) -> int:
        return len(self.ranks)

    @property
    def world_size(self) -> int:
        return len(self.ranks)

    def get_group_rank(self, rank: int) -> int:
        return self.ranks.index(rank) if rank in self.ranks else -1

    def __repr__(self):
        return f"Group(id={self.id}, ranks={self.ranks})"


_groups: List[Group] = []
_default_group: Optional[Group] = None


def _world_group() -> Group:
    global _default_group
    if _default_group is None:
        _default_group = Group(list(range(len(jax.devices()))), gid=0)
        _groups.append(_default_group)
    return _default_group


def new_group(ranks: Optional[Sequence[int]] = None, backend=None, timeout=None) -> Group:
    """Create a sub-group over the given global device ranks
    (reference: collective.py:343)."""
    if ranks is None:
        ranks = list(range(len(jax.devices())))
    g = Group(ranks, gid=len(_groups) + 1)
    _groups.append(g)
    return g


def get_group(gid: int = 0) -> Optional[Group]:
    for g in _groups:
        if g.id == gid:
            return g
    return None


def _group_or_world(group) -> Group:
    return group if isinstance(group, Group) else _world_group()


def _group_local(g: Group, rank: int, api: str, role: str) -> int:
    """Map a global rank to its index inside the group; reject outsiders."""
    if rank not in g.ranks:
        raise ValueError(f"{api}: {role} rank {rank} is not in group "
                         f"{g.ranks}")
    return g.ranks.index(rank)


def _check_stacked(arr, g: Group, api: str):
    if arr.ndim == 0 or arr.shape[0] != g.nranks:
        raise ValueError(
            f"{api}: single-controller SPMD collectives take rank-stacked "
            f"tensors — leading axis must equal group size {g.nranks}, got "
            f"shape {tuple(arr.shape)}. See paddle_tpu.distributed docs.")


def _smap(g: Group, body, n_in: int = 1):
    specs = [P(Group.AXIS)] * n_in
    return jax.shard_map(body, mesh=g.mesh, in_specs=tuple(specs) if n_in > 1 else specs[0],
                     out_specs=P(Group.AXIS))


def _run(name, fn, tensors):
    """Dispatch through the framework tape so collectives are differentiable
    and trace-cleanly under to_static."""
    return apply_op(name, fn, list(tensors))


def _make_reducer(op, g: Group):
    """Shard-level reduction body for `op` (signed product via gather —
    exp(psum(log)) would NaN on negatives)."""
    if op == ReduceOp.AVG:
        return lambda s: jax.lax.psum(s, Group.AXIS) / g.nranks
    if op == ReduceOp.PROD:
        return lambda s: jnp.prod(jax.lax.all_gather(s[0], Group.AXIS),
                                  axis=0)[None]
    base = _REDUCERS[op]
    return lambda s: base(s, Group.AXIS)


# -- collectives ----------------------------------------------------------

def all_reduce(tensor: Tensor, op=ReduceOp.SUM, group=None, sync_op=True):
    """In-place all-reduce over the group (reference: collective.py:618).
    Stacked semantics: every rank slice becomes the reduction."""
    g = _group_or_world(group)
    arr = tensor._value()
    _check_stacked(arr, g, "all_reduce")
    red = _make_reducer(op, g)
    out = _run("all_reduce", _smap(g, red), [tensor])
    tensor._set_data(out._value())
    return tensor


def all_reduce_chunked(tensor: Tensor, chunks: int = 1, op=ReduceOp.SUM,
                       group=None):
    """All-reduce issued as ``chunks`` independent slice reductions along
    the trailing axis — the collective-decomposition primitive behind the
    TP overlap schedule (fleet/meta_parallel/overlap.py) exposed at the
    collective API: XLA can interleave surrounding compute with the
    per-chunk reduces instead of stalling on one monolithic fused
    all-reduce.  ``chunks=1`` (or a non-dividing chunk count) is exactly
    :func:`all_reduce`."""
    g = _group_or_world(group)
    arr = tensor._value()
    _check_stacked(arr, g, "all_reduce_chunked")
    last = arr.shape[-1] if arr.ndim > 1 else 1
    if chunks <= 1 or last % chunks != 0:
        return all_reduce(tensor, op=op, group=g)
    red = _make_reducer(op, g)
    ch = last // chunks

    def body(s):
        parts = [red(s[..., c * ch:(c + 1) * ch]) for c in range(chunks)]
        return jnp.concatenate(parts, axis=-1)

    out = _run("all_reduce_chunked", _smap(g, body), [tensor])
    tensor._set_data(out._value())
    return tensor


def all_gather(tensor_or_list, tensor=None, group=None, sync_op=True):
    """all_gather(tensor, group) -> stacked [W, W, ...]; or the reference
    list form all_gather(tensor_list, tensor) (collective.py:840)."""
    g = _group_or_world(group)
    as_list = isinstance(tensor_or_list, list)
    src = tensor if as_list else tensor_or_list
    arr = src._value()
    _check_stacked(arr, g, "all_gather")

    def body(s):  # s: [1, ...] -> [1, W, ...]
        return jax.lax.all_gather(s[0], Group.AXIS)[None]

    out = _run("all_gather", _smap(g, body), [src])
    if as_list:
        tensor_or_list.clear()
        for i in range(g.nranks):
            tensor_or_list.append(Tensor._wrap(out._value()[:, i]))
        return tensor_or_list
    return out


def broadcast(tensor: Tensor, src: int = 0, group=None, sync_op=True):
    """Every rank slice becomes rank-src's slice (reference ProcessGroup
    Broadcast)."""
    g = _group_or_world(group)
    arr = tensor._value()
    _check_stacked(arr, g, "broadcast")
    src_local = _group_local(g, src, "broadcast", "src")

    def body(s):
        return jax.lax.all_gather(s[0], Group.AXIS)[src_local][None]

    out = _run("broadcast", _smap(g, body), [tensor])
    tensor._set_data(out._value())
    return tensor


def reduce(tensor: Tensor, dst: int = 0, op=ReduceOp.SUM, group=None, sync_op=True):
    """Only rank-dst's slice receives the reduction; others keep theirs."""
    g = _group_or_world(group)
    arr = tensor._value()
    _check_stacked(arr, g, "reduce")
    dst_local = _group_local(g, dst, "reduce", "dst")
    red = _make_reducer(op, g)

    def body(s):
        total = red(s)
        idx = jax.lax.axis_index(Group.AXIS)
        return jnp.where(idx == dst_local, total, s)

    out = _run("reduce", _smap(g, body), [tensor])
    tensor._set_data(out._value())
    return tensor


def scatter(tensor: Tensor, tensor_list=None, src: int = 0, group=None, sync_op=True):
    """Rank i receives chunk i of rank-src's [W, ...] payload.  Stacked input:
    [W(ranks), W(chunks), ...] (each rank holds its proposed chunk list; only
    src's row matters — reference ProcessGroup Scatter)."""
    g = _group_or_world(group)
    if tensor_list is not None:
        stacked = jnp.stack([t._value() for t in tensor_list], axis=0)
        stacked = jnp.broadcast_to(stacked[None], (g.nranks,) + stacked.shape)
        src_t = Tensor._wrap(stacked)
    else:
        src_t = tensor
    arr = src_t._value()
    _check_stacked(arr, g, "scatter")
    src_local = _group_local(g, src, "scatter", "src")

    def body(s):  # s: [1, W, ...] -> [1, ...] (keepdims keeps the rank dim)
        rows = jax.lax.all_gather(s[0], Group.AXIS)  # [W, W, ...]
        idx = jax.lax.axis_index(Group.AXIS)
        return jax.lax.dynamic_index_in_dim(rows[src_local], idx, 0)

    out = _run("scatter", _smap(g, body), [src_t])
    tensor._set_data(out._value())
    return tensor


def alltoall(in_tensor_list, out_tensor_list=None, group=None, sync_op=True):
    """out[i][j] = in[j][i].  Stacked form: [W, W, ...] -> [W, W, ...]
    (reference: collective.py:1769, global exchange for EP/MoE)."""
    g = _group_or_world(group)
    as_list = isinstance(in_tensor_list, list)
    if as_list:
        # each list entry is one chunk, itself rank-stacked [W, ...]; the
        # stacked payload is [W(ranks), W(chunks), ...]
        src = Tensor._wrap(jnp.stack([t._value() for t in in_tensor_list], axis=1))
    else:
        src = in_tensor_list
    arr = src._value()
    _check_stacked(arr, g, "alltoall")

    def body(s):  # s: [1, W, ...] -> my column across ranks
        rows = jax.lax.all_gather(s[0], Group.AXIS)  # [W, W, ...]
        idx = jax.lax.axis_index(Group.AXIS)
        return rows[:, idx][None]

    out = _run("alltoall", _smap(g, body), [src])
    if as_list and out_tensor_list is not None:
        out_tensor_list.clear()
        # list entry j is "what each rank received from rank j", itself
        # rank-stacked: entry_j[r] = in[j][r] = out[r][j]
        for j in range(g.nranks):
            out_tensor_list.append(Tensor._wrap(out._value()[:, j]))
        return out_tensor_list
    return out


def reduce_scatter(tensor: Tensor, tensor_or_list=None, op=ReduceOp.SUM,
                   group=None, sync_op=True):
    """Reduce [W, W*chunk...] then each rank keeps its chunk -> [W, chunk...]."""
    g = _group_or_world(group)
    src = tensor_or_list if tensor_or_list is not None else tensor
    if isinstance(src, list):
        src = Tensor._wrap(jnp.stack([t._value() for t in src], axis=0))
        src = Tensor._wrap(jnp.broadcast_to(src._value()[None],
                                            (g.nranks,) + src._value().shape))
    arr = src._value()
    _check_stacked(arr, g, "reduce_scatter")

    def body(s):  # s: [1, W, ...] -> [1, ...]
        total = jax.lax.psum(s[0], Group.AXIS)  # [W, ...]
        idx = jax.lax.axis_index(Group.AXIS)
        return jax.lax.dynamic_index_in_dim(total, idx, 0)

    out = _run("reduce_scatter", _smap(g, body), [src])
    if tensor_or_list is not None:
        tensor._set_data(out._value())
        return tensor
    return out


def barrier(group=None):
    """Synchronize: a zero psum everyone must reach (reference: barrier via
    dummy allreduce, ProcessGroupNCCL.cc:375)."""
    g = _group_or_world(group)
    x = jnp.zeros((g.nranks, 1), jnp.float32)
    out = _smap(g, lambda s: jax.lax.psum(s, Group.AXIS))(x)
    jax.block_until_ready(out)


def send(tensor, dst=0, group=None, sync_op=True):
    raise NotImplementedError(
        "Two-sided send/recv does not exist in single-controller SPMD; "
        "pipeline p2p uses collective-permute (see "
        "paddle_tpu.distributed.fleet.meta_parallel pipeline engine), and "
        "stacked p2p is available as distributed.ppermute().")


def recv(tensor, src=0, group=None, sync_op=True):
    raise NotImplementedError(
        "Two-sided send/recv does not exist in single-controller SPMD; use "
        "distributed.ppermute() (collective-permute) instead.")


def ppermute(tensor: Tensor, perm: Sequence, group=None) -> Tensor:
    """Collective permute over the group: out slice perm[i][1] = in slice
    perm[i][0] — the TPU-native p2p primitive replacing send_v2/recv_v2."""
    g = _group_or_world(group)
    arr = tensor._value()
    _check_stacked(arr, g, "ppermute")
    perm = [tuple(p) for p in perm]

    def body(s):
        return jax.lax.ppermute(s, Group.AXIS, perm)

    return _run("ppermute", _smap(g, body), [tensor])
