"""Collective communication API (reference: python/paddle/distributed/
collective.py + communication/*).

TPU-native: collectives are XLA ops over mesh axes (psum/all_gather/
ppermute/all_to_all riding ICI), not NCCL calls. Inside shard_map the
paddle API maps 1:1 onto lax collectives via the `group` → axis-name
mapping. Outside SPMD regions (pure eager, single process) they act on
replicated values (identity semantics), matching world_size==1 behavior.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from .._core.tensor import Tensor, apply, unwrap
from . import env


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


class Group:
    """Process-group parity object: names a mesh axis."""

    def __init__(self, axis_name=None, ranks=None, id=0):
        self.axis_name = axis_name
        self.ranks = ranks or []
        self.id = id

    @property
    def nranks(self):
        if self.axis_name is None:
            return env.get_world_size()
        return len(self.ranks) if self.ranks else env.device_count()

    @property
    def world_size(self):
        return self.nranks

    @property
    def rank(self):
        return env.get_rank()

    def get_group_rank(self, rank):
        return rank

    @property
    def process_group(self):
        return self


_default_group = Group()


def new_group(ranks=None, backend=None, timeout=None, axis_name=None):
    return Group(axis_name=axis_name, ranks=ranks, id=np.random.randint(1 << 30))


def get_group(gid=0):
    return _default_group


def _axis(group):
    if group is None:
        return None
    if isinstance(group, str):
        return group
    return getattr(group, "axis_name", None)


def _in_spmd(x):
    return isinstance(x, jax.core.Tracer)


def _eager_mesh_axes(raw, ax):
    """For a concrete array: (mesh, spec, axes-to-reduce) if it carries a
    NamedSharding whose mesh can serve the requested communication, else
    (None, None, ()) for the degenerate single-participant case. Raises
    when communication was explicitly requested but cannot happen —
    silently returning the input would corrupt multi-device math."""
    from jax.sharding import NamedSharding

    sharding = getattr(raw, "sharding", None)
    if isinstance(sharding, NamedSharding):
        mesh = sharding.mesh
        spec = sharding.spec
        used = set()
        for entry in spec:
            if entry is None:
                continue
            for a in (entry if isinstance(entry, tuple) else (entry,)):
                used.add(a)
        if ax is not None:
            if ax not in mesh.shape:
                raise RuntimeError(
                    f"collective over axis {ax!r}: tensor's mesh has axes "
                    f"{tuple(mesh.shape)}; cannot communicate over a "
                    f"nonexistent axis")
            axes = (ax,) if ax in used else ()
            if mesh.shape[ax] > 1 and ax not in used:
                # replicated over the axis: reduction is size * value for
                # SUM — still well-defined; treat as all-shards-equal
                axes = (ax,)
            return mesh, spec, axes
        return mesh, spec, tuple(a for a in mesh.axis_names if a in used)
    if ax is not None:
        raise RuntimeError(
            f"collective over axis {ax!r} called on an unsharded tensor "
            f"outside shard_map: no mesh to communicate over. Place the "
            f"tensor with a NamedSharding or call inside shard_map/jit.")
    if env.get_world_size() > 1:
        raise RuntimeError(
            "collective on an unsharded tensor in a multi-process run: "
            "cross-host eager collectives are not supported; use mesh-"
            "sharded arrays or shard_map.")
    return None, None, ()


def _drop_axes(spec, axes):
    """PartitionSpec with `axes` removed (those dims become replicated)."""
    from jax.sharding import PartitionSpec as P
    out = []
    for entry in spec:
        if entry is None:
            out.append(None)
        elif isinstance(entry, tuple):
            kept = tuple(a for a in entry if a not in axes)
            out.append(kept if kept else None)
        else:
            out.append(None if entry in axes else entry)
    return P(*out)


def _eager_psum(raw, op, mesh, spec, axes):
    """Real reduction of a sharded eager array: each shard is one
    participant (paddle rank semantics); result is the reduced shard,
    replicated over the reduced axes."""
    fn = {ReduceOp.SUM: lax.psum, ReduceOp.MAX: lax.pmax,
          ReduceOp.MIN: lax.pmin, ReduceOp.AVG: lax.pmean}.get(op)
    if fn is None:
        raise NotImplementedError(
            f"all_reduce op {op!r} has no XLA collective mapping "
            f"(SUM/MAX/MIN/AVG supported)")
    reduced = jax.shard_map(lambda s: fn(s, axes), mesh=mesh,
                            in_specs=(spec,),
                            out_specs=_drop_axes(spec, axes),
                            check_vma=False)(raw)
    return reduced


def all_reduce(tensor, op=ReduceOp.SUM, group=None, sync_op=True):
    ax = _axis(group)
    raw = unwrap(tensor)
    if _in_spmd(raw):
        if ax is None:
            return tensor  # traced but no axis: replicated value
        fn = {ReduceOp.SUM: lax.psum, ReduceOp.MAX: lax.pmax,
              ReduceOp.MIN: lax.pmin,
              ReduceOp.AVG: lambda v, a: lax.pmean(v, a)}.get(op)
        if fn is None:
            raise NotImplementedError(
                f"all_reduce op {op!r} has no XLA collective mapping "
                f"(SUM/MAX/MIN/AVG supported)")
        out = fn(raw, ax)
        if isinstance(tensor, Tensor):
            tensor._replace(out)
            return tensor
        return out
    mesh, spec, axes = _eager_mesh_axes(raw, ax)
    if mesh is None or not axes:
        return tensor  # world of one participant: reduction is identity
    out = _eager_psum(raw, op, mesh, spec, axes)
    if isinstance(tensor, Tensor):
        tensor._replace(out)
        return tensor
    return out


def _resolve_group_axis(mesh, spec, axes, ax, opname):
    """The single mesh axis a collective communicates over, or raise —
    multi-axis layouts need an explicit group and a dim sharded by
    exactly that axis (contiguous split is wrong otherwise)."""
    a = ax if ax is not None else (axes[0] if len(axes) == 1 else None)
    if a is None:
        raise RuntimeError(
            f"{opname}: tensor is sharded over multiple axes {axes}; "
            f"pass group=<axis name> to pick the group")
    dim = _sharded_dim(spec, (a,))
    if dim is not None:
        entry = spec[dim]
        ents = entry if isinstance(entry, tuple) else (entry,)
        if tuple(e for e in ents if e is not None) != (a,):
            raise RuntimeError(
                f"{opname} over {a!r}: dim {dim} is sharded over {ents}; "
                f"participant shards are not contiguous along a "
                f"multi-axis dim")
    return a, dim


def _sharded_dim(spec, axes):
    """First tensor dim partitioned over one of `axes` (None if none)."""
    for i, entry in enumerate(spec):
        ents = entry if isinstance(entry, tuple) else (entry,)
        if any(a in axes for a in ents if a is not None):
            return i
    return None


def all_gather(tensor_list, tensor, group=None, sync_op=True, axis=0):
    ax = _axis(group)
    raw = unwrap(tensor)
    if _in_spmd(raw):
        if ax is None:
            if isinstance(tensor_list, list):
                tensor_list.append(tensor)
                return tensor_list
            return tensor
        out = lax.all_gather(raw, ax)
        if isinstance(tensor_list, list):
            n = out.shape[0]
            tensor_list.extend(Tensor(out[i]) for i in range(n))
            return tensor_list
        return out
    mesh, spec, axes = _eager_mesh_axes(raw, ax)
    if mesh is not None and axes and isinstance(tensor_list, list):
        # each participant's tensor is its shard; replicated-over-axis
        # tensors contribute n identical copies (paddle: every rank's copy)
        a, dim = _resolve_group_axis(mesh, spec, axes, ax, "all_gather")
        n = mesh.shape[a]
        if dim is not None:
            pieces = jnp.split(raw, n, axis=dim)
            tensor_list.extend(Tensor(p) for p in pieces)
        else:
            tensor_list.extend(Tensor(raw) for _ in range(n))
        return tensor_list
    if isinstance(tensor_list, list):
        tensor_list.append(tensor.clone() if isinstance(tensor, Tensor) else tensor)
        return tensor_list
    return tensor


def all_gather_object(object_list, obj, group=None):
    object_list.append(obj)
    return object_list


def reduce_scatter(tensor, tensor_list=None, op=ReduceOp.SUM, group=None,
                   sync_op=True):
    ax = _axis(group)
    raw = unwrap(tensor)
    if _in_spmd(raw):
        if ax is None:
            return tensor
        out = lax.psum_scatter(raw, ax, scatter_dimension=0, tiled=True)
        if isinstance(tensor, Tensor):
            tensor._replace(out)
            return tensor
        return out
    mesh, spec, axes = _eager_mesh_axes(raw, ax)
    if mesh is not None and axes:
        a, dim = _resolve_group_axis(mesh, spec, axes, ax, "reduce_scatter")
        if dim != 0:
            raise NotImplementedError(
                f"eager reduce_scatter needs dim 0 sharded over the group "
                f"axis {a!r} (got sharded dim {dim}); out_specs for other "
                f"layouts would mislabel the scattered result")
        out = jax.shard_map(
            lambda s: lax.psum_scatter(s, a, scatter_dimension=0, tiled=True),
            mesh=mesh, in_specs=(spec,), out_specs=spec,
            check_vma=False)(raw)
        if isinstance(tensor, Tensor):
            tensor._replace(out)
            return tensor
        return out
    return tensor


def broadcast(tensor, src=0, group=None, sync_op=True):
    ax = _axis(group)
    raw = unwrap(tensor)
    if _in_spmd(raw):
        return tensor  # inside shard_map: value already per-device
    mesh, spec, axes = _eager_mesh_axes(raw, ax)
    if mesh is not None and axes:
        # every participant's shard becomes src's shard, along ONE group
        # axis (src indexes ranks of that axis)
        a, dim = _resolve_group_axis(mesh, spec, axes, ax, "broadcast")
        n = mesh.shape[a]
        if dim is not None:
            if not 0 <= src < n:
                raise ValueError(
                    f"broadcast src={src} out of range for group axis "
                    f"{a!r} of size {n}")
            piece = jnp.split(raw, n, axis=dim)[src]
            out = jnp.concatenate([piece] * n, axis=dim)
            out = jax.device_put(out, raw.sharding)
            if isinstance(tensor, Tensor):
                tensor._replace(out)
                return tensor
            return out
    return tensor  # replicated over the group: already src's value


def broadcast_object_list(object_list, src=0, group=None):
    return object_list


def scatter(tensor, tensor_list=None, src=0, group=None, sync_op=True):
    if tensor_list:
        v = tensor_list[env.get_rank() if env.get_rank() < len(tensor_list) else 0]
        if isinstance(tensor, Tensor):
            tensor._replace(unwrap(v))
    return tensor


def alltoall(in_tensor_list, out_tensor_list=None, group=None, sync_op=True):
    ax = _axis(group)
    if isinstance(in_tensor_list, Tensor) or (
            not isinstance(in_tensor_list, (list, tuple))):
        raw = unwrap(in_tensor_list)
        if ax is not None and _in_spmd(raw):
            n = lax.axis_size(ax)
            out = lax.all_to_all(raw.reshape((n, -1) + raw.shape[1:]), ax, 0, 0,
                                 tiled=False)
            return Tensor(out.reshape(raw.shape)) if isinstance(in_tensor_list,
                                                                Tensor) else out
        return in_tensor_list
    if out_tensor_list is not None:
        out_tensor_list.extend(t.clone() for t in in_tensor_list)
        return out_tensor_list
    return list(in_tensor_list)


def alltoall_single(in_tensor, out_tensor=None, in_split_sizes=None,
                    out_split_sizes=None, group=None, sync_op=True):
    ax = _axis(group)
    raw = unwrap(in_tensor)
    if ax is not None and _in_spmd(raw):
        n = lax.axis_size(ax)
        out = lax.all_to_all(raw, ax, split_axis=0, concat_axis=0, tiled=True)
        if out_tensor is not None and isinstance(out_tensor, Tensor):
            out_tensor._replace(out)
            return out_tensor
        return out
    if out_tensor is not None and isinstance(out_tensor, Tensor):
        out_tensor._replace(raw)
        return out_tensor
    return in_tensor


def send(tensor, dst=0, group=None, sync_op=True):
    raise RuntimeError("point-to-point send/recv outside shard_map is not a "
                       "TPU primitive; use ppermute inside shard_map "
                       "(paddle_tpu.distributed.p2p_ppermute)")


def recv(tensor, src=0, group=None, sync_op=True):
    raise RuntimeError("use ppermute inside shard_map (p2p_ppermute)")


def p2p_ppermute(x, perm, axis_name):
    """Ring/point-to-point transfer inside shard_map: lax.ppermute."""
    return lax.ppermute(unwrap(x), axis_name, perm)


def barrier(group=None):
    # blocking IS the contract of a barrier
    (jax.device_put(0) + 0).block_until_ready()  # tpulint: disable=TPL005 -- explicit barrier API


def reduce(tensor, dst=0, op=ReduceOp.SUM, group=None, sync_op=True):
    return all_reduce(tensor, op, group, sync_op)


def get_backend(group=None):
    return "xla"  # ICI/DCN via XLA collectives; NCCL does not exist here


def is_available():
    return True


def destroy_process_group(group=None):
    pass


def wait(tensor, group=None, use_calc_stream=True):
    raw = unwrap(tensor)
    if hasattr(raw, "block_until_ready"):
        raw.block_until_ready()  # tpulint: disable=TPL005 -- comm.wait() is an explicit fence
    return tensor
