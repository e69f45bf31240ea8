"""Collective communication API.

Reference counterpart: python/paddle/distributed/collective.py +
operators/collective/c_allreduce_op.h:123-158 (ring_id -> NCCL comm -> stream
launch). TPU-native: a collective is a jitted shard_map over a mesh axis —
XLA emits the ICI all-reduce; there are no rings, ids, or stream syncs.

Single-controller semantics note (documented divergence): the reference runs
one process per device, each holding its local tensor. Here one process sees
global arrays; collectives therefore take the mesh axis to reduce over and
operate on the array's shards. On fully-replicated input they are identity.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ..parallel.mesh import default_mesh, get_mesh

P = PartitionSpec


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"


def get_rank():
    return jax.process_index()


def get_world_size():
    return jax.process_count()


def _value(x):
    from ..dygraph.tracer import Tensor
    if isinstance(x, Tensor):
        return x.value, x
    return jnp.asarray(x), None


@functools.lru_cache(maxsize=64)
def _allreduce_fn(mesh, axis, op):
    if op == "prod":
        # no pprod primitive: gather shards then reduce on each device
        def body(v):
            g = jax.lax.all_gather(v, axis_name=axis)
            return jnp.prod(g, axis=0)
    else:
        red = {"sum": functools.partial(jax.lax.psum, axis_name=axis),
               "max": functools.partial(jax.lax.pmax, axis_name=axis),
               "min": functools.partial(jax.lax.pmin, axis_name=axis)}[op]

        def body(v):
            return red(v)

    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P(axis),
                                 out_specs=P(), check_vma=False))


def all_reduce(tensor, op=ReduceOp.SUM, group=None, sync_op=True, axis="dp"):
    """Reduce across the shards of `tensor` along the mesh axis.

    If the tensor is sharded on `axis` over dim 0, the result is the reduction
    of the per-shard values (matching the per-rank semantics of the
    reference); replicated tensors pass through unchanged.
    """
    val, wrapper = _value(tensor)
    mesh = get_mesh() or default_mesh()
    if axis not in mesh.shape or mesh.shape[axis] == 1:
        return tensor
    sh = getattr(val, "sharding", None)
    is_sharded = sh is not None and not sh.is_fully_replicated
    if not is_sharded:
        return tensor
    out = _allreduce_fn(mesh, axis, op)(val)
    if wrapper is not None:
        wrapper.value = out
        return wrapper
    return out


def all_gather(tensor_list, tensor, group=None, axis="dp"):
    """Gather shards along dim 0 (reference c_allgather)."""
    val, _ = _value(tensor)
    mesh = get_mesh() or default_mesh()
    n = mesh.shape.get(axis, 1)
    from ..dygraph.tracer import Tensor
    sh = getattr(val, "sharding", None)
    if sh is None or sh.is_fully_replicated or n == 1:
        pieces = [val] * max(n, 1)
    else:
        # shards along dim 0 in axis order
        gathered = jax.device_get(val)
        pieces = np.split(np.asarray(gathered), n, axis=0)
    if tensor_list is not None:
        tensor_list.extend(Tensor(jnp.asarray(p)) for p in pieces)
    return pieces


def broadcast(tensor, src=0, group=None):
    """Replicate tensor to all devices (reference c_broadcast). Under a
    single controller, setting a replicated sharding IS the broadcast."""
    val, wrapper = _value(tensor)
    mesh = get_mesh() or default_mesh()
    out = jax.device_put(val, NamedSharding(mesh, P()))
    if wrapper is not None:
        wrapper.value = out
        return wrapper
    return out


def reduce(tensor, dst=0, op=ReduceOp.SUM, group=None):
    return all_reduce(tensor, op, group)


def scatter(tensor, tensor_list=None, src=0, group=None, axis="dp"):
    """Shard dim 0 over the axis (reference c_scatter)."""
    val, wrapper = _value(tensor)
    mesh = get_mesh() or default_mesh()
    out = jax.device_put(val, NamedSharding(mesh, P(axis)))
    if wrapper is not None:
        wrapper.value = out
        return wrapper
    return out


def barrier(group=None):
    """Device-step barrier. XLA programs are ordered per device; a host-level
    sync is 'wait for everything enqueued'."""
    (jnp.zeros(()) + 0).block_until_ready()


def split_batch(array, axis="dp"):
    """Shard a host batch over the data axis — the dygraph DataParallel feed
    path (replaces reference scatter + per-process batching)."""
    mesh = get_mesh() or default_mesh()
    return jax.device_put(jnp.asarray(array), NamedSharding(mesh, P(axis)))
