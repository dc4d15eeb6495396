"""Distributed environment state (reference: python/paddle/distributed/
parallel.py env + fleet topology).

Single-controller JAX model: one python process drives all local TPU
chips; multi-host uses jax.distributed. "rank" = process index (for data
sharding); intra-process parallelism is expressed on the global mesh.
"""
from __future__ import annotations

import os
import threading

import jax

_state = threading.local()
_global_mesh = None
_hybrid_topology = None


def init_parallel_env():
    """reference: paddle.distributed.init_parallel_env. Multi-host init is
    driven by env vars set by paddle_tpu.distributed.launch.

    jax.distributed.initialize() only auto-detects the coordinator on known
    cluster environments (GKE/Cloud TPU metadata); on a bare launch the
    JAX_NUM_PROCESSES / JAX_PROCESS_ID vars our launcher exports are NOT
    read by jax itself, so pass them explicitly. A failed rendezvous must
    raise: silently continuing would run N independent single-process
    trainers that all see the same data shard and produce wrong results.
    """
    coord = os.environ.get("JAX_COORDINATOR_ADDRESS")
    # NB: must not call jax.process_count() (or anything else that
    # initializes the XLA backend) before jax.distributed.initialize —
    # initialize() refuses to run after backend init, which would make
    # every real rendezvous fail. Probe the distributed client directly.
    try:
        already = jax.distributed.is_initialized()
    except Exception:
        already = False
    if coord and not already:
        nproc = os.environ.get("JAX_NUM_PROCESSES")
        pid = os.environ.get("JAX_PROCESS_ID")
        try:
            if nproc is not None and pid is not None:
                jax.distributed.initialize(coordinator_address=coord,
                                           num_processes=int(nproc),
                                           process_id=int(pid))
            else:
                jax.distributed.initialize(coordinator_address=coord)
        except Exception as e:
            raise RuntimeError(
                f"init_parallel_env: jax.distributed.initialize failed "
                f"(coordinator={coord}, num_processes={nproc}, "
                f"process_id={pid}). Refusing to continue as a "
                f"single-process trainer inside a multi-host launch.") from e
    return get_rank()


def get_rank(group=None):
    try:
        return jax.process_index()
    except Exception:
        return 0


def get_world_size(group=None):
    try:
        return jax.process_count()
    except Exception:
        return 1


def is_initialized():
    return True


def device_count():
    return jax.device_count()


def local_device_count():
    return jax.local_device_count()


def set_global_mesh(mesh):
    global _global_mesh
    _global_mesh = mesh


def get_global_mesh():
    return _global_mesh


def set_topology(topo):
    global _hybrid_topology
    _hybrid_topology = topo


def get_topology():
    return _hybrid_topology


def inside_shard_map():
    """True when executing under shard_map manual axes (collectives
    with axis names are legal)."""
    return bool(jax.sharding.get_abstract_mesh().manual_axes)
