"""paddle_tpu.distributed — launch + eager collective API (reference:
`python/paddle/distributed/launch.py` and env contract
`distributed/utils.py:356-360`).

Multi-host bootstrap: `init_parallel_env` calls `jax.distributed.initialize`
over DCN (replacing the rank-0 TCP exchange of ncclUniqueId,
`imperative/nccl_context.cc:21-63`); within a host, all local TPU chips form
the default mesh.
"""
from __future__ import annotations

import os

import numpy as np

from ..parallel import env as penv


def get_rank():
    return penv.trainer_id()


def get_world_size():
    n = penv.trainer_num()
    return n


def init_parallel_env(backend="xla"):
    """Build the global 1-D data-parallel mesh over all visible devices.
    For multi-host (PADDLE_TRAINERS_NUM>1) also brings up jax.distributed
    over the endpoint list."""
    import jax

    nhosts = penv.trainer_num()
    if nhosts > 1 and penv.trainer_endpoints():
        coord = penv.trainer_endpoints()[0]
        # CPU backend: cross-process collectives (multihost
        # device_put, psum over DCN) need the gloo transport; the
        # default CPU backend refuses multiprocess computations.
        # Read the platform from config/env only — probing the
        # backend here would initialize it BEFORE distributed init.
        platforms = (getattr(jax.config, "jax_platforms", None)
                     or os.environ.get("JAX_PLATFORMS", ""))
        if platforms and "cpu" in str(platforms):
            jax.config.update(
                "jax_cpu_collectives_implementation", "gloo")
        # a second init_parallel_env() in one process is fine; a
        # coordinator that cannot be reached raises
        if not jax.distributed.is_initialized():
            jax.distributed.initialize(
                coordinator_address=coord,
                num_processes=nhosts,
                process_id=penv.trainer_id())
    from jax.sharding import Mesh

    devs = np.array(jax.devices())
    mesh = Mesh(devs, ("dp",))
    penv.set_global_mesh(mesh)
    penv.register_ring(0, "dp", devs.size)
    from ..fluid.dygraph.parallel import ParallelEnv

    return ParallelEnv()


def _mesh_or_none():
    return penv.global_mesh()


def _eager_collective(x, fn_name, **kw):
    """Apply a collective to a global array sharded over the dp mesh."""
    import jax

    mesh = _mesh_or_none()
    val = x._value() if hasattr(x, "_value") else x
    if mesh is None:
        return x
    from jax.sharding import PartitionSpec as P

    axes = {a: mesh.shape[a] for a in mesh.axis_names}

    def inner(v):
        with penv.collective_scope(axes):
            from .. import ops as ops_lib

            out = ops_lib.run_op(fn_name, {"X": [v]}, kw)
            return out["Out"][0]

    from ..parallel.env import shard_map_compat

    smapped = shard_map_compat(inner, mesh=mesh, in_specs=P("dp"),
                               out_specs=P("dp"), check_vma=False)
    out = jax.jit(smapped)(val)
    if hasattr(x, "_assign_raw"):
        x._assign_raw(out)
        return x
    return out


def all_reduce(tensor, op="sum", group=0):
    return _eager_collective(tensor, "c_allreduce_" + op, ring_id=group)


def broadcast(tensor, src=0, group=0):
    return _eager_collective(tensor, "c_broadcast", ring_id=group, root=src)


def all_gather(tensor_list, tensor, group=0):
    out = _eager_collective(tensor, "c_allgather", ring_id=group)
    tensor_list.append(out)
    return tensor_list


def reduce_scatter(tensor, group=0):
    return _eager_collective(tensor, "c_reducescatter", ring_id=group)


def barrier(group=0):
    pass


from . import faults  # noqa: F401,E402
from . import launch  # noqa: F401,E402
from .launch import ParallelEnvArgs  # noqa: F401,E402
from .sharded_checkpoint import ShardedCheckpointManager  # noqa: F401,E402


def spawn(func, args=(), nprocs=-1, join=True, daemon=False, **options):
    """Single-host multi-chip needs no process spawn on TPU (one process
    drives all local chips through the mesh); run func once."""
    init_parallel_env()
    func(*args)
