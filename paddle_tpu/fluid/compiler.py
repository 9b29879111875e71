"""CompiledProgram (reference: `python/paddle/fluid/compiler.py:87-310`).

`with_data_parallel` marks the program for SPMD lowering over the device
mesh: the reference's per-device graph clones + AllReduceOpHandles
(multi_devices_graph_pass.cc) collapse into one shard_map'd XLA computation
(SURVEY.md §3B TPU mapping).
"""
from __future__ import annotations


class BuildStrategy:
    """Accepted for API compatibility; most knobs are XLA's job now."""

    class ReduceStrategy:
        AllReduce = 0
        Reduce = 1

    class GradientScaleStrategy:
        CoeffNumDevice = 0
        One = 1
        Customized = 2

    def __init__(self):
        self.reduce_strategy = BuildStrategy.ReduceStrategy.AllReduce
        self.gradient_scale_strategy = \
            BuildStrategy.GradientScaleStrategy.CoeffNumDevice
        self.memory_optimize = None
        self.enable_inplace = None
        self.fuse_all_reduce_ops = None
        # accepted, changes nothing: XLA fuses the per-parameter update
        # loops itself (a coalesced flat update vector of a 133 M
        # parameter model is what the TPU compiler refused)
        self.fuse_all_optimizer_ops = None
        self.fuse_elewise_add_act_ops = False
        self.fuse_bn_act_ops = False
        self.nccl_comm_num = 1
        self.num_trainers = 1
        self.trainer_id = 0
        self.use_hierarchical_allreduce = False
        self.hierarchical_allreduce_inter_nranks = 0
        self.sync_batch_norm = False
        self.enable_sequential_execution = False
        self.remove_unnecessary_lock = True


class ExecutionStrategy:
    def __init__(self):
        self.num_threads = 0
        self.num_iteration_per_drop_scope = 1
        self.num_iteration_per_run = 1
        self.use_thread_barrier = False


class CompiledProgram:
    def __init__(self, program_or_graph, build_strategy=None):
        self._program = program_or_graph
        self._build_strategy = build_strategy or BuildStrategy()
        self._is_data_parallel = False

    def with_data_parallel(self, loss_name=None, build_strategy=None,
                           exec_strategy=None, share_vars_from=None,
                           places=None):
        self._is_data_parallel = True
        if build_strategy is not None:
            self._build_strategy = build_strategy
        p = self._program
        p._data_parallel = True
        if places is not None and p._mesh is None:
            import numpy as np
            from jax.sharding import Mesh

            devs = np.array([pl.jax_device() for pl in places])
            p._mesh = Mesh(devs, (p._dp_axis,))
        return self

    def with_inference_optimize(self, config):
        return self

    def _unwrap(self):
        return self._program


CompiledProgram.__doc__ = (CompiledProgram.__doc__ or "") + \
    "\nReference: compiler.py:87 (CompiledProgram), :160 (with_data_parallel)"
