"""Inference engine: an AnalysisPredictor-shaped API over compiled XLA
executables.

Reference: `paddle/fluid/inference/api/analysis_predictor.cc`
(CreatePaddlePredictor:1012, PrepareProgram:184, Run:289,
OptimizeInferenceProgram:498) and `paddle_inference_api.h` (Config /
Predictor / Tensor zero-copy surface).

TPU-native: the reference's analysis passes (fusions, TRT/Lite subgraph
capture) are XLA's job — the loaded program lowers to ONE compiled
computation cached by input shapes; "zero-copy" tensors hold numpy on the
host side and jax device arrays after run. MKLDNN/TensorRT/GPU knobs are
accepted as no-ops so reference configs port unchanged.

This is the per-call, load-and-run surface. For PERSISTENT serving —
continuous batching across concurrent requests, a paged KV cache, and
AOT-warmed decode-step buckets — see ``paddle_tpu.serving``
(serving/README.md); `Predictor.warmup(shapes=...)` pre-compiles this
predictor's own input-shape buckets through the same persistent
compile cache (JAX_COMPILATION_CACHE_DIR) so a serving process
restart answers its first request without paying XLA compilation.
"""
from __future__ import annotations

import os
from typing import Dict, List, Optional

import numpy as np

__all__ = [
    "Config", "AnalysisConfig", "Predictor", "Tensor",
    "create_predictor", "create_paddle_predictor", "PlaceType",
]


class PlaceType:
    kHost = CPU = 0
    kGPU = GPU = 1
    kTPU = TPU = 2


class Config:
    """Reference: AnalysisConfig (inference/api/paddle_analysis_config.h).

    Accepts both the dir form ``Config(model_dir)`` and the two-file form
    ``Config(prog_file, params_file)``.
    """

    def __init__(self, model_dir: Optional[str] = None,
                 params_path: Optional[str] = None):
        if model_dir is not None and params_path is not None:
            self._model_dir = os.path.dirname(model_dir) or "."
            self._prog_file = os.path.basename(model_dir)
            # keep the full params path: it may live in a different
            # directory than the program file (os.path.join in the loader
            # respects an absolute second component)
            self._params_file = os.path.abspath(params_path)
        else:
            self._model_dir = model_dir
            self._prog_file = None
            self._params_file = None
        self._use_tpu = True
        self._ir_optim = True
        self._enable_memory_optim = True
        self._cpu_math_threads = 1

    # -- model location ----------------------------------------------------
    def set_model(self, model_dir: str, params_path: Optional[str] = None):
        # only update the model location (reference AnalysisConfig.SetModel);
        # previously configured knobs (ir_optim, ...) must survive
        if params_path is not None:
            self._model_dir = None
            self._prog_file = model_dir
            self._params_file = params_path
        else:
            self._model_dir = model_dir
            self._prog_file = None
            self._params_file = None

    def model_dir(self) -> Optional[str]:
        return self._model_dir

    def prog_file(self) -> Optional[str]:
        return self._prog_file

    def params_file(self) -> Optional[str]:
        return self._params_file

    # -- device / optimization knobs (reference API kept; XLA makes most
    # of them no-ops on TPU) ----------------------------------------------
    def enable_use_gpu(self, memory_pool_init_size_mb=100, device_id=0):
        pass

    def disable_gpu(self):
        pass

    def use_gpu(self) -> bool:
        return False

    def enable_xpu(self, *a, **k):
        pass

    def switch_ir_optim(self, x: bool = True):
        self._ir_optim = x

    def ir_optim(self) -> bool:
        return self._ir_optim

    def switch_use_feed_fetch_ops(self, x: bool = False):
        pass

    def switch_specify_input_names(self, x: bool = True):
        pass

    def enable_memory_optim(self):
        self._enable_memory_optim = True

    def enable_mkldnn(self):
        pass

    def enable_tensorrt_engine(self, *a, **k):
        pass

    def tensorrt_engine_enabled(self) -> bool:
        return False

    def set_cpu_math_library_num_threads(self, n: int):
        self._cpu_math_threads = n

    def pass_builder(self):
        """Analysis pass control (reference:
        analysis_predictor.cc:498 + pass_builder.h PaddlePassBuilder).
        TPU-native: graph fusion/layout passes belong to XLA, so the
        builder lists the LOGICAL pipeline stages this runtime applies
        around the compiler; deleting a pass disables the matching
        stage where one exists (ir_optim gates XLA optimization
        itself via switch_ir_optim)."""
        if not hasattr(self, "_pass_builder"):
            self._pass_builder = PassStrategy()
        return self._pass_builder

    def enable_profile(self):
        pass

    def disable_glog_info(self):
        pass


AnalysisConfig = Config  # legacy name (reference: paddle_analysis_config.h)


class PassStrategy:
    """Reference: pass_builder.h — an ordered, editable pass list.
    Stages marked (xla) are owned by the compiler (they run iff
    ir_optim is on — switch_ir_optim is the real toggle for them).
    Two passes have REAL individual delete semantics:
    `memory_optimize_pass` (disables buffer donation) and
    `conv_bn_fuse_pass` (disables the load-time weight fold). Deleting
    any other (compiler-owned) pass warns that it has no individual
    effect."""

    _RUNTIME = {"memory_optimize_pass", "conv_bn_fuse_pass"}
    _DEFAULT = [
        "infer_clean_graph_pass",          # feed/fetch pruning (load)
        "conv_bn_fuse_pass",               # weight fold (load; real)
        "constant_folding_pass",           # (xla)
        "common_subexpression_elimination",  # (xla)
        "operator_fusion_pass",            # (xla)
        "layout_assignment_pass",          # (xla)
        "memory_optimize_pass",            # buffer donation (runtime)
    ]

    def __init__(self):
        self._passes = list(self._DEFAULT)

    def all_passes(self):
        return list(self._passes)

    def delete_pass(self, name):
        if name in self._passes and name not in self._RUNTIME:
            import warnings

            warnings.warn(
                "pass %r is owned by the XLA pipeline (or applied at "
                "model load); deleting it only edits the report — use "
                "switch_ir_optim(False) to disable compiler "
                "optimization as a whole" % (name,))
        self._passes = [p for p in self._passes if p != name]

    def insert_pass(self, idx, name):
        self._passes.insert(int(idx), str(name))

    def append_pass(self, name):
        self._passes.append(str(name))

    def memory_optim_enabled(self):
        return "memory_optimize_pass" in self._passes


class Tensor:
    """Zero-copy input/output handle (reference: ZeroCopyTensor,
    inference/api/details/zero_copy_tensor.cc)."""

    def __init__(self, name: str, predictor: "Predictor", is_input: bool):
        self._name = name
        self._pred = predictor
        self._is_input = is_input

    def name(self) -> str:
        return self._name

    def reshape(self, shape) -> None:
        if not self._is_input:
            raise RuntimeError("cannot reshape an output tensor")
        cur = self._pred._inputs.get(self._name)
        self._pred._inputs[self._name] = (
            np.zeros(shape, cur.dtype if cur is not None else "float32"))

    def copy_from_cpu(self, data: np.ndarray) -> None:
        if not self._is_input:
            raise RuntimeError("cannot write an output tensor")
        self._pred._inputs[self._name] = np.ascontiguousarray(data)

    def copy_to_cpu(self) -> np.ndarray:
        out = self._pred._outputs.get(self._name)
        if out is None:
            raise RuntimeError(
                "output %r not available — call run() first" % self._name)
        return np.asarray(out)

    def shape(self) -> List[int]:
        if self._is_input:
            a = self._pred._inputs.get(self._name)
        else:
            a = self._pred._outputs.get(self._name)
        return list(a.shape) if a is not None else []

    # paddle-2.x tensor handle aliases
    def copy_from_cpu_bind(self, data):
        self.copy_from_cpu(data)


class Predictor:
    """Reference: AnalysisPredictor. Loads the saved inference program,
    lowers it through the same block compiler as the Executor, and caches
    the XLA executable per input-shape signature."""

    def __init__(self, config: Config):
        import paddle_tpu.fluid as fluid
        from paddle_tpu.fluid import framework

        self._config = config
        self._exe = fluid.Executor()
        # load under a private scope so predictors don't collide
        from paddle_tpu.core.scope import Scope, scope_guard

        self._scope = Scope()
        with scope_guard(self._scope):
            prog, feed_names, fetch_targets = fluid.io.load_inference_model(
                config.model_dir(), self._exe,
                model_filename=config.prog_file(),
                params_filename=config.params_file())
        self._program = prog
        self._conv_bn_fused = 0
        if config.ir_optim() and "conv_bn_fuse_pass" in \
                config.pass_builder().all_passes():
            from .passes import conv_bn_fuse

            self._conv_bn_fused = conv_bn_fuse(
                prog, self._scope,
                keep_names=[t.name for t in fetch_targets])
        self._feed_names = list(feed_names)
        self._fetch_targets = fetch_targets
        self._fetch_names = [t.name for t in fetch_targets]
        self._inputs: Dict[str, np.ndarray] = {}
        self._outputs: Dict[str, np.ndarray] = {}

    # -- reference Predictor surface --------------------------------------
    def get_input_names(self) -> List[str]:
        return list(self._feed_names)

    def get_output_names(self) -> List[str]:
        return list(self._fetch_names)

    def get_input_handle(self, name: str) -> Tensor:
        if name not in self._feed_names:
            raise KeyError(name)
        return Tensor(name, self, is_input=True)

    def get_output_handle(self, name: str) -> Tensor:
        if name not in self._fetch_names:
            raise KeyError(name)
        return Tensor(name, self, is_input=False)

    # legacy ZeroCopy names
    get_input_tensor = get_input_handle
    get_output_tensor = get_output_handle

    def run(self, inputs: Optional[List[np.ndarray]] = None):
        """With ``inputs``: positional legacy mode, returns outputs list.
        Without: zero-copy mode over the bound input handles."""
        if inputs is not None:
            for name, arr in zip(self._feed_names, inputs):
                self._inputs[name] = np.asarray(arr)
        missing = [n for n in self._feed_names if n not in self._inputs]
        if missing:
            raise RuntimeError("inputs %s not set" % missing)
        from paddle_tpu.core.scope import scope_guard

        import contextlib

        import jax

        from paddle_tpu.utils.flags import get_flags, set_flags

        # switch_ir_optim(False): run unoptimized — op-by-op eager
        # dispatch instead of one fused XLA executable (the reference's
        # no-IR-passes NaiveExecutor path, analysis_predictor.cc:498)
        no_opt = (jax.disable_jit() if not self._config.ir_optim()
                  else contextlib.nullcontext())
        # memory_optimize_pass deleted (or memory optim disabled):
        # buffer donation off for this predictor's compilations
        donate_off = (
            not self._config.pass_builder().memory_optim_enabled()
            or not getattr(self._config, "_enable_memory_optim", True))
        flag = "FLAGS_tpu_donate_buffers"
        prev = get_flags([flag])[flag]
        try:
            if donate_off:
                set_flags({flag: False})
            with scope_guard(self._scope), no_opt:
                outs = self._exe.run(self._program,
                                     feed=dict(self._inputs),
                                     fetch_list=self._fetch_names)
        finally:
            if donate_off:
                set_flags({flag: prev})
        self._outputs = dict(zip(self._fetch_names,
                                 [np.asarray(o) for o in outs]))
        if inputs is not None:
            return [self._outputs[n] for n in self._fetch_names]
        return True

    # legacy alias
    zero_copy_run = run

    def warmup(self, shapes, meshes=None, background=False):
        """AOT-compile this predictor's program for the given
        input-shape buckets BEFORE traffic (PR 13 machinery:
        `Executor.warmup` + the JAX_COMPILATION_CACHE_DIR persistent
        tier). `shapes` is a list of dicts mapping input name ->
        concrete shape tuple / example array / ShapeDtypeStruct; each
        bucket executes one discarded run on state copies, so the
        first real request of that shape dispatches with
        compile_ms ~ 0 — and a RESTARTED serving process warms
        all-hit from the persistent tier. Returns the warmup report
        ({"compiled": [...], "cached": [...], "skipped": [...]}), or
        the background Thread when background=True."""
        from paddle_tpu.core.scope import scope_guard

        with scope_guard(self._scope):
            return self._exe.warmup(
                self._program, shapes, meshes=meshes,
                fetch_list=self._fetch_targets, scope=self._scope,
                background=background)

    def clear_intermediate_tensor(self):
        pass

    def try_shrink_memory(self):
        pass

    def get_optimization_report(self) -> Dict:
        """Analysis report (reference: the AnalysisConfig summary +
        argument dump, analysis_predictor.cc:498): what the pipeline
        will do to this program and how big it is."""
        block = self._program.global_block()
        op_types: Dict[str, int] = {}
        for op in block.ops:
            op_types[op.type] = op_types.get(op.type, 0) + 1
        return {
            "num_ops": len(block.ops),
            "op_types": op_types,
            "num_feeds": len(self._feed_names),
            "num_fetches": len(self._fetch_names),
            "ir_optim": self._config.ir_optim(),
            "conv_bn_fused": self._conv_bn_fused,
            "passes": self._config.pass_builder().all_passes(),
            "memory_optim": getattr(self._config,
                                    "_enable_memory_optim", False),
            "compiler": "xla",
        }


def create_predictor(config: Config) -> Predictor:
    """Reference: paddle_infer::CreatePredictor."""
    return Predictor(config)


def create_paddle_predictor(config: Config) -> Predictor:
    """Reference: CreatePaddlePredictor<AnalysisConfig>
    (analysis_predictor.cc:1012)."""
    return Predictor(config)
