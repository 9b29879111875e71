"""paddle_tpu.fluid — the Fluid programming model, TPU-native.

Reference parity: `python/paddle/fluid/__init__.py`. Static ProgramDesc
graphs + Executor, dygraph imperative mode, layers/optimizer/io APIs — all
lowering to XLA on TPU.
"""
from . import framework
from .framework import (  # noqa: F401
    Program, Variable, Parameter, Operator, program_guard,
    default_main_program, default_startup_program, name_scope,
    device_guard, in_dygraph_mode, cpu_places, cuda_places, tpu_places,
    CPUPlace, CUDAPlace, CUDAPinnedPlace, TPUPlace,
    unique_name_guard, require_version, is_compiled_with_cuda,
    load_op_library, ComplexVariable,
)
from . import unique_name  # noqa: F401
from .parallel_executor import ParallelExecutor  # noqa: F401
from .. import core  # noqa: F401  (fluid.core.CipherUtils etc.)
from ..core.scope import Scope, global_scope, scope_guard  # noqa: F401
from ..core.lod import (  # noqa: F401
    LoDTensor, create_lod_tensor, create_random_int_lodtensor,
)
from .executor import Executor, LazyFetch  # noqa: F401
from .backward import append_backward, gradients  # noqa: F401
from .compiler import (  # noqa: F401
    CompiledProgram, BuildStrategy, ExecutionStrategy,
)
from . import layers  # noqa: F401
from . import initializer  # noqa: F401
from . import optimizer  # noqa: F401
from . import regularizer  # noqa: F401
from . import clip  # noqa: F401
from .param_attr import ParamAttr, WeightNormParamAttr  # noqa: F401
from .clip import (  # noqa: F401
    GradientClipByValue, GradientClipByNorm, GradientClipByGlobalNorm,
)
from .initializer import (  # noqa: F401
    Constant, Uniform, Normal, TruncatedNormal, Xavier, MSRA,
    NumpyArrayInitializer,
)
from . import dygraph  # noqa: F401
from .dygraph.base import enable_dygraph, disable_dygraph  # noqa: F401
from . import io  # noqa: F401
from .io import (  # noqa: F401
    save_persistables, load_persistables, save_params, load_params,
    save_inference_model, load_inference_model,
)
from . import reader  # noqa: F401
from .reader import DataLoader, BatchSampler  # noqa: F401
from . import dataset  # noqa: F401
from .dataset import DatasetFactory, InMemoryDataset, QueueDataset  # noqa: F401
from . import metrics  # noqa: F401
from . import average  # noqa: F401
from . import evaluator  # noqa: F401
from . import debugger  # noqa: F401
from . import communicator  # noqa: F401
from .data_feed_desc import DataFeedDesc  # noqa: F401
from .input import embedding, one_hot  # noqa: F401
from . import contrib  # noqa: F401
from . import install_check  # noqa: F401
from . import incubate  # noqa: F401
from . import profiler  # noqa: F401
from . import checkpoint  # noqa: F401
from .data_feeder import DataFeeder  # noqa: F401


def data(name, shape, dtype="float32", lod_level=0):
    """fluid.data — batch dim must be explicit/-1 (reference:
    python/paddle/fluid/data.py)."""
    return layers.tensor.data(name, shape, dtype=dtype,
                              append_batch_size=False)


# flags system (reference: platform/flags.cc surfaced via
# global_value_getter_setter.cc)
from ..utils.flags import get_flags, set_flags  # noqa: F401,E402

# parameter-server transpiler (reference: fluid.DistributeTranspiler)
from . import transpiler  # noqa: F401,E402
from .transpiler import (  # noqa: F401,E402
    DistributeTranspiler, DistributeTranspilerConfig,
    memory_optimize, release_memory,
)

# composite network builders (reference: python/paddle/fluid/nets.py)
from . import nets  # noqa: F401,E402
