"""paddle_tpu: a TPU-native deep-learning framework with the capabilities of
PaddlePaddle Fluid (reference snapshot: MrGo2008/Paddle @ Fluid 1.2/1.3-dev).

Programs are Block/Op descriptions built from a fluid-style Python API
(layers, append_backward autodiff, in-graph optimizers), lowered wholesale to
XLA via JAX — `TPUPlace` is the first-class device, collectives ride ICI via
jax.sharding instead of NCCL/gRPC.  See SURVEY.md at the repo root for the
structural map to the reference.

Typical use mirrors fluid:

    import paddle_tpu as fluid
    img = fluid.layers.data("img", [1, 28, 28])
    ...
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(fluid.default_startup_program())
    loss_v, = exe.run(feed={...}, fetch_list=[loss])
"""

__version__ = "0.1.0"

from . import ops as _ops  # registers all op lowerings  # noqa: F401

from .core.framework import (  # noqa: F401
    Program,
    Variable,
    default_main_program,
    default_startup_program,
    name_scope,
    program_guard,
    recompute_scope,
    reset_default_env,
)
from .core.place import (  # noqa: F401
    CPUPlace,
    CUDAPinnedPlace,
    CUDAPlace,
    Place,
    TPUPlace,
    is_compiled_with_cuda,
)
from .core.scope import Scope, global_scope, scope_guard  # noqa: F401
from .core.lod import LoDValue, create_lod_tensor  # noqa: F401
from .core.executor import Executor  # noqa: F401
from .core.amp import enable_amp, disable_amp, amp_dtype  # noqa: F401
from .core.dtypes import enable_x64, x64_enabled, x64_scope  # noqa: F401
from .flags import get_flags, set_flags  # noqa: F401
from .core.backward import append_backward, calc_gradient  # noqa: F401
from .core import proto as core  # noqa: F401  (fluid.core-ish alias)

from . import average  # noqa: F401
from . import debugger  # noqa: F401
from . import evaluator  # noqa: F401
from . import clip  # noqa: F401
from . import contrib  # noqa: F401
from . import imperative  # noqa: F401
from . import inference  # noqa: F401
from . import transpiler  # noqa: F401
from . import nets  # noqa: F401
from . import learning_rate_decay  # noqa: F401
from . import unique_name  # noqa: F401
from . import recordio as recordio_writer  # noqa: F401
from .core import backward  # noqa: F401
from .tensor_shim import LoDTensor, LoDTensorArray, Tensor  # noqa: F401
from .transpiler import DistributeTranspiler, DistributeTranspilerConfig  # noqa: F401
from .transpiler import InferenceTranspiler  # noqa: F401
from .transpiler import memory_optimize, release_memory  # noqa: F401
from .async_executor import AsyncExecutor  # noqa: F401
from . import distributed  # noqa: F401
from . import elastic  # noqa: F401
from . import net_drawer  # noqa: F401
from .core import enforce  # noqa: F401
from .core.enforce import EnforceNotMet  # noqa: F401
from . import distribute_lookup_table  # noqa: F401
from .data_feed_desc import DataFeedDesc  # noqa: F401
from . import dataset  # noqa: F401
from . import executor  # noqa: F401
from . import io  # noqa: F401
from . import reader  # noqa: F401
from . import recordio  # noqa: F401
from . import resilience  # noqa: F401
from . import serving  # noqa: F401
from .data_feeder import DataFeeder  # noqa: F401
from .reader import batch  # noqa: F401
from . import metrics  # noqa: F401
from . import observability  # noqa: F401
from . import profiler  # noqa: F401
from . import parallel  # noqa: F401
from .parallel import BuildStrategy, ExecutionStrategy, ParallelExecutor  # noqa: F401
from .parallel.executor import CompiledProgram  # noqa: F401
from . import initializer  # noqa: F401
from . import layers  # noqa: F401
from . import optimizer  # noqa: F401
from . import regularizer  # noqa: F401
from .param_attr import ParamAttr, WeightNormParamAttr  # noqa: F401

# fluid-style direct names
from .initializer import Constant, MSRA, Normal, TruncatedNormal, Uniform, Xavier  # noqa: F401

# the set-up log's first mark: Python, jax, the backend's client and this
# package are imported (observability/compiles.py)
observability.default_compile_log().note_imported()
