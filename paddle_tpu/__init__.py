"""paddle_tpu — a TPU-native deep-learning framework with the capabilities of
PaddlePaddle Fluid 1.5 (reference at /root/reference, surveyed in SURVEY.md).

Fluid's contract — declarative Program IR built from Python layers, autodiff
and distribution as program transformations, Executor.run(feed, fetch) — with
a new execution model: whole-block lowering to XLA via JAX, SPMD parallelism
over jax.sharding meshes, and Pallas kernels for hot ops.
"""
import time as _time

_import_t0 = _time.perf_counter()
from . import flags  # noqa: E402,F401  (first: other modules read flags at import)
from . import observability  # noqa: E402,F401  (before profiler: its shims use it)
from . import core  # noqa: F401
from . import ops  # noqa: F401
from . import profiler  # noqa: F401
from . import layers  # noqa: F401
from . import initializer  # noqa: F401
from . import optimizer  # noqa: F401
from . import regularizer  # noqa: F401
from . import clip  # noqa: F401
from . import unique_name  # noqa: F401
from . import parallel  # noqa: F401
from . import nets  # noqa: F401
from . import models  # noqa: F401
from . import metrics  # noqa: F401
from . import io  # noqa: F401
from . import contrib  # noqa: F401
from . import reader  # noqa: F401
from . import dataset  # noqa: F401
from . import transpiler  # noqa: F401
from . import debugger  # noqa: F401
from . import average  # noqa: F401
from . import evaluator  # noqa: F401
from . import net_drawer  # noqa: F401
from . import install_check  # noqa: F401
from . import passes  # noqa: F401
from . import distributed  # noqa: F401
from . import inference  # noqa: F401
from . import dygraph  # noqa: F401
from . import resilience  # noqa: F401
from . import pipeline  # noqa: F401
from . import serving  # noqa: F401
from .pipeline import DeviceLoader  # noqa: F401
from .transpiler import DistributeTranspiler, DistributeTranspilerConfig  # noqa: F401
from .fluid_dataset import DatasetFactory, InMemoryDataset, QueueDataset  # noqa: F401
from .data_feeder import DataFeeder  # noqa: F401
from .pyreader import DataLoader, PyReader  # noqa: F401
batch = reader.batch  # paddle.batch alias
from .backward import append_backward, gradients  # noqa: F401
from .compiler import BuildStrategy, CompiledProgram, ExecutionStrategy  # noqa: F401
from .executor import Executor, Scope, global_scope, scope_guard  # noqa: F401
from .framework import (  # noqa: F401
    Block,
    OpError,
    Operator,
    Parameter,
    Program,
    Variable,
    default_main_program,
    default_startup_program,
    name_scope,
    program_guard,
)
from .param_attr import ParamAttr  # noqa: F401

# Place objects: thin tags for API parity (reference platform/place.h:79).
# Device selection is JAX's job; these only pick cpu vs tpu backends.
class CPUPlace:
    def __repr__(self):
        return "CPUPlace"


class TPUPlace:
    def __init__(self, device_id: int = 0):
        self.device_id = device_id

    def __repr__(self):
        return f"TPUPlace({self.device_id})"


# CUDAPlace intentionally absent: zero CUDA in this build (BASELINE.json).

__version__ = "0.1.0"

# what the package cost to import, this file's first line to here (jax's own
# import too where nothing loaded it before): `setup.import.seconds`, booked
# when the registry is first made
observability.note_import(_time.perf_counter() - _import_t0)
