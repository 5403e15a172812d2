"""Op registry + kernel modules. Importing this package registers all ops."""
from .registry import (
    ExecContext,
    OpDef,
    all_op_types,
    default_grad_maker,
    get_op_def,
    has_op,
    infer_op,
    register_grad_compute,
    register_op,
)

from . import math_ops  # noqa: F401
from . import activation_ops  # noqa: F401
from . import tensor_ops  # noqa: F401
from . import nn_ops  # noqa: F401
from . import optimizer_ops  # noqa: F401
from . import collective_ops  # noqa: F401
from . import control_flow_ops  # noqa: F401
from . import attention_ops  # noqa: F401
from . import cca_moe_ops  # noqa: F401
from . import decoder_train_ops  # noqa: F401
from . import vision_ops  # noqa: F401
from . import crf_ops  # noqa: F401
from . import distributed_ops  # noqa: F401
from . import sequence_ops  # noqa: F401
from . import rnn_ops  # noqa: F401
from . import sampling_ops  # noqa: F401
from . import ctc_ops  # noqa: F401
from . import quant_ops  # noqa: F401
from . import detection_ops  # noqa: F401
