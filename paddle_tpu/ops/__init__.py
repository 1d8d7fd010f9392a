"""Op emitter corpus — importing this package registers all builtin ops
(capability parity with the reference's static-initializer op registration,
framework/op_registry.h:197)."""

from paddle_tpu.ops import basic  # noqa: F401
from paddle_tpu.ops import math_ops  # noqa: F401
from paddle_tpu.ops import nn_ops  # noqa: F401
from paddle_tpu.ops import optimizer_ops  # noqa: F401
from paddle_tpu.ops import metric_ops  # noqa: F401
from paddle_tpu.ops import grad_ops  # noqa: F401
from paddle_tpu.ops import control_flow  # noqa: F401
from paddle_tpu.ops import rnn_ops  # noqa: F401
from paddle_tpu.ops import sequence_ops  # noqa: F401
from paddle_tpu.ops import loss_ops  # noqa: F401
from paddle_tpu.ops import beam_ops  # noqa: F401
from paddle_tpu.ops import misc_ops  # noqa: F401
from paddle_tpu.ops import image_ops  # noqa: F401
from paddle_tpu.ops import detection_ops  # noqa: F401
from paddle_tpu.ops import rpn_ops  # noqa: F401
from paddle_tpu.ops import lod_ops  # noqa: F401
from paddle_tpu.ops import ctc_ops  # noqa: F401
from paddle_tpu.ops import quant_ops  # noqa: F401
from paddle_tpu.ops import infra_ops  # noqa: F401
from paddle_tpu.ops import kv_attention  # noqa: F401
from paddle_tpu.ops import parallel_ops  # noqa: F401
from paddle_tpu.ops import kda  # noqa: F401
from paddle_tpu.ops import gdn  # noqa: F401
from paddle_tpu.ops import ssd  # noqa: F401
from paddle_tpu.ops import s6  # noqa: F401
from paddle_tpu.ops import shortconv  # noqa: F401
from paddle_tpu.ops import expert_ffn  # noqa: F401
from paddle_tpu.ops import mla  # noqa: F401
