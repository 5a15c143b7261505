"""JAX op implementations — importing this package registers all ops."""

from .registry import (OPS, OP_SPECS, register, get_op, has_op,
                       LoweringContext, op_spec, get_op_spec, has_op_spec,
                       VarSig, SpecMismatch)
from . import op_specs   # noqa: F401  (registers the built-in spec library)
from . import math_ops      # noqa: F401
from . import nn_ops        # noqa: F401
from . import tensor_ops    # noqa: F401
from . import optimizer_ops  # noqa: F401
from . import collective_ops  # noqa: F401
from . import attention_ops  # noqa: F401
from . import cache_ops     # noqa: F401
from . import sampling_ops  # noqa: F401
from . import fused_ops     # noqa: F401
from . import controlflow_ops  # noqa: F401
from . import sequence_ops  # noqa: F401
from . import math_ext_ops  # noqa: F401
from . import nn_ext_ops    # noqa: F401
from . import detection_ops  # noqa: F401
from . import loss_ext_ops  # noqa: F401
from . import quant_ops     # noqa: F401
from . import tp_ops        # noqa: F401
from . import moe_ops       # noqa: F401
from . import decoder_lm_ops  # noqa: F401
from . import mla_ops        # noqa: F401
from . import linear_attn_ops  # noqa: F401
from . import breadth_ops   # noqa: F401
from . import breadth2_ops  # noqa: F401
from . import crf_ops       # noqa: F401
from . import yolo_loss_op  # noqa: F401
from . import proposal_ops  # noqa: F401
from . import deform_ops    # noqa: F401
from . import breadth3_ops  # noqa: F401
from . import recsys_ops    # noqa: F401
from . import ctr_text_ops  # noqa: F401
from . import pipeline_op   # noqa: F401
from . import ps_ops        # noqa: F401
from . import eval_tail_ops  # noqa: F401
from . import label_gen_ops  # noqa: F401
from . import legacy_cf_ops  # noqa: F401
from . import beam_ops       # noqa: F401
from . import registry_tail_ops  # noqa: F401
