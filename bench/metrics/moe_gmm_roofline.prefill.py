"""moe_gmm_roofline.prefill -- kernels: the dropless MoE's grouped matmuls.

Over the traced executions of the prefill step (``prefill_step``), the
roofline time of their three grouped expert matmuls per layer
(``harness/moe_gmm.py``) over the device time of their ``%ragged-dot``
ops and of the ops that stage each layer's expert weights for them
(matched by result type), in percent.  None where the trace has no
``%ragged-dot`` op.  Moves
``gen_tokens_per_s``.
"""
from harness import moe_gmm


def read(run):
    return moe_gmm.roofline_pct(run, "prefill_step")
