"""moe_gmm_roofline.decode -- kernels: the dropless MoE's grouped matmuls.

Over the traced executions of the decode step (``decode_one``), the
roofline time of their three grouped expert matmuls per layer
(``harness/moe_gmm.py``: FLOPs, or the routed rows and the weights of
every expert they could reach at HBM bandwidth, whichever takes longer)
over the device time of their ``%ragged-dot`` ops and of the ops that
stage each layer's expert weights for them (matched by result type), in
percent.  None where the trace has no ``%ragged-dot`` op.  Moves ``gen_tokens_per_s``.
"""
from harness import moe_gmm


def read(run):
    return moe_gmm.roofline_pct(run, "decode_one")
