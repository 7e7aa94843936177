"""moe_gmm_kernel_ms.decode -- kernels: the dropless MoE's grouped matmuls.

Mean device time, per traced execution of the decode step
(``decode_one``), of its ``%ragged-dot`` kernels alone: not the
``%ragged-dot-metadata`` calls, and not the ops that stage each layer's
expert weights for them, which ``moe_gmm_roofline.decode`` times with the
kernels (``harness/moe_gmm.py``).  In milliseconds; None where the trace
has no ``%ragged-dot`` op.  Moves ``gen_tokens_per_s``.
"""
from harness import moe_gmm


def read(run):
    return moe_gmm.kernel_ms(run, "decode_one")
