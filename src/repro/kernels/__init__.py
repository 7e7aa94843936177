"""Pallas TPU kernels for the two compute hot spots.

flash_attention: fused GQA attention (causal/window/softcap).
ssd_scan: Mamba2 SSD chunk scan with VMEM-resident state.
ops: jit'd wrappers (compiled on TPU, interpret mode on the CPU backend);
ref: jnp oracles.
"""
from . import ops, ref
from .flash_attention import flash_attention_bhsd
from .ssd_scan import ssd_scan_bhsd

__all__ = ["flash_attention_bhsd", "ops", "ref", "ssd_scan_bhsd"]
