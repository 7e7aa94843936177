"""jit'd dispatch wrappers: model-layout in, kernel-layout inside.

``flash_attention`` / ``ssd_scan`` are what the model layers call when
``use_kernel=True``; ``ssd_decode`` is what mamba2's decode step always
calls (the ``ssm`` family).  On the TPU the ``pallas_call`` lowers to
Mosaic; on the CPU backend (the test suite) the same body runs in Pallas
interpret mode; any other backend is refused rather than quietly
interpreted.  The jnp
reference path (`repro.kernels.ref`) is the oracle and the default dry-run
path (the dry-run measures the XLA program, and Mosaic kernels are opaque
to HLO cost analysis anyway).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from . import ref
from .flash_attention import flash_attention_bhsd
from .ssd_decode import ssd_decode_bhpn
from .ssd_scan import ssd_scan_bhsd


def _interpret() -> bool:
    """Interpret on the CPU backend, compile on the TPU, refuse the rest."""
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"Pallas kernels compile for the TPU and interpret on the CPU; "
            f"backend {backend!r} is neither")
    return backend == "cpu"


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    logit_cap: float = 0.0, scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128) -> jax.Array:
    """Model layout (B,S,H,D) in/out; kernel runs (B,H,S,D).  ``scale``
    multiplies the scores (None: ``1/sqrt(D)``)."""
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = flash_attention_bhsd(qt, kt, vt, causal=causal, window=window,
                               logit_cap=logit_cap, scale=scale,
                               block_q=block_q,
                               block_k=block_k, interpret=_interpret())
    return jnp.swapaxes(out, 1, 2)


def ssd_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
             c: jax.Array, chunk: int,
             initial_state: Optional[jax.Array] = None
             ) -> Tuple[jax.Array, jax.Array]:
    """Model layout x: (B,S,H,P), b/c: (B,S,G,N) -> (y, final_state).

    Groups are broadcast to heads; initial_state must be None (the kernel
    starts from zero state — prefill semantics).
    """
    B, S, H, P = x.shape
    G, N = b.shape[2], b.shape[3]
    rep = H // G
    xt = jnp.transpose(x, (0, 2, 1, 3))              # (B,H,S,P)
    dtt = jnp.transpose(dt, (0, 2, 1))               # (B,H,S)
    bt = jnp.repeat(jnp.transpose(b, (0, 2, 1, 3)), rep, axis=1)
    ct = jnp.repeat(jnp.transpose(c, (0, 2, 1, 3)), rep, axis=1)
    if initial_state is not None:
        raise NotImplementedError(
            "kernel path starts from zero state; pass initial_state only "
            "on the jnp path")
    y, state = ssd_scan_bhsd(xt, dtt, a, bt, ct, chunk,
                             interpret=_interpret())
    y = jnp.transpose(y, (0, 2, 1, 3))               # (B,S,H,P)
    # model layout state: (B,H,N,P)
    return y, state


def ssd_decode(states: jax.Array, out: jax.Array, layer: jax.Array,
               decay: jax.Array, x: jax.Array, bdt: jax.Array, c: jax.Array
               ) -> Tuple[jax.Array, jax.Array]:
    """One layer's decode SSM state update and read-out
    (``ssd_decode_bhpn``): ``out`` with layer ``layer`` set to the new
    state, and y (B,H,P).

    Compiled or interpreted by the platform the step is lowered for, not by
    the default backend, so a step compiled for a TPU from a CPU-only host
    carries the Mosaic kernel; any platform but the two is refused.
    """
    return jax.lax.platform_dependent(
        states, out, layer, decay, x, bdt, c,
        tpu=functools.partial(ssd_decode_bhpn, interpret=False),
        cpu=functools.partial(ssd_decode_bhpn, interpret=True))


# convenience: oracle access under one namespace
mha_reference = ref.mha_reference
ssd_reference = ref.ssd_reference
