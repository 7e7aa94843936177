"""Mamba2's decode-step SSM state update and read-out for TPU (Pallas).

One call serves one layer of the decode cache.  It reads the layer's
state in place from the whole (L, B, H, P, N) input stack (the layer index
is a prefetched scalar, so no slice of the stack is copied first), computes

    s = state * decay + (x (x) B*dt)      y = sum_N s * C

in float32 per (batch row, block of heads) tile, and writes ``s`` into
layer ``layer`` of an output stack it updates in place (the stack is an
aliased operand; its other layers are left as they are) and ``y`` from the
same tile in VMEM.  The state is read once and written once.

The outer-product term is rounded to the input state's dtype before the
add, as the XLA form (``models/ssm.py:mamba2_decode_step``) does: the state
is bf16 out of prefill and float32 after the first decode step.

Validated against that XLA form in interpret mode on the CPU;
``tests/test_tpu_compile.py`` compiles it, inside mamba2-1.3b's decode
step, for the chip.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Bytes of float32 state per grid step: 2 MiB, all 64 heads of one batch
# row at mamba2-1.3b's widths, so a layer is 16 grid steps and each step's
# fixed cost stays small against its 4 MiB of HBM traffic.
BLOCK_BYTES = 2 << 20


def _heads_per_block(heads: int, p: int, n: int) -> int:
    """The most heads whose float32 state fits ``BLOCK_BYTES``, among the
    divisors of ``heads`` that make a tile-legal second-minor block
    dimension (a multiple of 8, or all of them); the fewest if none fits."""
    legal = [hb for hb in range(1, heads + 1)
             if heads % hb == 0 and (hb % 8 == 0 or hb == heads)]
    fits = [hb for hb in legal if hb * p * n * 4 <= BLOCK_BYTES]
    return max(fits) if fits else min(legal)


def _decode_kernel(layer_ref, decay_ref, state_ref, x_ref, bdt_ref, c_ref,
                   _out_in_ref, out_ref, y_ref):
    x = x_ref[...].astype(jnp.float32)                 # (hb, P)
    outer = x[:, :, None] * bdt_ref[...][:, None, :]   # (hb, P, N)
    s = (state_ref[...].astype(jnp.float32) * decay_ref[...][:, :, None]
         + outer.astype(state_ref.dtype).astype(jnp.float32))
    out_ref[...] = s
    y_ref[...] = jnp.sum(s * c_ref[...][:, None, :], axis=-1)


def ssd_decode_bhpn(states: jax.Array, out: jax.Array, layer: jax.Array,
                    decay: jax.Array, x: jax.Array, bdt: jax.Array,
                    c: jax.Array, *, interpret: bool
                    ) -> Tuple[jax.Array, jax.Array]:
    """One layer's decode state update and read-out.

    states: (L, B, H, P, N) input stack, read at ``layer``; out: the
    (L, B, H, P, N) float32 output stack, returned with layer ``layer``
    overwritten (aliased: on the chip it is updated in place); layer: int32
    scalar; decay: (B, H) float32; x: (B, H, P); bdt (B*dt): (B, H, N)
    float32; c: (B, H, N) (groups pre-broadcast to heads).
    Returns (out, y: (B, H, P) float32).
    """
    _, B, H, P, N = states.shape
    hb = _heads_per_block(H, P, N)
    stack = pl.BlockSpec((None, None, hb, P, N),
                         lambda b, j, l: (l[0], b, j, 0, 0))

    def rows(width):
        return pl.BlockSpec((None, hb, width), lambda b, j, l: (b, j, 0))
    return pl.pallas_call(
        _decode_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H // hb),
            in_specs=[rows(1), stack, rows(P), rows(N), rows(N),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[stack, rows(P)]),
        out_shape=[jax.ShapeDtypeStruct(out.shape, jnp.float32),
                   jax.ShapeDtypeStruct((B, H, P), jnp.float32)],
        input_output_aliases={6: 0},
        interpret=interpret,
        name="ssd_decode",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      decay.astype(jnp.float32)[..., None], states, x,
      bdt.astype(jnp.float32), c.astype(jnp.float32), out)
