"""Mamba2 SSD chunk kernel for TPU (Pallas).

One grid step processes one (batch, head, chunk) tile entirely in VMEM:
the chunk-local "attention-like" quadratic term, the inter-chunk
contribution from the carried state, and the state update.  The chunk axis
is the sequential (arbitrary) grid dimension; the running state
(N x P floats) lives in VMEM scratch — the TPU-native shape of the SSD
recurrence: all heavy ops are (Q x Q)/(Q x N)/(N x P) MXU matmuls, and HBM
traffic is exactly one read of x/dt/B/C and one write of y per token.

Validated against ``ref.ssd_reference`` in interpret mode on the CPU;
``tests/test_tpu_compile.py`` compiles it for the chip at mamba2-1.3b widths.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(a_ref, x_ref, dtr_ref, dtc_ref, b_ref, c_ref, y_ref, st_ref,
                state_scr, *, num_chunks: int, chunk: int):
    hi = pl.program_id(1)
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    a = a_ref[hi]                                   # scalar decay rate (SMEM)
    x = x_ref[0, 0].astype(jnp.float32)             # (Q, P)
    dt_row = dtr_ref[0, 0].astype(jnp.float32)      # (1, Q)
    dt_col = dtc_ref[0, 0].astype(jnp.float32)      # (Q, 1)
    b = b_ref[0, 0].astype(jnp.float32)             # (Q, N)
    c = c_ref[0, 0].astype(jnp.float32)             # (Q, N)

    # chunk-local cumulative log-decays, in both layouts, as masked 2-D
    # reductions (Mosaic has no 1-D scan): cum_i = sum_{k<=i} dt_k a
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = rows >= cols
    cum_col = jnp.sum(jnp.where(tri, dt_row * a, 0.0), axis=1,
                      keepdims=True)                # (Q, 1)
    cum_row = jnp.sum(jnp.where(rows <= cols, dt_col * a, 0.0), axis=0,
                      keepdims=True)                # (1, Q)
    total = jnp.sum(dt_row * a, axis=1, keepdims=True)  # (1, 1): cum_Q

    # intra-chunk: L[i,j] = exp(cum_i - cum_j) (i >= j), att = (C B^T) * L * dt_j
    L = jnp.where(tri, jnp.exp(cum_col - cum_row), 0.0)
    scores = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    att = scores * L * dt_row
    y = jax.lax.dot_general(att, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # inter-chunk: y += (C * exp(cum)) @ state
    state = state_scr[...]                          # (N, P)
    y += jax.lax.dot_general(c * jnp.exp(cum_col), state,
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)

    # state update: state' = exp(cum_Q) * state + B^T @ (x * dt * decay_to_end)
    wx = x * (dt_col * jnp.exp(total - cum_col))    # (Q, P)
    state_new = jnp.exp(total) * state + jax.lax.dot_general(
        b, wx, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    state_scr[...] = state_new

    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(ci == num_chunks - 1)
    def _emit_state():
        st_ref[0, 0] = state_new.astype(st_ref.dtype)


def ssd_scan_bhsd(x: jax.Array, dt: jax.Array, a: jax.Array,
                  b: jax.Array, c: jax.Array, chunk: int, *,
                  interpret: bool) -> Tuple[jax.Array, jax.Array]:
    """x: (B,H,S,P); dt: (B,H,S); a: (H,); b/c: (B,H,S,N) (groups
    pre-broadcast to heads).  Returns (y: (B,H,S,P), state: (B,H,N,P)).

    On the chip ``chunk`` must be a multiple of 128 (or equal S): ``dt``
    enters both as rows (B,H,1,S) and as columns (B,H,S,1), so every block's
    last two dimensions are tile-legal.
    """
    B, H, S, P = x.shape
    N = b.shape[-1]
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk

    kernel = functools.partial(_ssd_kernel, num_chunks=nc, chunk=chunk)
    y, state = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, chunk, P), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda bi, hi, ci: (bi, hi, 0, ci)),
            pl.BlockSpec((1, 1, chunk, 1), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda bi, hi, ci: (bi, hi, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, N, P), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, N, P), x.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
    )(a.astype(jnp.float32), x, dt[:, :, None, :], dt[..., None], b, c)
    return y, state
