"""Fused flash attention for TPU (Pallas): GQA + causal + window + softcap.

TPU adaptation notes (DESIGN.md §2): the online-softmax accumulation runs in
VMEM scratch across the sequential last grid dimension (kv blocks), with
(128 x 128) MXU-aligned tiles.  Block sizes are BlockSpec parameters, so the
working set (q tile + kv tile + accumulators = BQ*D + 2*BK*D + 2*BQ*BK
floats) is tuned to fit the ~16 MiB VMEM budget with D=128 head dims.

Layout: (batch, heads, seq, head_dim).  GQA maps query head h to kv head
h // (Hq // Hkv) in the kv index_map — no KV duplication in HBM.
Validated against ``ref.mha_reference`` in interpret mode on the CPU;
``tests/test_tpu_compile.py`` compiles it for the chip at granite widths.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: int,
                  logit_cap: float, num_k_blocks: int, block_q: int,
                  block_k: int, seq_q: int, seq_k: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)          # (BQ, D)
    k = k_ref[0, 0].astype(jnp.float32)          # (BK, D)
    v = v_ref[0, 0].astype(jnp.float32)          # (BK, D)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if logit_cap:
        s = logit_cap * jnp.tanh(s / logit_cap)

    rows = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    cols = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = cols < seq_k                           # padding guard
    mask &= rows < seq_q
    if causal:
        mask &= cols <= rows
    if window > 0:
        mask &= (rows - cols) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                           # (BQ, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_new = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc_new

    @pl.when(ki == num_k_blocks - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def flash_attention_bhsd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                         causal: bool = True, window: int = 0,
                         logit_cap: float = 0.0,
                         scale: Optional[float] = None,
                         block_q: int = DEFAULT_BLOCK_Q,
                         block_k: int = DEFAULT_BLOCK_K,
                         interpret: bool) -> jax.Array:
    """q: (B, Hq, Sq, D); k/v: (B, Hkv, Sk, D) -> (B, Hq, Sq, D).

    ``scale`` multiplies the scores; None is ``1/sqrt(D)``."""
    b, hq, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    assert hq % hkv == 0, (hq, hkv)
    group = hq // hkv
    if scale is None:
        scale = 1.0 / math.sqrt(d)

    block_q = min(block_q, max(sq, 8))
    block_k = min(block_k, max(sk, 8))
    pad_q = (-sq) % block_q
    pad_k = (-sk) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    nq = q.shape[2] // block_q
    nk = k.shape[2] // block_k

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        logit_cap=logit_cap, num_k_blocks=nk, block_q=block_q,
        block_k=block_k, seq_q=sq, seq_k=sk)

    out = pl.pallas_call(
        kernel,
        grid=(b, hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, qi, ki, g=group:
                         (bi, hi // g, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, qi, ki, g=group:
                         (bi, hi // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),   # running denom l
            pltpu.VMEM((block_q, d), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
    )(q, k, v)
    if pad_q:
        out = out[:, :, :sq, :]
    return out
