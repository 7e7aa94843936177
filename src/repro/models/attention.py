"""GQA attention with local/global windows, softcap, qk-norm, KV caches.

Baseline math is pure jnp (what the dry-run lowers); the TPU hot path is the
Pallas flash-attention kernel in ``repro.kernels`` selected via
``ops.attention`` when ``use_kernel=True`` (validated in interpret mode).

Supports:
* grouped-query attention (num_kv_heads <= num_heads),
* sliding-window masks (gemma2 local layers; window passed per-layer so a
  scan over alternating local/global layers stays a single fused body),
* attention logit soft-capping (gemma2),
* qk layer-norm (chameleon),
* decode with a (batch, kv_heads, max_seq, head_dim) cache updated in place,
* cross-attention (whisper decoder).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .common import ArchConfig, KeyGen, apply_rope, dense_init, rms_norm, softcap


def init_attention(kg: KeyGen, cfg: ArchConfig, dtype: Any,
                   cross: bool = False) -> Dict[str, jax.Array]:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    p = {
        "wq": dense_init(kg(), (d, nq, hd), dtype, fan_in=d),
        "wk": dense_init(kg(), (d, nkv, hd), dtype, fan_in=d),
        "wv": dense_init(kg(), (d, nkv, hd), dtype, fan_in=d),
        "wo": dense_init(kg(), (nq, hd, d), dtype, fan_in=nq * hd),
    }
    if cfg.use_bias:
        p["bq"] = jnp.zeros((nq, hd), dtype)
        p["bk"] = jnp.zeros((nkv, hd), dtype)
        p["bv"] = jnp.zeros((nkv, hd), dtype)
        p["bo"] = jnp.zeros((d,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), dtype)
        p["k_norm"] = jnp.zeros((hd,), dtype)
    return p


def _project_qkv(p: Dict[str, jax.Array], x: jax.Array, kv_src: jax.Array,
                 cfg: ArchConfig, positions: Optional[jax.Array],
                 kv_positions: Optional[jax.Array],
                 use_rope: bool) -> Tuple[jax.Array, jax.Array, jax.Array]:
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("btd,dhk->bthk", kv_src, p["wk"])
    v = jnp.einsum("btd,dhk->bthk", kv_src, p["wv"])
    if cfg.use_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    if use_rope:
        assert positions is not None and kv_positions is not None
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, kv_positions, cfg.rope_theta)
    return q, k, v


def _gqa_scores(q: jax.Array, k: jax.Array, cfg: ArchConfig) -> jax.Array:
    """q: (B,S,nq,hd), k: (B,T,nkv,hd) -> scores (B,nkv,G,S,T)."""
    b, s, nq, hd = q.shape
    nkv = k.shape[2]
    g = nq // nkv
    qg = q.reshape(b, s, nkv, g, hd)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg.astype(jnp.float32),
                        k.astype(jnp.float32))
    if cfg.attention_multiplier is not None:
        return scores * cfg.attention_multiplier
    return scores / math.sqrt(hd)


def _gqa_out(probs: jax.Array, v: jax.Array) -> jax.Array:
    """probs: (B,nkv,G,S,T), v: (B,T,nkv,hd) -> (B,S,nq,hd)."""
    b, nkv, g, s, t = probs.shape
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v.astype(jnp.float32))
    return out.reshape(b, s, nkv * g, -1)


def attention(p: Dict[str, jax.Array], x: jax.Array, cfg: ArchConfig, *,
              positions: jax.Array,
              window: Optional[jax.Array] = None,
              causal: bool = True,
              kv_src: Optional[jax.Array] = None,
              kv_positions: Optional[jax.Array] = None,
              use_rope: bool = True,
              use_kernel: bool = False) -> jax.Array:
    """Full-sequence attention (train / prefill).

    ``window``: scalar (static or traced) sliding-window size; None/0 = full.
    ``kv_src``: encoder output for cross-attention (then causal=False).
    """
    cross = kv_src is not None
    kv_src = x if kv_src is None else kv_src
    if kv_positions is None:
        kv_positions = (positions if not cross else
                        jnp.arange(kv_src.shape[1])[None, :])
    q, k, v = _project_qkv(p, x, kv_src, cfg, positions, kv_positions,
                           use_rope and not cross)

    if use_kernel and not cross:
        from ..kernels import ops as kops
        out = kops.flash_attention(
            q, k, v, causal=causal,
            window=int(window) if window is not None else 0,
            logit_cap=cfg.attn_softcap,
            scale=cfg.attention_multiplier)
    else:
        scores = _gqa_scores(q, k, cfg)
        scores = softcap(scores, cfg.attn_softcap)
        qpos = positions[:, None, None, :, None]          # (B,1,1,S,1)
        kpos = kv_positions[:, None, None, None, :]       # (B,1,1,1,T)
        mask = jnp.ones_like(scores, dtype=bool)
        if causal:
            mask = mask & (kpos <= qpos)
        if window is not None:
            w = jnp.asarray(window)
            mask = mask & jnp.where(w > 0, qpos - kpos < w, True)
        scores = jnp.where(mask, scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        out = _gqa_out(probs, v)
    out = out.astype(x.dtype)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    if cfg.use_bias:
        y = y + p["bo"]
    return y


# ---------------------------------------------------------------------------
# Decode path (single new token against a cache)
# ---------------------------------------------------------------------------


def init_kv_cache(cfg: ArchConfig, batch: int, max_seq: int,
                  dtype: Any) -> Dict[str, jax.Array]:
    hd = cfg.resolved_head_dim
    return {
        "k": jnp.zeros((batch, max_seq, cfg.num_kv_heads, hd), dtype),
        "v": jnp.zeros((batch, max_seq, cfg.num_kv_heads, hd), dtype),
    }


def decode_attention(p: Dict[str, jax.Array], x: jax.Array,
                     cache: Dict[str, jax.Array], pos: jax.Array,
                     cfg: ArchConfig, *,
                     window: Optional[jax.Array] = None,
                     use_rope: bool = True
                     ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token decode.  x: (B,1,d); cache k/v: (B,T,nkv,hd); pos scalar."""
    b = x.shape[0]
    positions = jnp.full((b, 1), pos, dtype=jnp.int32)
    t_max = cache["k"].shape[1]
    kv_positions = positions  # rope for the new key at `pos`
    q, k_new, v_new = _project_qkv(p, x, x, cfg, positions, kv_positions,
                                   use_rope)
    k = jax.lax.dynamic_update_slice(cache["k"], k_new.astype(
        cache["k"].dtype), (0, pos, 0, 0))
    v = jax.lax.dynamic_update_slice(cache["v"], v_new.astype(
        cache["v"].dtype), (0, pos, 0, 0))
    scores = _gqa_scores(q, k, cfg)                 # (B,nkv,G,1,T)
    scores = softcap(scores, cfg.attn_softcap)
    kpos = jnp.arange(t_max)[None, None, None, None, :]
    mask = kpos <= pos
    if window is not None:
        w = jnp.asarray(window)
        mask = mask & jnp.where(w > 0, pos - kpos < w, True)
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = _gqa_out(probs, v).astype(x.dtype)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    if cfg.use_bias:
        y = y + p["bo"]
    return y, {"k": k, "v": v}


def decode_cross_attention(p: Dict[str, jax.Array], x: jax.Array,
                           k: jax.Array, v: jax.Array,
                           cfg: ArchConfig) -> jax.Array:
    """Cross-attention against precomputed encoder K/V (whisper decode)."""
    b = x.shape[0]
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if cfg.use_bias:
        q = q + p["bq"]
    scores = _gqa_scores(q, k, cfg)
    probs = jax.nn.softmax(scores, axis=-1)
    out = _gqa_out(probs, v).astype(x.dtype)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    if cfg.use_bias:
        y = y + p["bo"]
    return y
