"""Shared model substrate: configs, norms, rope, activations, losses.

Everything is functional JAX (params as pytrees, pure apply fns) so that
Application Drops wrapping these steps are stateless, exactly as the paper
requires of pipeline components (§3.1: "the computational tasks are
stateless, the Application Drops are stateful").
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


# ---------------------------------------------------------------------------
# Architecture config
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ArchConfig:
    """One assigned architecture (exact published numbers in configs/)."""

    name: str
    family: str                    # dense | moe | ssm | hybrid | encdec | vlm
    num_layers: int
    d_model: int
    num_heads: int                 # query heads (0 for attn-free)
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0              # 0 -> d_model // num_heads
    # attention details
    rope_theta: float = 10000.0
    local_window: int = 0          # 0 -> full attention
    alternate_local_global: bool = False   # gemma2: even layers local
    attn_softcap: float = 0.0      # gemma2 logit soft-capping
    final_softcap: float = 0.0
    qk_norm: bool = False          # chameleon
    use_bias: bool = False
    activation: str = "swiglu"     # swiglu | gelu | relu2
    tie_embeddings: bool = False
    # MoE
    num_experts: int = 0
    top_k: int = 0
    capacity_factor: float = 1.25
    moe_dropless: bool = False     # grouped matmul over every routed row;
    #                                capacity_factor is then unused
    # Granite's multipliers; the defaults are neutral and emit no operation
    embedding_multiplier: float = 1.0
    attention_multiplier: Optional[float] = None  # None -> 1/sqrt(head_dim)
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0         # logits are divided by it
    # SSM (mamba2)
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_headdim: int = 64
    ssm_conv: int = 4
    ssm_chunk: int = 256
    ssm_groups: int = 1
    # hybrid (zamba2): one shared attention block applied every N layers
    shared_attn_period: int = 0
    # enc-dec (whisper)
    num_encoder_layers: int = 0
    encoder_ratio: int = 8         # enc_len = seq_len // ratio (stub frontend)
    # systems knobs
    dtype: str = "bfloat16"
    sharding_strategy: str = "dp"  # dp | fsdp
    subquadratic: bool = False     # eligible for long_500k
    notes: str = ""

    # -- derived ----------------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim:
            return self.head_dim
        return self.d_model // max(self.num_heads, 1)

    @property
    def padded_vocab(self) -> int:
        return round_up(self.vocab_size, 256)

    @property
    def ssm_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.ssm_inner // self.ssm_headdim

    def param_count(self) -> int:
        """Analytic parameter count (for MODEL_FLOPS and reporting)."""
        d, f, v = self.d_model, self.d_ff, self.padded_vocab
        hd = self.resolved_head_dim
        nq, nkv = self.num_heads, self.num_kv_heads
        attn = d * hd * nq + 2 * d * hd * nkv + hd * nq * d
        if self.activation in ("swiglu", "geglu"):
            mlp = 3 * d * f
        else:
            mlp = 2 * d * f
        if self.family == "moe":
            mlp_total = self.num_experts * mlp + d * self.num_experts
        else:
            mlp_total = mlp
        ssm = 0
        if self.family in ("ssm", "hybrid"):
            di, n, g = self.ssm_inner, self.ssm_state, self.ssm_groups
            h = self.ssm_heads
            in_proj = d * (2 * di + 2 * g * n + h)
            conv = (di + 2 * g * n) * self.ssm_conv
            ssm = in_proj + conv + di * d + di + 2 * h  # out, norm, A/D
        per_layer: float
        if self.family == "ssm":
            per_layer = ssm + d            # + norm
        elif self.family == "hybrid":
            per_layer = ssm + 2 * d
        else:
            per_layer = attn + mlp_total + 2 * d
        total = self.num_layers * per_layer
        if self.family == "hybrid" and self.shared_attn_period:
            total += attn + mlp_total + 2 * d   # one shared block
        if self.family == "encdec":
            enc = self.num_encoder_layers * (attn + mlp_total + 2 * d)
            dec_cross = self.num_layers * (attn + d)   # cross-attn per layer
            total += enc + dec_cross
        total += v * d                      # embedding
        if not self.tie_embeddings:
            total += v * d                  # lm head
        total += d                          # final norm
        return int(total)

    def active_param_count(self) -> int:
        """Active params per token (MoE: only top_k experts count)."""
        if self.family != "moe" or not self.num_experts:
            return self.param_count()
        d, f = self.d_model, self.d_ff
        mlp = (3 if self.activation in ("swiglu", "geglu") else 2) * d * f
        dead = self.num_layers * (self.num_experts - self.top_k) * mlp
        return int(self.param_count() - dead)


@dataclass(frozen=True)
class ShapeConfig:
    """One assigned input shape."""

    name: str
    seq_len: int
    global_batch: int
    kind: str        # "train" | "prefill" | "decode"


SHAPES: Dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


# ---------------------------------------------------------------------------
# Numerics
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))
    return out.astype(dt)


def activation_fn(name: str):
    if name in ("swiglu", "geglu"):   # gated: handled at call sites
        return jax.nn.silu if name == "swiglu" else jax.nn.gelu
    if name == "gelu":
        return jax.nn.gelu
    if name == "relu2":   # nemotron squared-ReLU
        return lambda x: jnp.square(jax.nn.relu(x))
    raise ValueError(f"unknown activation {name!r}")


def softcap(x: jax.Array, cap: float) -> jax.Array:
    if not cap:
        return x
    return cap * jnp.tanh(x / cap)


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                       dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array,
               theta: float) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: (..., seq)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                      # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    angles = angles[..., None, :]                      # (..., S, 1, hd/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin,
                           x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(seq: int, dim: int) -> jax.Array:
    pos = np.arange(seq)[:, None]
    i = np.arange(dim // 2)[None, :]
    angle = pos / np.power(10000.0, 2 * i / dim)
    emb = np.concatenate([np.sin(angle), np.cos(angle)], axis=-1)
    return jnp.asarray(emb, dtype=jnp.float32)


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  vocab_size: int) -> jax.Array:
    """Mean CE over tokens; logits (..., V) fp32-accumulated; labels (...)."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(
        logits, labels[..., None].astype(jnp.int32), axis=-1)[..., 0]
    mask = (labels >= 0) & (labels < vocab_size)
    loss = (lse - gold) * mask
    return loss.sum() / jnp.maximum(mask.sum(), 1)


# ---------------------------------------------------------------------------
# Initialisation helpers
# ---------------------------------------------------------------------------


def dense_init(key: jax.Array, shape: Tuple[int, ...],
               dtype: Any, fan_in: Optional[int] = None) -> jax.Array:
    fan_in = fan_in if fan_in is not None else shape[0]
    std = 1.0 / math.sqrt(max(fan_in, 1))
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


class KeyGen:
    """Deterministic key splitter for param init."""

    def __init__(self, key: jax.Array) -> None:
        self.key = key

    def __call__(self) -> jax.Array:
        self.key, sub = jax.random.split(self.key)
        return sub
