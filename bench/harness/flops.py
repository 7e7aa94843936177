"""Model FLOPs of serving work, from a configuration's sizes alone.

This counts the work that any implementation of the same semantics must
do, not what the program happens to compute:

* 2 FLOPs per multiply-add of every weight a token uses: attention and
  SSM projections, the router and the ``top_k`` experts it picks (the MoE
  capacity slots the program computes and then discards do not count);
  the embedding is a gather and counts nothing;
* the LM head (2 * d_model * vocab) once for every token whose logits are
  needed: each decode token, and the last prompt token of a prefill;
* causal attention: QK^T and PV over the keys up to the token's position,
  4 * heads * head_dim FLOPs per key;
* Mamba-2: the recurrent form of the SSD scan, per head the state update
  (decay, outer product, add: 3 * N * P) and the read-out (2 * N * P),
  plus the depthwise causal convolution (2 * width per channel).

A share of the peak above 100% therefore means this count or the time is
wrong, not that the chip was fast.  ``bench/tests`` checks these counts
against hand counts.
"""
from __future__ import annotations

from typing import Any, Dict


def _hd(a: Dict[str, Any]) -> int:
    return a.get("head_dim") or a["d_model"] // a["num_heads"]


def layer_matmul_flops(a: Dict[str, Any]) -> int:
    """FLOPs of one layer's weights for one token (attention excluded)."""
    d = a["d_model"]
    if a["family"] == "ssm":
        di = a["ssm_expand"] * d
        n, g = a["ssm_state"], a.get("ssm_groups", 1)
        h = di // a["ssm_headdim"]
        in_proj = d * (2 * di + 2 * g * n + h)
        return 2 * (in_proj + di * d)
    hd, nq, nkv = _hd(a), a["num_heads"], a["num_kv_heads"]
    attn = d * nq * hd + 2 * d * nkv * hd + nq * hd * d
    ffn = 3 * d * a["d_ff"]        # gated (SwiGLU) feed-forward
    if a["family"] == "moe":
        mlp = d * a["num_experts"] + a["top_k"] * ffn
    else:
        mlp = ffn
    return 2 * (attn + mlp)


def layer_mixing_flops(a: Dict[str, Any], pos: int) -> int:
    """FLOPs of one layer's sequence mixing for the token at ``pos``
    (0-based): attention over ``pos + 1`` keys, or one SSD scan step."""
    if a["family"] == "ssm":
        d = a["d_model"]
        di = a["ssm_expand"] * d
        n, g = a["ssm_state"], a.get("ssm_groups", 1)
        h, p = di // a["ssm_headdim"], a["ssm_headdim"]
        conv = 2 * a.get("ssm_conv", 4) * (di + 2 * g * n)
        return conv + 5 * h * n * p
    return 4 * a["num_heads"] * _hd(a) * (pos + 1)


def head_flops(a: Dict[str, Any]) -> int:
    return 2 * a["d_model"] * a["vocab_size"]


def token_flops(a: Dict[str, Any], pos: int, logits: bool) -> int:
    """FLOPs of one token at position ``pos`` through the whole model."""
    per_layer = layer_matmul_flops(a) + layer_mixing_flops(a, pos)
    return a["num_layers"] * per_layer + (head_flops(a) if logits else 0)


def prefill_flops(a: Dict[str, Any], batch: int, prompt_len: int) -> int:
    """One prefill of ``batch`` prompts of ``prompt_len`` tokens."""
    L = a["num_layers"]
    per_seq = prompt_len * L * layer_matmul_flops(a) + head_flops(a)
    if a["family"] == "ssm":
        per_seq += prompt_len * L * layer_mixing_flops(a, 0)
    else:
        # sum over positions of (pos + 1) keys
        keys = prompt_len * (prompt_len + 1) // 2
        per_seq += L * 4 * a["num_heads"] * _hd(a) * keys
    return batch * per_seq


def decode_flops(a: Dict[str, Any], batch: int, pos: int) -> int:
    """One decode step of ``batch`` tokens at position ``pos``."""
    return batch * token_flops(a, pos, logits=True)
