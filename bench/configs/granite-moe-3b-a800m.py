"""Plain reference of granite-moe-3b-a800m as the benchmark serves it.

A straightforward float32 forward pass over whole sequences, written from
the published description (ibm-granite/granite-3.0-3b-a800m-base, a
GraniteMoe decoder) and from the semantics the served program states,
with no cache, no kernels and no batching across requests:

* pre-norm decoder layers: RMS norm (eps 1e-6), grouped-query attention
  with rotary positions (theta 10000, half-split rotation), causal
  softmax at scale 1/sqrt(head_dim), then RMS norm and a mixture of
  experts: softmax router over 40 experts, the top 8 gates renormalised
  to sum 1, each expert a SwiGLU feed-forward;
* the program's stated expert capacity: within a prompt, expert ``e``
  takes the first ``max(8, ceil8(int(prompt_len * top_k * 1.25 / E)))``
  of the prompt's tokens routed to it and drops the rest (their share of
  the output is zero, the other gates unchanged); a generated token is
  never dropped, which holds while a decode microbatch has at most
  ``capacity`` rows;
* tied embeddings: the logits are the final RMS norm times the
  embedding table's transpose, over the first ``vocab_size`` rows.

Departures from the published model that the program makes and this
reference follows, so that the two compute the same function: no
``embedding_multiplier`` (12), ``attention_multiplier`` (1/64, here
1/sqrt(64)), ``residual_multiplier`` (0.22) or ``logits_scaling`` (6), and
the capacity drops above (the published model is dropless).

The weights are drawn layer by layer from the seed's key in the order
the served weights are (``harness/reflib.KeyChain``), so no layer but the
current one is held, in float32.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from harness.reflib import HIGHEST, KeyChain, Numerics, fp8, rms_norm, weight


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def capacity(a: Dict, tokens: int) -> int:
    c = int(tokens * a["top_k"] * a["capacity_factor"] / a["num_experts"])
    return max(8, (c + 7) // 8 * 8)


def _gen_layer(a: Dict, key: jax.Array):
    d, f, e = a["d_model"], a["d_ff"], a["num_experts"]
    nq, nkv, hd = a["num_heads"], a["num_kv_heads"], a["head_dim"]
    kc = KeyChain(key)
    dt = jnp.dtype(a["dtype"])
    w = {"wq": weight(kc(), (d, nq, hd), d, dt),
         "wk": weight(kc(), (d, nkv, hd), d, dt),
         "wv": weight(kc(), (d, nkv, hd), d, dt),
         "wo": weight(kc(), (nq, hd, d), nq * hd, dt),
         "router": weight(kc(), (d, e), d, jnp.float32),
         "w1": weight(kc(), (e, d, f), d, dt),
         "w2": weight(kc(), (e, f, d), f, dt),
         "w3": weight(kc(), (e, d, f), d, dt)}
    return w, kc.key


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """x: (B, T, H, D) at positions 0..T-1."""
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(a: Dict, control: bool, prompt_len: int, w: Dict,
           h: jax.Array) -> jax.Array:
    """One decoder layer over h: (B, T, d), all rows of one length whose
    first ``prompt_len`` tokens are the prompt."""
    num = Numerics(control)
    B, T, _ = h.shape
    nq, nkv, hd = a["num_heads"], a["num_kv_heads"], a["head_dim"]
    g = nq // nkv

    x = rms_norm(h)
    q = _rope(num.mm("btd,dhk->bthk", x, w["wq"]), a["rope_theta"])
    k = _rope(num.mm("btd,dhk->bthk", x, w["wk"]), a["rope_theta"])
    v = num.mm("btd,dhk->bthk", x, w["wv"])
    q = num.act(q).reshape(B, T, nkv, g, hd)
    s = jnp.einsum("btkgd,bskd->bkgts", q, num.act(k),
                   precision=HIGHEST) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bkgts,bskd->btkgd", num.act(p), num.act(v),
                   precision=HIGHEST).reshape(B, T, nq, hd)
    h = h + num.mm("bthk,hkd->btd", o, w["wo"])

    x = rms_norm(h)
    probs = jax.nn.softmax(num.mm("btd,de->bte", x, w["router"]), axis=-1)
    gates, idx = jax.lax.top_k(probs, a["top_k"])
    gates = gates / gates.sum(-1, keepdims=True)
    chosen = jax.nn.one_hot(idx, a["num_experts"])            # (B,T,k,E)
    routed = chosen.sum(2)                                     # (B,T,E)
    in_prompt = (jnp.arange(T) < prompt_len)[None, :, None]
    rank = jnp.cumsum(routed * in_prompt, axis=1) - 1
    keep = jnp.where(in_prompt, rank < capacity(a, prompt_len), True)
    combine = (chosen * gates[..., None]).sum(2) * keep        # (B,T,E)

    def expert(y, e):
        h1 = num.mm("btd,df->btf", x, w["w1"][e])
        h3 = num.mm("btd,df->btf", x, w["w3"][e])
        out = num.mm("btf,fd->btd", jax.nn.silu(h1) * h3, w["w2"][e])
        return y + combine[..., e, None] * out, None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h), jnp.arange(a["num_experts"]))
    return h + y


def _logits(a: Dict, control: bool, head: jax.Array, h: jax.Array):
    num = Numerics(control)
    return num.mm("btd,vd->btv", rms_norm(h), head)[..., :a["vocab_size"]]


def reference_logits(a: Dict, key_int: int, seqs: Sequence[np.ndarray],
                     prompt_lens: Sequence[int], control: bool = False
                     ) -> Tuple[List[np.ndarray], Optional[List[np.ndarray]]]:
    """Logits at the positions that predict each sequence's served tokens.

    ``seqs[i]`` is a prompt of ``prompt_lens[i]`` tokens followed by all
    but the last served token.  Returns, per sequence, the float32 logits
    of positions ``prompt_len - 1`` to the end (one row per served token),
    and with ``control`` the same from the fp8 control."""
    d, vocab = a["d_model"], a["vocab_size"]
    vp = _round_up(vocab, 256)
    kc = KeyChain(jax.random.PRNGKey(key_int))
    dt = jnp.dtype(a["dtype"])
    embed = jax.jit(lambda k: weight(k, (vp, d), d, dt))(kc())
    assert a["tie_embeddings"]
    gen = jax.jit(lambda k: _gen_layer(a, k))
    modes = [False, True] if control else [False]
    # rows of one length and prompt length are batched together
    groups: Dict[Tuple[int, int], List[int]] = {}
    for i, (s, p) in enumerate(zip(seqs, prompt_lens)):
        groups.setdefault((len(s), int(p)), []).append(i)
    layer = {(m, p): jax.jit(lambda w, h, m=m, p=p: _layer(a, m, p, w, h))
             for m in modes for (_, p) in groups}
    hs = {}
    for m in modes:
        table = fp8(embed) if m else embed
        for key, rows in groups.items():
            toks = jnp.asarray(np.stack([seqs[i] for i in rows]))
            hs[m, key] = table[toks]
    layer_key = kc.key
    for _ in range(a["num_layers"]):
        w, layer_key = gen(layer_key)
        for (m, key) in hs:
            hs[m, key] = layer[m, key[1]](w, hs[m, key])
        del w
    out = {m: [None] * len(seqs) for m in modes}
    head = {m: jax.jit(lambda e, h, m=m: _logits(a, m, e, h)) for m in modes}
    for (m, key), h in hs.items():
        lg = np.asarray(head[m](embed, h[:, key[1] - 1:]))
        for j, i in enumerate(groups[key]):
            out[m][i] = lg[j]
    return out[False], (out[True] if control else None)
