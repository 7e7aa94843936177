"""Plain reference of granite-3.0-3b-a800m, the published model.

A straightforward float32 forward pass over whole sequences, written from
the published GraniteMoe decoder (ibm-granite/granite-3.0-3b-a800m-base,
``model_type`` granitemoe), with no cache, no kernels, no batching across
requests and no dispatch:

* the input is the token's embedding row times ``embedding_multiplier``
  (12);
* each layer is pre-norm: RMS norm (eps 1e-6), grouped-query attention
  with rotary positions (theta 10000, half-split rotation) and a causal
  softmax over ``attention_multiplier * q.k`` (1/64), whose output
  projection is added to the residual times ``residual_multiplier``
  (0.22); then RMS norm and the mixture of experts, added the same way;
* the mixture of experts is dropless: the router's logits (float32) are
  taken at their top 8 of 40 experts, the gates are the softmax over those
  8, and each token's output is the gate-weighted sum of its 8 experts'
  SwiGLU feed-forwards, ``w2(silu(w1 x) * w3 x)``, every token computed;
* tied embeddings: the logits are the final RMS norm times the embedding
  table's transpose, over the first ``vocab_size`` rows, divided by
  ``logits_scaling`` (6).

The four multipliers are read from the configuration's ``arch`` block
under the names above.  The weights are random, drawn layer by layer from
the seed's key in the order the served weights are
(``harness/reflib.KeyChain``), so no layer but the current one is held, in
float32.  The embedding table is drawn at ``1/embedding_multiplier`` of
the usual scale (fan-in ``d_model * 12**2``), as the program draws it.
Every norm scale is 1, as the served weights' are.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from harness.reflib import HIGHEST, KeyChain, Numerics, fp8, rms_norm, weight


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


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


def _layer(a: Dict, control: bool, w: Dict, h: jax.Array) -> jax.Array:
    """One decoder layer over h: (B, T, d)."""
    num = Numerics(control)
    B, T, _ = h.shape
    nq, nkv, hd = a["num_heads"], a["num_kv_heads"], a["head_dim"]
    g = nq // nkv
    res = a["residual_multiplier"]

    x = rms_norm(h)
    q = _rope(num.mm("btd,dhk->bthk", x, w["wq"]), a["rope_theta"])
    k = _rope(num.mm("btd,dhk->bthk", x, w["wk"]), a["rope_theta"])
    v = num.mm("btd,dhk->bthk", x, w["wv"])
    q = num.act(q).reshape(B, T, nkv, g, hd)
    s = a["attention_multiplier"] * jnp.einsum(
        "btkgd,bskd->bkgts", q, num.act(k), precision=HIGHEST)
    causal = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bkgts,bskd->btkgd", num.act(p), num.act(v),
                   precision=HIGHEST).reshape(B, T, nq, hd)
    h = h + res * num.mm("bthk,hkd->btd", o, w["wo"])

    x = rms_norm(h)
    logits = num.mm("btd,de->bte", x, w["router"])
    top, idx = jax.lax.top_k(logits, a["top_k"])
    gates = jax.nn.softmax(top, axis=-1)                       # (B,T,k)
    # each expert's weight in each token's output: its gate where chosen
    combine = (jax.nn.one_hot(idx, a["num_experts"])
               * gates[..., None]).sum(2)                      # (B,T,E)

    def expert(y, e):
        h1 = num.mm("btd,df->btf", x, w["w1"][e])
        h3 = num.mm("btd,df->btf", x, w["w3"][e])
        out = num.mm("btf,fd->btd", jax.nn.silu(h1) * h3, w["w2"][e])
        return y + combine[..., e, None] * out, None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h), jnp.arange(a["num_experts"]))
    return h + res * y


def _logits(a: Dict, control: bool, head: jax.Array, h: jax.Array):
    num = Numerics(control)
    lg = num.mm("btd,vd->btv", rms_norm(h), head)[..., :a["vocab_size"]]
    return lg / a["logits_scaling"]


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
    em = a["embedding_multiplier"]
    embed = jax.jit(lambda k: weight(k, (vp, d), d * em * em, dt))(kc())
    assert a["tie_embeddings"]
    gen = jax.jit(lambda k: _gen_layer(a, k))
    modes = [False, True] if control else [False]
    # rows of one length and prompt length are batched together
    groups: Dict[Tuple[int, int], List[int]] = {}
    for i, (s, p) in enumerate(zip(seqs, prompt_lens)):
        groups.setdefault((len(s), int(p)), []).append(i)
    layer = {m: jax.jit(lambda w, h, m=m: _layer(a, m, w, h)) for m in modes}
    hs = {}
    for m in modes:
        table = fp8(embed) if m else embed
        for key, rows in groups.items():
            toks = jnp.asarray(np.stack([seqs[i] for i in rows]))
            hs[m, key] = table[toks] * em
    layer_key = kc.key
    for _ in range(a["num_layers"]):
        w, layer_key = gen(layer_key)
        for (m, key) in hs:
            hs[m, key] = layer[m](w, hs[m, key])
        del w
    out = {m: [None] * len(seqs) for m in modes}
    head = {m: jax.jit(lambda e, h, m=m: _logits(a, m, e, h)) for m in modes}
    for (m, key), h in hs.items():
        lg = np.asarray(head[m](embed, h[:, key[1] - 1:]))
        for j, i in enumerate(groups[key]):
            out[m][i] = lg[j]
    return out[False], (out[True] if control else None)
