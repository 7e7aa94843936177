"""Plain reference of mamba2-1.3b as the benchmark serves it.

A straightforward float32 forward pass over whole sequences, written from
the Mamba-2 paper (arXiv:2405.21060) and the published configuration
(state-spaces/mamba2-1.3b), with no cache, no kernels and no chunking:
the SSD layer is run in its recurrent form, one token after another.

Each of the 48 layers: RMS norm (eps 1e-6), an input projection to
``z, x, B, C, dt``, a depthwise causal convolution of width 4 with bias
and SiLU over ``x, B, C``, ``dt = softplus(dt + dt_bias)``, and per head
the recurrence ``S_t = exp(dt_t * A) S_{t-1} + dt_t * B_t x_t^T``,
``y_t = C_t S_t + D x_t`` (one group: ``B``, ``C`` shared by the heads),
then a gated RMS norm ``norm(y * silu(z))`` and the output projection,
added to the residual.  Embeddings are tied: the logits are the final
RMS norm times the embedding table's transpose, over the first
``vocab_size`` rows.

The weights are drawn layer by layer from the seed's key in the order
the served weights are (``harness/reflib.KeyChain``).  At initialisation
``A_log``, ``dt_bias``, the conv bias and the norm scales are zero and
``D`` is one, so ``A = -1`` for every head.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from harness.reflib import HIGHEST, KeyChain, Numerics, fp8, rms_norm, weight


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _sizes(a: Dict):
    d = a["d_model"]
    di = a["ssm_expand"] * d
    n, g, p = a["ssm_state"], a["ssm_groups"], a["ssm_headdim"]
    return d, di, n, g, di // p, p, di + 2 * g * n


def _gen_layer(a: Dict, key: jax.Array):
    d, di, n, g, h, _, conv_ch = _sizes(a)
    kc = KeyChain(key)
    dt = jnp.dtype(a["dtype"])
    w = {"in_proj": weight(kc(), (d, 2 * di + 2 * g * n + h), d, dt),
         "conv_w": weight(kc(), (a["ssm_conv"], conv_ch), a["ssm_conv"], dt),
         "out_proj": weight(kc(), (di, d), di, dt)}
    return w, kc.key


def _layer(a: Dict, control: bool, w: Dict, hid: jax.Array) -> jax.Array:
    num = Numerics(control)
    d, di, n, g, h, p, _ = _sizes(a)
    B, T, _ = hid.shape
    width = a["ssm_conv"]
    zxbcdt = num.mm("btd,dk->btk", rms_norm(hid), w["in_proj"])
    z, xbc, dt = jnp.split(zxbcdt, [di, 2 * di + 2 * g * n], axis=-1)
    pad = jnp.pad(xbc, ((0, 0), (width - 1, 0), (0, 0)))
    conv = sum(pad[:, i:i + T] * w["conv_w"][i] for i in range(width))
    xs, bs, cs = jnp.split(jax.nn.silu(conv), [di, di + g * n], axis=-1)
    dt = jax.nn.softplus(dt)                            # (B, T, h)
    a_rate = -1.0
    xs = xs.reshape(B, T, h, p)

    def step(state, t):
        x_t, b_t, c_t, dt_t = t                         # (B,h,p) (B,n) (B,n) (B,h)
        state = (jnp.exp(dt_t * a_rate)[..., None, None] * state
                 + dt_t[..., None, None] * b_t[:, None, :, None]
                 * x_t[:, :, None, :])
        y = jnp.einsum("bn,bhnp->bhp", c_t, state, precision=HIGHEST)
        return state, y + x_t

    state0 = jnp.zeros((B, h, n, p), jnp.float32)
    seq = (jnp.moveaxis(xs, 1, 0), jnp.moveaxis(bs, 1, 0),
           jnp.moveaxis(cs, 1, 0), jnp.moveaxis(dt, 1, 0))
    _, ys = jax.lax.scan(step, state0, seq)
    y = jnp.moveaxis(ys, 0, 1).reshape(B, T, di)
    y = rms_norm(y * jax.nn.silu(z))
    return hid + num.mm("btk,kd->btd", y, w["out_proj"])


def _logits(a: Dict, control: bool, head: jax.Array, h: jax.Array):
    num = Numerics(control)
    return num.mm("btd,vd->btv", rms_norm(h), head)[..., :a["vocab_size"]]


def reference_logits(a: Dict, key_int: int, seqs: Sequence[np.ndarray],
                     prompt_lens: Sequence[int], control: bool = False
                     ) -> Tuple[List[np.ndarray], Optional[List[np.ndarray]]]:
    """Logits at the positions that predict each sequence's served tokens
    (see the granite reference for the contract)."""
    d, vocab = a["d_model"], a["vocab_size"]
    vp = _round_up(vocab, 256)
    kc = KeyChain(jax.random.PRNGKey(key_int))
    dt = jnp.dtype(a["dtype"])
    embed = jax.jit(lambda k: weight(k, (vp, d), d, dt))(kc())
    assert a["tie_embeddings"]
    gen = jax.jit(lambda k: _gen_layer(a, k))
    modes = [False, True] if control else [False]
    groups: Dict[Tuple[int, int], List[int]] = {}
    for i, (s, p) in enumerate(zip(seqs, prompt_lens)):
        groups.setdefault((len(s), int(p)), []).append(i)
    layer = {m: jax.jit(lambda w, h, m=m: _layer(a, m, w, h)) for m in modes}
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
            hs[m, key] = layer[m](w, hs[m, key])
        del w
    out = {m: [None] * len(seqs) for m in modes}
    head = {m: jax.jit(lambda e, h, m=m: _logits(a, m, e, h)) for m in modes}
    for (m, key), h in hs.items():
        lg = np.asarray(head[m](embed, h[:, key[1] - 1:]))
        for j, i in enumerate(groups[key]):
            out[m][i] = lg[j]
    return out[False], (out[True] if control else None)
