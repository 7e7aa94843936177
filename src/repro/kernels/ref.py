"""Pure-jnp oracles for the Pallas kernels (the allclose ground truth)."""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def mha_reference(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool = True, window: int = 0,
                  logit_cap: float = 0.0,
                  scale: Optional[float] = None) -> jax.Array:
    """q: (B,Hq,Sq,D); k/v: (B,Hkv,Sk,D) -> (B,Hq,Sq,D).  GQA by head map;
    ``scale`` multiplies the scores (None: ``1/sqrt(D)``)."""
    b, hq, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    group = hq // hkv
    kx = jnp.repeat(k, group, axis=1)
    vx = jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   kx.astype(jnp.float32))
    s = s / math.sqrt(d) if scale is None else s * scale
    if logit_cap:
        s = logit_cap * jnp.tanh(s / logit_cap)
    rows = jnp.arange(sq)[:, None]
    cols = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= cols <= rows
    if window > 0:
        mask &= (rows - cols) < window
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vx.astype(jnp.float32))
    return out.astype(q.dtype)


def ssd_reference(x: jax.Array, dt: jax.Array, a: jax.Array,
                  b: jax.Array, c: jax.Array,
                  initial_state: Optional[jax.Array] = None
                  ) -> Tuple[jax.Array, jax.Array]:
    """Sequential (exact) SSD recurrence.

    x: (B,H,S,P); dt: (B,H,S); a: (H,); b/c: (B,H,S,N).
    h_t = exp(dt_t a) h_{t-1} + dt_t B_t x_t^T ;  y_t = C_t . h_t
    Returns (y: (B,H,S,P), final_state: (B,H,N,P)).
    """
    B, H, S, P = x.shape
    N = b.shape[-1]
    h0 = (initial_state if initial_state is not None
          else jnp.zeros((B, H, N, P), jnp.float32))

    def step(h, t):
        dA = jnp.exp(dt[:, :, t] * a[None, :])          # (B,H)
        upd = jnp.einsum("bhn,bhp->bhnp", b[:, :, t],
                         x[:, :, t] * dt[:, :, t][..., None])
        h = h * dA[..., None, None] + upd
        y = jnp.einsum("bhn,bhnp->bhp", c[:, :, t], h)
        return h, y

    h, ys = jax.lax.scan(step, h0.astype(jnp.float32), jnp.arange(S))
    y = jnp.moveaxis(ys, 0, 2)                          # (B,H,S,P)
    return y.astype(x.dtype), h.astype(x.dtype)


def ssd_decode_reference(states: jax.Array, out: jax.Array, layer: jax.Array,
                         decay: jax.Array, x: jax.Array, bdt: jax.Array,
                         c: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """One layer's decode state update and read-out, in the XLA form of
    ``models/ssm.py:mamba2_decode_step``.

    states: (L,B,H,P,N); out: (L,B,H,P,N) float32; decay: (B,H);
    x: (B,H,P); bdt: (B,H,N); c: (B,H,N).
    s = states[layer] * decay + (x (x) bdt) rounded to the state's dtype;
    y = sum_N s * C.  Returns (out with layer ``layer`` set to s, y).
    """
    state = states[layer]
    s = (state * decay[..., None, None]
         + jnp.einsum("bhp,bhk->bhpk", x, bdt).astype(state.dtype))
    y = jnp.einsum("bhpk,bhk->bhp", s.astype(jnp.float32), c)
    return out.at[layer].set(s.astype(out.dtype)), y
