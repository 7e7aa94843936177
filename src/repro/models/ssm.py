"""Mamba2 — State Space Duality (SSD), chunked scan + O(1) decode.

The SSD "dual form" (arXiv:2405.21060) computes the selective-SSM sequence
mixing as chunk-local attention-like matmuls plus a tiny cross-chunk
recurrence — ideal for the TPU MXU: all heavy ops are (Q x Q) / (Q x N)
matmuls with Q = chunk length, N = state size.

The chunk-local contraction is also available as a Pallas kernel
(``repro.kernels.ssd_scan``); this file is the pure-jnp form the dry-run
lowers and the oracle the kernel is tested against.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .common import ArchConfig, KeyGen, dense_init, rms_norm


def init_mamba2(kg: KeyGen, cfg: ArchConfig, dtype: Any
                ) -> Dict[str, jax.Array]:
    d = cfg.d_model
    di, n, g, h = cfg.ssm_inner, cfg.ssm_state, cfg.ssm_groups, cfg.ssm_heads
    conv_ch = di + 2 * g * n
    return {
        "in_proj": dense_init(kg(), (d, 2 * di + 2 * g * n + h), dtype,
                              fan_in=d),
        "conv_w": dense_init(kg(), (cfg.ssm_conv, conv_ch), dtype,
                             fan_in=cfg.ssm_conv),
        "conv_b": jnp.zeros((conv_ch,), dtype),
        "A_log": jnp.zeros((h,), jnp.float32),
        "D": jnp.ones((h,), jnp.float32),
        "dt_bias": jnp.zeros((h,), jnp.float32),
        "norm": jnp.zeros((di,), dtype),
        "out_proj": dense_init(kg(), (di, d), dtype, fan_in=di),
    }


def _split_proj(cfg: ArchConfig, zxbcdt: jax.Array):
    di, n, g, h = cfg.ssm_inner, cfg.ssm_state, cfg.ssm_groups, cfg.ssm_heads
    z, x, bc, dt = jnp.split(
        zxbcdt, [di, 2 * di, 2 * di + 2 * g * n], axis=-1)
    b_, c_ = jnp.split(bc, 2, axis=-1)
    return z, x, b_, c_, dt


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv1d.  x: (B,S,C), w: (W,C)."""
    wsz = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (wsz - 1, 0), (0, 0)))
    out = sum(xp[:, i:i + x.shape[1], :] * w[i] for i in range(wsz))
    return jax.nn.silu(out + b)


def ssd_chunked(x: jax.Array, dt: jax.Array, a: jax.Array, b_: jax.Array,
                c_: jax.Array, chunk: int,
                initial_state: Optional[jax.Array] = None,
                use_kernel: bool = False
                ) -> Tuple[jax.Array, jax.Array]:
    """SSD dual-form scan.

    x: (B,S,H,P)   dt: (B,S,H)   a: (H,) negative decay rates
    b_, c_: (B,S,G,N) with G groups broadcast over H heads.
    Returns (y: (B,S,H,P), final_state: (B,H,N,P)).
    """
    B, S, H, P = x.shape
    G, N = b_.shape[2], b_.shape[3]
    assert S % chunk == 0, (S, chunk)
    nc, Q = S // chunk, chunk
    rep = H // G

    if use_kernel:
        from ..kernels import ops as kops
        return kops.ssd_scan(x, dt, a, b_, c_, chunk,
                             initial_state=initial_state)

    xc = x.reshape(B, nc, Q, H, P)
    dtc = dt.reshape(B, nc, Q, H)                       # already softplus'ed
    bc = jnp.repeat(b_.reshape(B, nc, Q, G, N), rep, axis=3)  # (B,nc,Q,H,N)
    cc = jnp.repeat(c_.reshape(B, nc, Q, G, N), rep, axis=3)

    dA = dtc * a[None, None, None, :]                   # (B,nc,Q,H) log-decay
    cum = jnp.cumsum(dA, axis=2)                        # (B,nc,Q,H)

    # ---- intra-chunk (the "attention-like" quadratic term) --------------------
    # L[i,j] = exp(cum_i - cum_j) for i >= j
    li = cum[:, :, :, None, :]                          # (B,nc,Q,1,H)
    lj = cum[:, :, None, :, :]                          # (B,nc,1,Q,H)
    mask = jnp.tril(jnp.ones((Q, Q), bool))[None, None, :, :, None]
    L = jnp.where(mask, jnp.exp(li - lj), 0.0)          # (B,nc,Q,Q,H)
    scores = jnp.einsum("bnihk,bnjhk->bnijh", cc, bc)   # (B,nc,Q,Q,H)
    att = scores * L * dtc[:, :, None, :, :]            # weight by dt_j
    y_intra = jnp.einsum("bnijh,bnjhp->bnihp", att, xc)

    # ---- chunk states ------------------------------------------------------------
    decay_to_end = jnp.exp(cum[:, :, -1:, :] - cum)     # (B,nc,Q,H)
    weighted_x = xc * (dtc * decay_to_end)[..., None]   # (B,nc,Q,H,P)
    states = jnp.einsum("bnqhk,bnqhp->bnhkp", bc, weighted_x)  # (B,nc,H,N,P)

    # ---- inter-chunk recurrence ----------------------------------------------------
    # log-depth associative scan over chunks (no while-loop in the HLO:
    # cheaper on the MXU pipeline AND correctly accounted by cost analysis).
    # Composition of (decay a, state b): (a1,b1)*(a2,b2) = (a1a2, a2b1+b2).
    chunk_decay = jnp.exp(cum[:, :, -1, :])             # (B,nc,H)
    h0 = (initial_state if initial_state is not None
          else jnp.zeros((B, H, N, P), x.dtype)).astype(jnp.float32)

    def combine(left, right):
        a1, b1 = left
        a2, b2 = right
        return a1 * a2, a2[..., None, None] * b1 + b2

    a_scan, b_scan = jax.lax.associative_scan(
        combine, (chunk_decay.astype(jnp.float32),
                  states.astype(jnp.float32)), axis=1)
    # inclusive scan gives state AFTER each chunk; shift right for BEFORE
    h_after = (a_scan[..., None, None] * h0[:, None] + b_scan)
    h_prevs = jnp.concatenate([h0[:, None], h_after[:, :-1]],
                              axis=1).astype(x.dtype)   # (B,nc,H,N,P)
    h_final = h_after[:, -1].astype(x.dtype)

    # ---- inter-chunk contribution ----------------------------------------------------
    y_inter = jnp.einsum("bnqhk,bnhkp->bnqhp",
                         cc * jnp.exp(cum)[..., None], h_prevs)
    y = (y_intra + y_inter).reshape(B, S, H, P)
    return y, h_final


def mamba2_forward(p: Dict[str, jax.Array], x: jax.Array, cfg: ArchConfig,
                   use_kernel: bool = False) -> jax.Array:
    """Full-sequence Mamba2 mixer.  x: (B,S,d) -> (B,S,d)."""
    B, S, d = x.shape
    di, n, g, h = cfg.ssm_inner, cfg.ssm_state, cfg.ssm_groups, cfg.ssm_heads
    P = cfg.ssm_headdim
    zxbcdt = jnp.einsum("bsd,dk->bsk", x, p["in_proj"])
    z, xin, b_, c_, dt = _split_proj(cfg, zxbcdt)
    conv_in = jnp.concatenate([xin, b_, c_], axis=-1)
    conv_out = _causal_conv(conv_in, p["conv_w"], p["conv_b"])
    xin, b_, c_ = jnp.split(conv_out, [di, di + g * n], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    a = -jnp.exp(p["A_log"])
    xh = xin.reshape(B, S, h, P)
    y, _ = ssd_chunked(xh, dt, a,
                       b_.reshape(B, S, g, n), c_.reshape(B, S, g, n),
                       min(cfg.ssm_chunk, S), use_kernel=use_kernel)
    y = (y + xh * p["D"][None, None, :, None]).astype(x.dtype)
    y = y.reshape(B, S, di)
    y = rms_norm(y * jax.nn.silu(z), p["norm"])
    return jnp.einsum("bsk,kd->bsd", y, p["out_proj"])


# ---------------------------------------------------------------------------
# Decode (O(1) per token — why SSM archs run the long_500k cell)
# ---------------------------------------------------------------------------


def init_ssm_cache(cfg: ArchConfig, batch: int, dtype: Any
                   ) -> Dict[str, jax.Array]:
    """Conv window (B, W-1, C) and SSM state (B, h, headdim, d_state).

    The state is stored with d_state minor, the order the decode step's
    update and read-out contract in, so the TPU keeps it in one layout
    across steps; ``ssd_chunked``'s (B, h, d_state, headdim) final state
    is transposed once at prefill.
    """
    di, n, g = cfg.ssm_inner, cfg.ssm_state, cfg.ssm_groups
    h, P = cfg.ssm_heads, cfg.ssm_headdim
    conv_ch = di + 2 * g * n
    return {
        "conv": jnp.zeros((batch, cfg.ssm_conv - 1, conv_ch), dtype),
        "state": jnp.zeros((batch, h, P, n), dtype),
    }


def _decode_inputs(p: Dict[str, jax.Array], x: jax.Array, conv: jax.Array,
                   cfg: ArchConfig):
    """The decode step up to the state update.  x: (B,1,d) one token;
    conv: (B,W-1,C) window.  Returns the gate z, the heads' input xh
    (B,h,P), the decay (B,h) and B*dt (B,h,n) in float32, C (B,h,n) and
    the new conv window."""
    B = x.shape[0]
    di, n, g = cfg.ssm_inner, cfg.ssm_state, cfg.ssm_groups
    h, P = cfg.ssm_heads, cfg.ssm_headdim
    zxbcdt = jnp.einsum("bsd,dk->bsk", x, p["in_proj"])
    z, xin, b_, c_, dt = _split_proj(cfg, zxbcdt)
    conv_in = jnp.concatenate([xin, b_, c_], axis=-1)[:, 0]   # (B,C)
    window = jnp.concatenate([conv, conv_in[:, None]], axis=1)
    conv_out = jax.nn.silu(
        jnp.einsum("bwc,wc->bc", window, p["conv_w"]) + p["conv_b"])
    xin, b_, c_ = jnp.split(conv_out, [di, di + g * n], axis=-1)

    dt = jax.nn.softplus(dt[:, 0].astype(jnp.float32)
                         + p["dt_bias"])                      # (B,h)
    a = -jnp.exp(p["A_log"])
    decay = jnp.exp(dt * a)                                    # (B,h)
    rep = h // g
    bh = jnp.repeat(b_.reshape(B, g, n), rep, axis=1)          # (B,h,n)
    ch = jnp.repeat(c_.reshape(B, g, n), rep, axis=1)
    xh = xin.reshape(B, h, P)
    return z, xh, decay, bh * dt[..., None], ch, window[:, 1:]


def _decode_output(p: Dict[str, jax.Array], x: jax.Array, z: jax.Array,
                   xh: jax.Array, y: jax.Array, cfg: ArchConfig) -> jax.Array:
    """The decode step after the read-out y (B,h,P) float32: the D skip,
    the gate, the norm and the output projection."""
    y = y + xh.astype(jnp.float32) * p["D"][None, :, None]
    y = y.reshape(x.shape[0], 1, cfg.ssm_inner).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z), p["norm"])
    return jnp.einsum("bsk,kd->bsd", y, p["out_proj"])


def mamba2_decode_step(p: Dict[str, jax.Array], x: jax.Array,
                       cache: Dict[str, jax.Array], cfg: ArchConfig
                       ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x: (B,1,d) one token; cache: conv window + SSM state."""
    z, xh, decay, bdt, ch, new_conv = _decode_inputs(p, x, cache["conv"],
                                                      cfg)
    state = (cache["state"] * decay[..., None, None]
             + jnp.einsum("bhp,bhk->bhpk", xh,
                          bdt).astype(cache["state"].dtype))
    y = jnp.einsum("bhpk,bhk->bhp", state.astype(jnp.float32), ch)
    out = _decode_output(p, x, z, xh, y, cfg)
    return out, {"conv": new_conv, "state": state}


def mamba2_decode_layer(p: Dict[str, jax.Array], x: jax.Array,
                        conv: jax.Array, states: jax.Array, out: jax.Array,
                        layer: jax.Array, cfg: ArchConfig
                        ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``mamba2_decode_step`` for layer ``layer`` of a whole cache, with
    the state update and read-out in one kernel (``kernels/ssd_decode.py``).

    states: the cache's (L,B,h,P,n) state stack, read at ``layer`` in
    place; out: the (L,B,h,P,n) float32 stack of new states, returned
    with layer ``layer`` written.  Returns (y, new conv window, out)."""
    from ..kernels import ops as kops
    z, xh, decay, bdt, ch, new_conv = _decode_inputs(p, x, conv, cfg)
    out, y = kops.ssd_decode(states, out, layer, decay, xh, bdt, ch)
    return _decode_output(p, x, z, xh, y, cfg), new_conv, out
