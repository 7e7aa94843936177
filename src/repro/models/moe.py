"""Mixture-of-Experts layer: top-k routing, then dropless grouped-matmul
experts or capacity dispatch.

Dropless (``cfg.moe_dropless``, the published Granite MoE): the token x k
assignments are sorted by expert and the three expert matmuls run as
grouped matmuls (``jax.lax.ragged_dot``) over the routed rows only; every
assignment is computed.  On the TPU, ``ragged_dot`` lowers to its own
kernel.

TPU-native adaptation of the paper's ``GroupBy`` corner-turn: the token ->
expert shuffle is *exactly* DALiuGE's static re-grouping (keys known a
priori: the router's top-k).  Under capacity dispatch it is a
scatter/gather pair that GSPMD lowers to all-to-all when experts and
tokens live on different mesh axes.

Capacity dispatch is group-wise (GShard-style): tokens are viewed as
(groups, S, d) with per-group expert capacity
C = S*top_k*capacity_factor/E.  Instead of the
classic one-hot dispatch einsum — O(S*E*C) memory, infeasible at 1M tokens —
we use scatter-add / gather with computed slot positions, which XLA handles
as dynamic-update ops and shards cleanly.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..sharding import ctx as sctx
from .common import ArchConfig, KeyGen, activation_fn, dense_init


def init_moe(kg: KeyGen, cfg: ArchConfig, dtype: Any) -> Dict[str, jax.Array]:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    p = {
        "router": dense_init(kg(), (d, e), jnp.float32, fan_in=d),
        "w1": dense_init(kg(), (e, d, f), dtype, fan_in=d),
        "w2": dense_init(kg(), (e, f, d), dtype, fan_in=f),
    }
    if cfg.activation in ("swiglu", "geglu"):
        p["w3"] = dense_init(kg(), (e, d, f), dtype, fan_in=d)
    return p


def expert_capacity(cfg: ArchConfig, tokens_per_group: int) -> int:
    c = int(tokens_per_group * cfg.top_k * cfg.capacity_factor
            / cfg.num_experts)
    return max(8, (c + 7) // 8 * 8)


def _route(p: Dict[str, jax.Array], x: jax.Array, cfg: ArchConfig
           ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Router in float32 -> top-k -> gates renormalised to sum 1.

    x: (..., d) -> (gates (..., k), idx (..., k), aux):
    ``aux`` is the Switch/GShard load-balancing loss
    E * mean(frac_i * prob_i) over every token."""
    e, k = cfg.num_experts, cfg.top_k
    logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    me = probs.reshape(-1, e).mean(axis=0)
    ce = jax.nn.one_hot(idx[..., 0], e).reshape(-1, e).mean(axis=0)
    return gates, idx, e * jnp.sum(me * ce)


def _expert_ffn(cfg: ArchConfig, h1: jax.Array, h3) -> jax.Array:
    """The gated (or plain) activation between the first and last expert
    matmuls; ``h3`` is the gate branch (None for ungated activations)."""
    if cfg.activation in ("swiglu", "geglu"):
        gate = jax.nn.silu if cfg.activation == "swiglu" else jax.nn.gelu
        return gate(h1) * h3
    return activation_fn(cfg.activation)(h1)


def _dropless(p: Dict[str, jax.Array], x: jax.Array, cfg: ArchConfig,
              layer: Optional[jax.Array] = None
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Every token's k assignments, grouped by expert, through one grouped
    matmul per expert weight: nothing is dropped and no capacity is
    computed.  x: (T, d) -> (y (T, d), aux, rows per expert (E,)).

    With ``layer`` (the inference scans), ``p`` holds the layer's router
    and the whole (L, E, ...) expert stacks.  On the TPU the grouped
    matmuls read layer ``layer``'s experts where they lie (``_in_place``).
    Elsewhere ``ragged_dot`` is lowered densely over every group, which
    over L*E groups would multiply by every layer's experts and round
    unlike the layer's own E, so the layer's experts are sliced out."""
    t, d = x.shape
    e, k = cfg.num_experts, cfg.top_k
    prof = sctx.current()
    if prof is not None and prof.mesh is not None and prof.expert_axis:
        raise NotImplementedError(
            "dropless MoE under a mesh that shards experts "
            f"(profile {prof.name!r}, axis {prof.expert_axis!r}) is not "
            "supported: expert-parallel dropless dispatch is not written")
    with jax.named_scope("moe.route"):
        gates, idx, aux = _route(p, x, cfg)
        flat = idx.reshape(t * k)
        order = jnp.argsort(flat, stable=True)       # assignments by expert
        rows = jnp.bincount(flat, length=e).astype(jnp.int32)
    with jax.named_scope("moe.gmm"):
        xs = x[order // k]                           # (T*k, d), sorted
        w = {n: p[n] for n in ("w1", "w2", "w3") if n in p}
        if layer is None:
            out = _gmm(cfg, xs, w, rows)
        else:
            out = jax.lax.platform_dependent(
                xs, w, rows, layer,
                tpu=lambda xs, w, rows, i: _gmm(
                    cfg, xs, *_in_place(w, rows, i)),
                default=lambda xs, w, rows, i: _gmm(
                    cfg, xs, {n: a[i] for n, a in w.items()}, rows))
    with jax.named_scope("moe.combine"):
        inv = jnp.zeros_like(order).at[order].set(
            jnp.arange(t * k, dtype=order.dtype))
        y = jnp.einsum("tkd,tk->td",
                       out[inv].reshape(t, k, d).astype(jnp.float32), gates)
    return y.astype(x.dtype), aux, rows


def _gmm(cfg: ArchConfig, xs: jax.Array, w: Dict[str, jax.Array],
         sizes: jax.Array) -> jax.Array:
    """The expert FFN of the sorted rows ``xs`` (T*k, d) as three grouped
    matmuls over the experts of ``w``, ``sizes`` rows each."""
    h1 = jax.lax.ragged_dot(xs, w["w1"], sizes)
    h3 = jax.lax.ragged_dot(xs, w["w3"], sizes) if "w3" in w else None
    return jax.lax.ragged_dot(_expert_ffn(cfg, h1, h3).astype(xs.dtype),
                              w["w2"], sizes)


def _in_place(w: Dict[str, jax.Array], rows: jax.Array, layer: jax.Array
              ) -> Tuple[Dict[str, jax.Array], jax.Array]:
    """The grouped matmuls' operands for layer ``layer``'s experts read in
    place: each (L, E, ...) stack viewed as L*E groups (a bitcast), and
    group sizes that are ``rows`` at the layer's E groups and 0 elsewhere.

    ``ragged_dot`` is a custom call on the TPU that takes each weight as a
    whole buffer, so a layer's (E, ...) slice fed to it is copied first;
    the whole stack is not.  Its kernel visits only groups with rows, so
    it streams from HBM the experts this layer's rows reached and nothing
    of the other layers'."""
    n = next(iter(w.values())).shape[0] * rows.shape[0]
    flat = {name: a.reshape(n, *a.shape[2:]) for name, a in w.items()}
    sizes = jax.lax.dynamic_update_slice(
        jnp.zeros((n,), rows.dtype), rows, (layer * rows.shape[0],))
    return flat, sizes


def moe_block(p: Dict[str, jax.Array], x: jax.Array, cfg: ArchConfig,
              num_groups: Optional[int] = None,
              layer: Optional[jax.Array] = None
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: (B, S, d) -> (y, aux_loss, rows routed to each expert (E,)).

    With ``cfg.moe_dropless`` every assignment is computed (``_dropless``;
    ``layer`` given, ``p``'s expert weights are the whole stacks and layer
    ``layer``'s are read in place).  Otherwise dispatch is by capacity:
    ``num_groups`` dispatch groups (defaults to B); tokens within a group
    share one capacity budget, and groups shard over the data axes.
    """
    b, s, d = x.shape
    if cfg.moe_dropless:
        y, aux, rows = _dropless(p, x.reshape(b * s, d), cfg, layer)
        return y.reshape(b, s, d), aux, rows
    e, k = cfg.num_experts, cfg.top_k
    g = num_groups if num_groups else b
    tokens = b * s
    assert tokens % g == 0, (tokens, g)
    sg = tokens // g
    xg = x.reshape(g, sg, d)
    cap = expert_capacity(cfg, sg)

    # --- routing ------------------------------------------------------------
    gates, idx, aux = _route(p, xg, cfg)                 # (g, sg, k)
    rows = jnp.bincount(idx.reshape(-1), length=e).astype(jnp.int32)

    # --- slot positions within each expert's capacity ----------------------------
    # flatten the k assignment slots; earlier slots win capacity
    flat_idx = idx.reshape(g, sg * k)                     # (g, n)
    slot_one_hot = jax.nn.one_hot(flat_idx, e, dtype=jnp.int32)
    pos_in_expert = jnp.cumsum(slot_one_hot, axis=1) - 1  # (g, n, e)
    pos = jnp.take_along_axis(
        pos_in_expert, flat_idx[..., None], axis=-1)[..., 0]   # (g, n)
    keep = pos < cap
    # dropped tokens scatter out of bounds -> mode='drop' discards them
    pos_safe = jnp.where(keep, pos, cap)

    # --- dispatch: buffer[g, e, c, d] via scatter-add ------------------------------
    token_src = jnp.broadcast_to(
        jnp.repeat(jnp.arange(sg), k)[None, :], (g, sg * k))
    vals = jnp.take_along_axis(xg, token_src[..., None], axis=1)  # (g,n,d)
    buf = jnp.zeros((g, e, cap, d), x.dtype)
    g_ids = jnp.broadcast_to(jnp.arange(g)[:, None], (g, sg * k))
    buf = buf.at[g_ids, flat_idx, pos_safe].add(vals, mode="drop")
    # EP profile: tokens corner-turn to their experts here (GroupBy!)
    buf = sctx.constrain(buf, "moe_buffer")

    # --- expert FFN (E stacked experts; f-dim is TP-sharded) ----------------------
    h = jnp.einsum("gecd,edf->gecf", buf, p["w1"])
    hg = (jnp.einsum("gecd,edf->gecf", buf, p["w3"])
          if cfg.activation in ("swiglu", "geglu") else None)
    h = _expert_ffn(cfg, h, hg)
    out_buf = jnp.einsum("gecf,efd->gecd", h, p["w2"])
    out_buf = sctx.constrain(out_buf, "moe_buffer")

    # --- combine: gather back + gate-weighted sum over k ---------------------------
    gathered = out_buf[g_ids, flat_idx, pos_safe]          # (g, n, d)
    gathered = jnp.where(keep[..., None], gathered, 0.0)
    gathered = gathered.reshape(g, sg, k, d)
    y = jnp.einsum("gskd,gsk->gsd", gathered.astype(jnp.float32),
                   gates).astype(x.dtype)
    return y.reshape(b, s, d), aux, rows
