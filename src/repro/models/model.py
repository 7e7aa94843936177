"""Composite model assembly for all 10 assigned architectures.

One functional API:
  * ``init_params(cfg, key)``      — parameter pytree (layers stacked for scan)
  * ``forward_train(params, cfg, batch)`` — mean token loss (+ aux)
  * ``init_cache(cfg, batch, max_seq)``   — KV / SSM / hybrid cache pytree
  * ``prefill(params, cfg, batch)``       — logits + primed cache
  * ``decode_step(params, cfg, cache, tokens, pos)`` — one-token serve step

Layers are scanned (``jax.lax.scan`` over stacked params) so the lowered HLO
is depth-independent — a 64-layer 314B model compiles as fast as a 2-layer
toy, which is what makes the 80-cell dry-run tractable and is standard
practice for production JAX LLM stacks.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .attention import (attention, decode_attention, decode_cross_attention,
                        init_attention, init_kv_cache)
from .common import (ArchConfig, KeyGen, activation_fn, cross_entropy,
                     dense_init, rms_norm, sinusoidal_positions, softcap)
from .moe import init_moe, moe_block
from .ssm import (init_mamba2, init_ssm_cache, mamba2_decode_layer,
                  mamba2_decode_step, mamba2_forward)
from ..sharding import ctx as sctx


def _dtype(cfg: ArchConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# Layer-scan control.  Production lowers a `lax.scan` (depth-independent
# HLO); the dry-run *cost pass* unrolls it because XLA's HloCostAnalysis
# counts a while-body exactly once, which would undercount FLOPs/bytes/
# collective bytes by a factor of num_layers.
# ---------------------------------------------------------------------------

_UNROLL_LAYERS = False


@contextlib.contextmanager
def unrolled_layers(enable: bool = True):
    global _UNROLL_LAYERS
    prev = _UNROLL_LAYERS
    _UNROLL_LAYERS = enable
    try:
        yield
    finally:
        _UNROLL_LAYERS = prev


def _scan(body, carry, xs):
    if not _UNROLL_LAYERS:
        return jax.lax.scan(body, carry, xs)
    length = jax.tree.leaves(xs)[0].shape[0]
    ys = []
    for i in range(length):
        x_i = jax.tree.map(lambda a: a[i], xs)
        carry, y = body(carry, x_i)
        ys.append(y)
    if ys and jax.tree.leaves(ys[0]):
        stacked = jax.tree.map(lambda *zs: jnp.stack(zs), *ys)
    else:
        stacked = None
    return carry, stacked


# ---------------------------------------------------------------------------
# Block params
# ---------------------------------------------------------------------------


def _init_mlp(kg: KeyGen, cfg: ArchConfig, dt) -> Dict[str, jax.Array]:
    d, f = cfg.d_model, cfg.d_ff
    p = {"w1": dense_init(kg(), (d, f), dt, fan_in=d),
         "w2": dense_init(kg(), (f, d), dt, fan_in=f)}
    if cfg.activation in ("swiglu", "geglu"):
        p["w3"] = dense_init(kg(), (d, f), dt, fan_in=d)
    return p


def _mlp(p: Dict[str, jax.Array], x: jax.Array, cfg: ArchConfig) -> jax.Array:
    h = jnp.einsum("bsd,df->bsf", x, p["w1"])
    if cfg.activation in ("swiglu", "geglu"):
        gate = jax.nn.silu if cfg.activation == "swiglu" else jax.nn.gelu
        h = gate(h) * jnp.einsum("bsd,df->bsf", x, p["w3"])
    else:
        h = activation_fn(cfg.activation)(h)
    return jnp.einsum("bsf,fd->bsd", h, p["w2"])


def _init_dense_block(kg: KeyGen, cfg: ArchConfig, dt,
                      cross: bool = False) -> Dict[str, Any]:
    p = {"attn_norm": jnp.zeros((cfg.d_model,), dt),
         "attn": init_attention(kg, cfg, dt),
         "mlp_norm": jnp.zeros((cfg.d_model,), dt)}
    if cfg.family == "moe":
        p["moe"] = init_moe(kg, cfg, dt)
    else:
        p["mlp"] = _init_mlp(kg, cfg, dt)
    if cross:
        p["cross_norm"] = jnp.zeros((cfg.d_model,), dt)
        p["cross"] = init_attention(kg, cfg, dt, cross=True)
    return p


@functools.partial(jax.jit, static_argnums=(0, 1))
def _init_layer(cfg: ArchConfig, kind: str, key: jax.Array):
    """One layer's params of ``kind`` ("dense" | "cross" | "ssm") from
    ``key``; returns them with the key advanced past their draws."""
    kg, dt = KeyGen(key), _dtype(cfg)
    if kind == "ssm":
        p = {"norm": jnp.zeros((cfg.d_model,), dt),
             "mamba": init_mamba2(kg, cfg, dt)}
    else:
        p = _init_dense_block(kg, cfg, dt, cross=kind == "cross")
    return p, kg.key


@functools.partial(jax.jit, donate_argnums=0)
def _put_layer(stack, layer, i):
    return jax.tree.map(
        lambda s, x: jax.lax.dynamic_update_index_in_dim(s, x, i, 0),
        stack, layer)


def _init_stacked(kg: KeyGen, cfg: ArchConfig, kind: str,
                  n: int) -> Dict[str, Any]:
    """``n`` layers' params stacked on a leading axis, drawn from ``kg`` in
    order — the same keys as ``n`` successive eager layer inits.

    One jitted call initialises one layer and writes it into a preallocated
    (donated) stack, so the compile covers one layer whatever the depth and
    the peak is the stack plus one layer — a full-width model built eagerly
    and then stacked would need both copies at once."""
    shapes, _ = jax.eval_shape(
        functools.partial(_init_layer, cfg, kind), kg.key)
    stack = jax.tree.map(lambda a: jnp.zeros((n, *a.shape), a.dtype), shapes)
    for i in range(n):
        layer, kg.key = _init_layer(cfg, kind, kg.key)
        stack = _put_layer(stack, layer, i)
    return stack


# ---------------------------------------------------------------------------
# init_params
# ---------------------------------------------------------------------------


def init_params(cfg: ArchConfig, key: jax.Array) -> Dict[str, Any]:
    kg = KeyGen(key)
    dt = _dtype(cfg)
    d, vp = cfg.d_model, cfg.padded_vocab
    # drawn at 1/embedding_multiplier of the usual scale, so that the
    # scaled rows enter the residual at the scale an unscaled model's do:
    # at full scale they would swamp every layer, and with tied embeddings
    # a random model would then only repeat its last token
    em = cfg.embedding_multiplier
    params: Dict[str, Any] = {
        "embed": dense_init(kg(), (vp, d), dt, fan_in=d * em * em),
        "final_norm": jnp.zeros((d,), dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(kg(), (d, vp), dt, fan_in=d)

    if cfg.family in ("dense", "moe", "vlm"):
        params["layers"] = _init_stacked(kg, cfg, "dense", cfg.num_layers)
    elif cfg.family == "ssm":
        params["layers"] = _init_stacked(kg, cfg, "ssm", cfg.num_layers)
    elif cfg.family == "hybrid":
        assert cfg.shared_attn_period > 0
        assert cfg.num_layers % cfg.shared_attn_period == 0
        params["layers"] = _init_stacked(kg, cfg, "ssm", cfg.num_layers)
        params["shared"] = _init_dense_block(kg, cfg, dt)
    elif cfg.family == "encdec":
        params["enc_layers"] = _init_stacked(kg, cfg, "dense",
                                             cfg.num_encoder_layers)
        params["enc_norm"] = jnp.zeros((d,), dt)
        params["layers"] = _init_stacked(kg, cfg, "cross", cfg.num_layers)
    else:
        raise ValueError(f"unknown family {cfg.family!r}")
    return params


# ---------------------------------------------------------------------------
# Layer-window schedule (gemma2 alternating local/global)
# ---------------------------------------------------------------------------


def layer_windows(cfg: ArchConfig, seq_or_cache_len: int) -> Optional[np.ndarray]:
    if cfg.alternate_local_global:
        w = [cfg.local_window if i % 2 == 0 else 0
             for i in range(cfg.num_layers)]
        return np.asarray(w, dtype=np.int32)
    if cfg.local_window:
        return np.full((cfg.num_layers,), cfg.local_window, dtype=np.int32)
    return None


# ---------------------------------------------------------------------------
# Forward (train / prefill share the full-sequence path)
# ---------------------------------------------------------------------------


def _residual(cfg: ArchConfig, h: jax.Array, y: jax.Array) -> jax.Array:
    """``h + y`` scaled by the residual multiplier (none at 1)."""
    if cfg.residual_multiplier != 1.0:
        y = y * cfg.residual_multiplier
    return h + y


def _dense_body(cfg: ArchConfig, positions, use_kernel, remat: bool):
    def body(carry, layer):
        h, aux = carry
        p, window = layer
        a = attention(p["attn"], rms_norm(h, p["attn_norm"]), cfg,
                      positions=positions, window=window,
                      causal=True, use_kernel=use_kernel)
        h = _residual(cfg, h, a)
        xin = rms_norm(h, p["mlp_norm"])
        if cfg.family == "moe":
            m, aux_l, _ = moe_block(p["moe"], xin, cfg)
            aux = aux + aux_l
        else:
            m = _mlp(p["mlp"], xin, cfg)
        h = sctx.constrain(_residual(cfg, h, m), "residual")
        return (h, aux), None
    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    return body


def _ssm_body(cfg: ArchConfig, use_kernel, remat: bool):
    def body(carry, p):
        h, aux = carry
        h = h + mamba2_forward(p["mamba"], rms_norm(h, p["norm"]), cfg,
                               use_kernel=use_kernel)
        h = sctx.constrain(h, "residual")
        return (h, aux), None
    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    return body


def _shared_block(cfg: ArchConfig, p, h, positions, use_kernel):
    a = attention(p["attn"], rms_norm(h, p["attn_norm"]), cfg,
                  positions=positions, window=None, causal=True,
                  use_kernel=use_kernel)
    h = h + a
    h = h + _mlp(p["mlp"], rms_norm(h, p["mlp_norm"]), cfg)
    return h


def backbone(params: Dict[str, Any], cfg: ArchConfig, x: jax.Array,
             positions: jax.Array, *, use_kernel: bool = False,
             remat: bool = False,
             enc_out: Optional[jax.Array] = None) -> Tuple[jax.Array, jax.Array]:
    """Run the stacked layers.  Returns (hidden, aux_loss)."""
    aux0 = jnp.zeros((), jnp.float32)
    if cfg.family in ("dense", "moe", "vlm"):
        windows = layer_windows(cfg, x.shape[1])
        if windows is None:
            windows = np.zeros((cfg.num_layers,), np.int32)
        body = _dense_body(cfg, positions, use_kernel, remat)
        (h, aux), _ = _scan(body, (x, aux0),
                                   (params["layers"], jnp.asarray(windows)))
        return h, aux
    if cfg.family == "ssm":
        body = _ssm_body(cfg, use_kernel, remat)
        (h, aux), _ = _scan(body, (x, aux0), params["layers"])
        return h, aux
    if cfg.family == "hybrid":
        per = cfg.shared_attn_period
        groups = cfg.num_layers // per
        grouped = jax.tree.map(
            lambda a: a.reshape(groups, per, *a.shape[1:]), params["layers"])
        inner = _ssm_body(cfg, use_kernel, remat)

        def outer(carry, gp):
            (h, aux), _ = _scan(inner, carry, gp)
            h = _shared_block(cfg, params["shared"], h, positions, use_kernel)
            return (h, aux), None
        if remat:
            outer = jax.checkpoint(outer, prevent_cse=False)
        (h, aux), _ = _scan(outer, (x, aux0), grouped)
        return h, aux
    if cfg.family == "encdec":
        assert enc_out is not None, "enc-dec backbone needs encoder output"
        windows = np.zeros((cfg.num_layers,), np.int32)

        def body(carry, layer):
            h, aux = carry
            p, window = layer
            a = attention(p["attn"], rms_norm(h, p["attn_norm"]), cfg,
                          positions=positions, window=window, causal=True,
                          use_rope=False, use_kernel=use_kernel)
            h = h + a
            c = attention(p["cross"], rms_norm(h, p["cross_norm"]), cfg,
                          positions=positions, causal=False, kv_src=enc_out,
                          use_rope=False, use_kernel=False)
            h = h + c
            h = sctx.constrain(
                h + _mlp(p["mlp"], rms_norm(h, p["mlp_norm"]), cfg),
                "residual")
            return (h, aux), None
        if remat:
            body = jax.checkpoint(body, prevent_cse=False)
        (h, aux), _ = _scan(body, (x, aux0),
                                   (params["layers"], jnp.asarray(windows)))
        return h, aux
    raise ValueError(cfg.family)


def encode(params: Dict[str, Any], cfg: ArchConfig,
           frames: jax.Array, *, use_kernel: bool = False,
           remat: bool = False) -> jax.Array:
    """Whisper encoder over stub frame embeddings (B, enc_len, d)."""
    x = frames + sinusoidal_positions(frames.shape[1],
                                      cfg.d_model).astype(frames.dtype)
    positions = jnp.broadcast_to(
        jnp.arange(frames.shape[1]), frames.shape[:2])

    def body(carry, p):
        h, aux = carry
        a = attention(p["attn"], rms_norm(h, p["attn_norm"]), cfg,
                      positions=positions, causal=False, use_rope=False,
                      use_kernel=use_kernel)
        h = h + a
        h = h + _mlp(p["mlp"], rms_norm(h, p["mlp_norm"]), cfg)
        return (h, aux), None
    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    (h, _), _ = _scan(body, (x, jnp.zeros((), jnp.float32)),
                             params["enc_layers"])
    return rms_norm(h, params["enc_norm"])


def embed_tokens(params: Dict[str, Any], cfg: ArchConfig,
                 tokens: jax.Array) -> jax.Array:
    x = params["embed"][tokens]
    if cfg.embedding_multiplier != 1.0:
        x = x * cfg.embedding_multiplier
    if cfg.family == "encdec":
        x = x + sinusoidal_positions(
            tokens.shape[-1], cfg.d_model).astype(x.dtype)
    return x


def logits_fn(params: Dict[str, Any], cfg: ArchConfig,
              h: jax.Array) -> jax.Array:
    h = rms_norm(h, params["final_norm"])
    head = (params["embed"].T if cfg.tie_embeddings
            else params["lm_head"])
    logits = jnp.einsum("bsd,dv->bsv", h, head).astype(jnp.float32)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return softcap(logits, cfg.final_softcap)


def forward_train(params: Dict[str, Any], cfg: ArchConfig,
                  batch: Dict[str, jax.Array], *, use_kernel: bool = False,
                  remat: bool = True) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    tokens, labels = batch["tokens"], batch["labels"]
    x = embed_tokens(params, cfg, tokens)
    positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
    enc_out = None
    if cfg.family == "encdec":
        enc_out = encode(params, cfg, batch["frames"],
                         use_kernel=use_kernel, remat=remat)
    h, aux = backbone(params, cfg, x, positions, use_kernel=use_kernel,
                      remat=remat, enc_out=enc_out)
    logits = logits_fn(params, cfg, h)
    loss = cross_entropy(logits, labels, cfg.vocab_size)
    total = loss + 0.01 * aux
    return total, {"loss": loss, "aux_loss": aux}


# ---------------------------------------------------------------------------
# Serving: cache init, prefill, decode
# ---------------------------------------------------------------------------


def init_cache(cfg: ArchConfig, batch: int, max_seq: int) -> Dict[str, Any]:
    dt = _dtype(cfg)
    L = cfg.num_layers

    def stack_kv(n):
        one = init_kv_cache(cfg, batch, max_seq, dt)
        return jax.tree.map(
            lambda a: jnp.zeros((n, *a.shape), a.dtype), one)

    if cfg.family == "moe":
        return {"kv": stack_kv(L), "moe": init_moe_counts(cfg)}
    if cfg.family in ("dense", "vlm"):
        return {"kv": stack_kv(L)}
    if cfg.family == "ssm":
        one = init_ssm_cache(cfg, batch, dt)
        return {"ssm": jax.tree.map(
            lambda a: jnp.zeros((L, *a.shape), a.dtype), one)}
    if cfg.family == "hybrid":
        groups = cfg.num_layers // cfg.shared_attn_period
        one = init_ssm_cache(cfg, batch, dt)
        return {
            "ssm": jax.tree.map(
                lambda a: jnp.zeros((L, *a.shape), a.dtype), one),
            "kv": stack_kv(groups),   # one KV cache per shared-block call
        }
    if cfg.family == "encdec":
        enc_len = max(max_seq // cfg.encoder_ratio, 1)
        hd = cfg.resolved_head_dim
        return {
            "kv": stack_kv(L),
            "cross_k": jnp.zeros((L, batch, enc_len, cfg.num_kv_heads, hd),
                                 dt),
            "cross_v": jnp.zeros((L, batch, enc_len, cfg.num_kv_heads, hd),
                                 dt),
        }
    raise ValueError(cfg.family)


def init_moe_counts(cfg: ArchConfig) -> Dict[str, jax.Array]:
    """The MoE routing counters a decode cache carries, all zero: per
    layer, the rows routed to each expert summed over steps (``rows``,
    (L, E)), the experts that got at least one row summed over steps
    (``touched``, (L,)) and the most rows one expert got in a step
    (``rows_max``, (L,)); and the steps each layer counted (``steps``,
    (L,)).  Every leaf has a layer axis, so the cache's leaves all pad and
    stack alike."""
    L, E = cfg.num_layers, cfg.num_experts
    return {"rows": jnp.zeros((L, E), jnp.int32),
            "touched": jnp.zeros((L,), jnp.int32),
            "rows_max": jnp.zeros((L,), jnp.int32),
            "steps": jnp.zeros((L,), jnp.int32)}


def _count_moe(counts: Dict[str, jax.Array],
               rows: jax.Array) -> Dict[str, jax.Array]:
    """``counts`` advanced by one step whose per-layer rows per expert are
    ``rows`` (L, E)."""
    return {"rows": counts["rows"] + rows,
            "touched": counts["touched"] + (rows > 0).sum(-1, dtype=jnp.int32),
            "rows_max": jnp.maximum(counts["rows_max"], rows.max(-1)),
            "steps": counts["steps"] + 1}


def _in_place_experts(cfg: ArchConfig, layers: Dict[str, Any]
                      ) -> Tuple[Dict[str, Any], Dict[str, jax.Array]]:
    """``layers`` without the dropless MoE's expert stacks, and the stacks.

    The inference scans close over the whole (L, E, ...) stacks and give
    each layer its index, so the TPU's grouped matmuls read the layer's
    experts in place (``moe._in_place``): a slice scanned out of a stack
    would be copied whole before the kernel reads it.  The training
    forward keeps scanning slices, since a gradient through the whole
    stack would build a whole-stack cotangent in every layer.  Other
    configs: (layers, {})."""
    if not (cfg.family == "moe" and cfg.moe_dropless):
        return layers, {}
    moe = dict(layers["moe"])
    stacks = {n: moe.pop(n) for n in ("w1", "w2", "w3") if n in moe}
    return {**layers, "moe": moe}, stacks


def _mamba_decode_body(cfg: ArchConfig):
    """The scan body of a Mamba-2 decode in the XLA form: one layer's
    (params, cache) in, its cache out."""
    def body(h, layer):
        p, c = layer
        y, c2 = mamba2_decode_step(p["mamba"], rms_norm(h, p["norm"]), c, cfg)
        return h + y, c2
    return body


def decode_step(params: Dict[str, Any], cfg: ArchConfig,
                cache: Dict[str, Any], tokens: jax.Array,
                pos: jax.Array) -> Tuple[jax.Array, Dict[str, Any]]:
    """One serve step: tokens (B,1) at position ``pos`` -> (logits, cache)."""
    x = embed_tokens(params, cfg, tokens)
    if cfg.family == "encdec":
        # learned-pos analogue at decode: add the sinusoid for `pos`
        x = params["embed"][tokens] + jax.lax.dynamic_slice_in_dim(
            sinusoidal_positions(cache["kv"]["k"].shape[2], cfg.d_model),
            pos, 1, axis=0).astype(x.dtype)[None]

    if cfg.family in ("dense", "moe", "vlm"):
        windows = layer_windows(cfg, 0)
        if windows is None:
            windows = np.zeros((cfg.num_layers,), np.int32)
        layers, experts = _in_place_experts(cfg, params["layers"])
        at = jnp.arange(cfg.num_layers) if experts else None

        def body(h, layer):
            p, kv, window, i = layer
            a, kv2 = decode_attention(
                p["attn"], rms_norm(h, p["attn_norm"]), kv, pos, cfg,
                window=window)
            h = _residual(cfg, h, a)
            xin = rms_norm(h, p["mlp_norm"])
            if cfg.family == "moe":
                m, _, rows = moe_block({**p["moe"], **experts}, xin, cfg,
                                       num_groups=1, layer=i)
                return _residual(cfg, h, m), (kv2, rows)
            return _residual(cfg, h, _mlp(p["mlp"], xin, cfg)), kv2
        h, ys = _scan(
            body, x, (layers, cache["kv"], jnp.asarray(windows), at))
        if cfg.family == "moe":
            kv, rows = ys
            new_cache: Dict[str, Any] = {
                "kv": kv, "moe": _count_moe(cache["moe"], rows)}
        else:
            new_cache = {"kv": ys}
    elif cfg.family == "ssm" and jax.sharding.get_abstract_mesh().empty:
        # The state stack is closed over and read in place by each layer's
        # kernel, which writes the new state into a float32 stack carried
        # through the scan: a state scanned in and out would be read twice
        # (update, then read-out) and written once more into the scan's
        # output.
        states = cache["ssm"]["state"]

        def body(carry, layer):
            h, out = carry
            p, conv, i = layer
            y, conv2, out = mamba2_decode_layer(
                p["mamba"], rms_norm(h, p["norm"]), conv, states, out, i,
                cfg)
            return (h + y, out), conv2
        (h, state), conv = _scan(
            body, (x, jax.lax.empty(states.shape, jnp.float32)),
            (params["layers"], cache["ssm"]["conv"],
             jnp.arange(cfg.num_layers)))
        new_cache = {"ssm": {"conv": conv, "state": state}}
    elif cfg.family == "ssm":
        # Sharded over a mesh (the dry-run's programs): XLA's partitioner
        # does not split a Pallas kernel, so the step keeps the XLA form.
        h, ssm = _scan(_mamba_decode_body(cfg), x,
                       (params["layers"], cache["ssm"]))
        new_cache = {"ssm": ssm}
    elif cfg.family == "hybrid":
        per = cfg.shared_attn_period
        groups = cfg.num_layers // per
        grouped_p = jax.tree.map(
            lambda a: a.reshape(groups, per, *a.shape[1:]), params["layers"])
        grouped_c = jax.tree.map(
            lambda a: a.reshape(groups, per, *a.shape[1:]), cache["ssm"])

        def outer(h, layer):
            gp, gc, kv = layer
            h, gc2 = _scan(_mamba_decode_body(cfg), h, (gp, gc))
            sp = params["shared"]
            a, kv2 = decode_attention(
                sp["attn"], rms_norm(h, sp["attn_norm"]), kv, pos, cfg)
            h = h + a
            h = h + _mlp(sp["mlp"], rms_norm(h, sp["mlp_norm"]), cfg)
            return h, (gc2, kv2)
        h, (gc, kv) = _scan(
            outer, x, (grouped_p, grouped_c, cache["kv"]))
        new_cache = {
            "ssm": jax.tree.map(
                lambda a: a.reshape(cfg.num_layers, *a.shape[2:]), gc),
            "kv": kv,
        }
    elif cfg.family == "encdec":
        def body(h, layer):
            p, kv, ck, cv = layer
            a, kv2 = decode_attention(
                p["attn"], rms_norm(h, p["attn_norm"]), kv, pos, cfg,
                use_rope=False)
            h = h + a
            c = decode_cross_attention(
                p["cross"], rms_norm(h, p["cross_norm"]), ck, cv, cfg)
            h = h + c
            h = h + _mlp(p["mlp"], rms_norm(h, p["mlp_norm"]), cfg)
            return h, kv2
        h, kv = _scan(
            body, x, (params["layers"], cache["kv"],
                      cache["cross_k"], cache["cross_v"]))
        new_cache = {"kv": kv, "cross_k": cache["cross_k"],
                     "cross_v": cache["cross_v"]}
    else:
        raise ValueError(cfg.family)

    logits = logits_fn(params, cfg, h)
    return logits, new_cache


def prefill(params: Dict[str, Any], cfg: ArchConfig,
            batch: Dict[str, jax.Array], *, use_kernel: bool = False
            ) -> Tuple[jax.Array, Dict[str, Any]]:
    """Process the full prompt; return last-position logits + primed cache.

    The cache is primed by running the full-sequence backbone and projecting
    K/V per layer (for attention families) / final SSM states (for SSM).
    """
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = embed_tokens(params, cfg, tokens)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    enc_out = None
    if cfg.family == "encdec":
        enc_out = encode(params, cfg, batch["frames"], use_kernel=use_kernel)
    cache = init_cache(cfg, B, S)

    # The priming pass IS the forward pass: one sweep over the layers that
    # both produces the final hidden state and captures per-layer K/V (or
    # final SSM states) into the cache — no duplicated backbone work.
    if cfg.family in ("dense", "moe", "vlm", "encdec"):
        h, cache = _prime_kv(params, cfg, x, positions, cache, enc_out,
                             use_kernel)
    else:
        h, cache = _prime_ssm(params, cfg, x, positions, cache, use_kernel)
    logits = logits_fn(params, cfg, h[:, -1:, :])
    return logits, cache


def _prime_kv(params, cfg, x, positions, cache, enc_out, use_kernel):
    """Run layers sequentially, storing per-layer K/V into the cache."""
    from .attention import _project_qkv  # noqa: PLC2701 (intra-package)
    windows = layer_windows(cfg, x.shape[1])
    if windows is None:
        windows = np.zeros((cfg.num_layers,), np.int32)
    layers, experts = _in_place_experts(cfg, params["layers"])
    at = jnp.arange(cfg.num_layers) if experts else None

    def body(carry, layer):
        h = carry
        if cfg.family == "encdec":
            p, window, i, ck, cv = layer
        else:
            p, window, i = layer
        xin = rms_norm(h, p["attn_norm"])
        _, k, v = _project_qkv(p["attn"], xin, xin, cfg, positions,
                               positions,
                               use_rope=cfg.family != "encdec")
        a = attention(p["attn"], xin, cfg, positions=positions,
                      window=window, causal=True,
                      use_rope=cfg.family != "encdec",
                      use_kernel=use_kernel)
        h = _residual(cfg, h, a)
        outs = {"k": k, "v": v}
        if cfg.family == "encdec":
            c = attention(p["cross"], rms_norm(h, p["cross_norm"]), cfg,
                          positions=positions, causal=False, kv_src=enc_out,
                          use_rope=False)
            h = h + c
            ck2 = jnp.einsum("btd,dhk->bthk", enc_out, p["cross"]["wk"])
            cv2 = jnp.einsum("btd,dhk->bthk", enc_out, p["cross"]["wv"])
            if cfg.use_bias:
                ck2 = ck2 + p["cross"]["bk"]
                cv2 = cv2 + p["cross"]["bv"]
            outs["ck"] = ck2
            outs["cv"] = cv2
        xin2 = rms_norm(h, p["mlp_norm"])
        if cfg.family == "moe":
            m, _, _ = moe_block({**p["moe"], **experts}, xin2, cfg, layer=i)
        else:
            m = _mlp(p["mlp"], xin2, cfg)
        h = _residual(cfg, h, m)
        return h, outs

    if cfg.family == "encdec":
        xs = (layers, jnp.asarray(windows), at,
              cache["cross_k"], cache["cross_v"])
    else:
        xs = (layers, jnp.asarray(windows), at)
    h, outs = _scan(body, x, xs)
    kv = {"k": outs["k"].astype(cache["kv"]["k"].dtype),
          "v": outs["v"].astype(cache["kv"]["v"].dtype)}
    new = dict(cache)
    new["kv"] = kv
    if cfg.family == "encdec":
        new["cross_k"] = outs["ck"].astype(cache["cross_k"].dtype)
        new["cross_v"] = outs["cv"].astype(cache["cross_v"].dtype)
    return h, new


def _prime_ssm(params, cfg, x, positions, cache, use_kernel):
    """Sequence pass capturing final SSM states (+ shared-block K/V)."""
    from .ssm import _causal_conv, _split_proj, ssd_chunked

    def mamba_with_state(p, h, c):
        B, S, d = h.shape
        di, n, g = cfg.ssm_inner, cfg.ssm_state, cfg.ssm_groups
        hh, P = cfg.ssm_heads, cfg.ssm_headdim
        zxbcdt = jnp.einsum("bsd,dk->bsk", h, p["in_proj"])
        z, xin, b_, c_, dt = _split_proj(cfg, zxbcdt)
        conv_in = jnp.concatenate([xin, b_, c_], axis=-1)
        conv_out = _causal_conv(conv_in, p["conv_w"], p["conv_b"])
        new_conv = conv_in[:, -(cfg.ssm_conv - 1):, :]
        xin, b_, c_ = jnp.split(conv_out, [di, di + g * n], axis=-1)
        dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
        a = -jnp.exp(p["A_log"])
        y, state = ssd_chunked(
            xin.reshape(B, S, hh, P), dt, a, b_.reshape(B, S, g, n),
            c_.reshape(B, S, g, n), min(cfg.ssm_chunk, S),
            use_kernel=use_kernel)
        y = (y + xin.reshape(B, S, hh, P)
             * p["D"][None, None, :, None]).astype(h.dtype)
        y = rms_norm(y.reshape(B, S, di) * jax.nn.silu(z), p["norm"])
        out = jnp.einsum("bsk,kd->bsd", y, p["out_proj"])
        return out, {"conv": new_conv.astype(c["conv"].dtype),
                     "state": jnp.swapaxes(state, -1, -2).astype(
                         c["state"].dtype)}

    if cfg.family == "ssm":
        def body(h, layer):
            p, c = layer
            y, c2 = mamba_with_state(p["mamba"], rms_norm(h, p["norm"]), c)
            return h + y, c2
        h, ssm = _scan(body, x, (params["layers"], cache["ssm"]))
        return h, {"ssm": ssm}

    # hybrid
    from .attention import _project_qkv
    per = cfg.shared_attn_period
    groups = cfg.num_layers // per
    grouped_p = jax.tree.map(
        lambda a: a.reshape(groups, per, *a.shape[1:]), params["layers"])
    grouped_c = jax.tree.map(
        lambda a: a.reshape(groups, per, *a.shape[1:]), cache["ssm"])

    def inner(h, layer):
        p, c = layer
        y, c2 = mamba_with_state(p["mamba"], rms_norm(h, p["norm"]), c)
        return h + y, c2

    def outer(h, layer):
        gp, gc = layer
        h, gc2 = _scan(inner, h, (gp, gc))
        sp = params["shared"]
        xin = rms_norm(h, sp["attn_norm"])
        q, k, v = _project_qkv(sp["attn"], xin, xin, cfg, positions,
                               positions, use_rope=True)
        a = attention(sp["attn"], xin, cfg, positions=positions,
                      causal=True)
        h = h + a
        h = h + _mlp(sp["mlp"], rms_norm(h, sp["mlp_norm"]), cfg)
        return h, (gc2, {"k": k, "v": v})
    h, (gc, kv) = _scan(outer, x, (grouped_p, grouped_c))
    return h, {
        "ssm": jax.tree.map(
            lambda a: a.reshape(cfg.num_layers, *a.shape[2:]), gc),
        "kv": {"k": kv["k"].astype(cache["kv"]["k"].dtype),
               "v": kv["v"].astype(cache["kv"]["v"].dtype)},
    }
