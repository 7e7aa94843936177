"""Dropless MoE, Granite's multipliers and the MoE routing counters.

* the dropless route equals a per-token loop over the chosen experts, at a
  size where capacity dispatch drops tokens (and does drop them);
* its in-place form, which reads one layer's experts in the whole stacks,
  equals it exactly, and the training forward keeps slicing the stacks;
* a granite smoke configuration with all four multipliers serves from a
  primed cache the logits of one full forward;
* the routing counters a decode cache carries match the routing
  recomputed on the host, and ``run_serving`` reports their sums.
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.configs import get_smoke_config
from repro.models import model as M
from repro.models.common import ArchConfig, KeyGen
from repro.models.moe import _gmm, _in_place, init_moe, moe_block
from repro.sharding.ctx import ShardProfile, use_profile

KEY = jax.random.PRNGKey(0)


def moe_cfg(**kw):
    base = dict(name="moe-test", family="moe", num_layers=1, d_model=32,
                num_heads=2, num_kv_heads=1, head_dim=16, d_ff=24,
                vocab_size=64, num_experts=8, top_k=2, dtype="float32",
                capacity_factor=0.5)
    return ArchConfig(**dict(base, **kw))


def per_token_loop(p, x, cfg):
    """Each token through its top-k experts, one at a time."""
    xt = np.asarray(x, np.float64).reshape(-1, cfg.d_model)
    router = np.asarray(p["router"], np.float64)
    w1, w2, w3 = (np.asarray(p[k], np.float64) for k in ("w1", "w2", "w3"))
    out = np.zeros_like(xt)
    for t, row in enumerate(xt):
        logits = row @ router
        probs = np.exp(logits - logits.max())
        probs /= probs.sum()
        top = np.argsort(-probs, kind="stable")[:cfg.top_k]
        gates = probs[top] / probs[top].sum()
        for g, e in zip(gates, top):
            h1, h3 = row @ w1[e], row @ w3[e]
            out[t] += g * ((h1 / (1 + np.exp(-h1))) * h3) @ w2[e]
    return out.reshape(x.shape)


def test_dropless_equals_a_per_token_loop_where_capacity_drops():
    cfg = moe_cfg(moe_dropless=True)
    p = init_moe(KeyGen(KEY), cfg, jnp.float32)
    # one sequence of 64 tokens: capacity 0.5 x the mean load is 8 slots
    # per expert, fewer than the 16 assignments an expert gets on average
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 64, cfg.d_model))
    want = per_token_loop(p, x, cfg)
    y, aux, rows = jax.jit(lambda p, x: moe_block(p, x, cfg))(p, x)
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-4, atol=1e-5)
    assert int(rows.sum()) == 64 * cfg.top_k and np.isfinite(float(aux))
    # the capacity route of the same weights drops tokens there
    capped = moe_cfg(moe_dropless=False)
    yc, _, rows_c = jax.jit(lambda p, x: moe_block(p, x, capped))(p, x)
    np.testing.assert_array_equal(np.asarray(rows_c), np.asarray(rows))
    assert int(rows.max()) > 8
    wrong = np.abs(np.asarray(yc) - want).max(axis=-1)[0]
    assert (wrong > 1e-3).sum() > 0


def _stacked_moe(cfg, routing):
    """Three layers' MoE params, their routers set up for ``routing``, and
    inputs for them."""
    kg = KeyGen(KEY)
    layers = [init_moe(kg, cfg, jnp.float32) for _ in range(3)]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    if routing != "spread":
        x = jnp.abs(x)          # positive inputs: router columns decide
        for p in layers:
            r = p["router"] * 0.1
            if routing == "idle_experts":
                r = r.at[:, [2, 5]].set(-1.0)      # never in a top-k
            else:                                  # "one_expert"
                r = r.at[:, 3].set(1.0)            # every row's top-1
            p["router"] = r
    return layers, x


@pytest.mark.parametrize("routing, top_k", [("spread", 2),
                                            ("idle_experts", 2),
                                            ("one_expert", 1)])
@pytest.mark.parametrize("layer", [0, 1, 2])
def test_in_place_experts_equal_the_sliced_layer(layer, routing, top_k):
    """Layer ``layer``'s experts read in place in the (3, E, ...) stacks
    give exactly the y, aux and rows of the layer's own (E, ...) slice;
    and the TPU's operands, the stacks as 3 x E groups with rows only in
    the layer's, give exactly the slice's grouped matmuls."""
    cfg = moe_cfg(moe_dropless=True, top_k=top_k)
    layers, x = _stacked_moe(cfg, routing)
    stacks = jax.tree.map(lambda *a: jnp.stack(a), *layers)
    sliced = jax.jit(lambda p, x: moe_block(p, x, cfg))(layers[layer], x)
    in_place = jax.jit(lambda p, x, i: moe_block(p, x, cfg, layer=i))(
        {**stacks, "router": layers[layer]["router"]}, x, jnp.int32(layer))
    for want, got in zip(sliced, in_place):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    rows = np.asarray(sliced[2])
    if routing == "idle_experts":
        assert rows[2] == rows[5] == 0 and (rows > 0).sum() > 2
    if routing == "one_expert":
        assert rows[3] == x.shape[0] * x.shape[1] == rows.sum()

    xs = jnp.repeat(x.reshape(-1, cfg.d_model), top_k, axis=0)
    w = {n: stacks[n] for n in ("w1", "w2", "w3")}
    want = _gmm(cfg, xs, jax.tree.map(lambda a: a[layer], w), sliced[2])
    got = jax.jit(lambda w, r, i: _gmm(cfg, xs, *_in_place(w, r, i)))(
        w, sliced[2], jnp.int32(layer))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _groups(jaxpr):
    """The group count of every ``ragged_dot_general`` in ``jaxpr`` and the
    jaxprs nested in it (the length of its group sizes)."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "ragged_dot_general":
            yield eqn.invars[2].aval.shape[0]
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _groups(sub)


def test_training_forward_keeps_slicing_the_experts():
    """The inference scans read the experts in place over L*E groups (on
    the TPU; the jaxpr holds both platforms' routes); the training forward
    and its gradient run every grouped matmul over one layer's E, so no
    cotangent is as large as a whole stack."""
    cfg = dataclasses.replace(granite_smoke(), num_layers=3)
    params = M.init_params(cfg, KEY)
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 9), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    e, flat = cfg.num_experts, cfg.num_layers * cfg.num_experts

    def groups(fn, *args):
        return set(_groups(jax.make_jaxpr(fn)(*args).jaxpr))

    grad = jax.grad(lambda p: M.forward_train(p, cfg, batch)[0])
    assert groups(grad, params) == {e}
    cache = M.init_cache(cfg, 2, 8)
    assert flat in groups(lambda p: M.decode_step(
        p, cfg, cache, toks[:, :1], jnp.int32(0)), params)
    assert flat in groups(lambda p: M.prefill(p, cfg, {"tokens": toks}),
                          params)


def test_dropless_refuses_an_expert_sharded_mesh():
    cfg = moe_cfg(moe_dropless=True)
    p = init_moe(KeyGen(KEY), cfg, jnp.float32)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1),
                ("data", "expert", "tp"))
    prof = ShardProfile(name="ep", mesh=mesh, data_axes=("data",),
                        tp_axes=("tp",), expert_axis="expert")
    x = jnp.ones((1, 4, cfg.d_model))
    with use_profile(prof), pytest.raises(NotImplementedError,
                                          match="expert-parallel"):
        moe_block(p, x, cfg)


def granite_smoke():
    cfg = get_smoke_config("granite_moe_3b_a800m")
    assert cfg.moe_dropless and cfg.tie_embeddings
    assert (cfg.embedding_multiplier, cfg.attention_multiplier,
            cfg.residual_multiplier, cfg.logits_scaling) == (
        12.0, 0.015625, 0.22, 6.0)
    return cfg


def test_granite_cache_decode_matches_one_forward():
    """Prefill of S tokens, then one decode step from the primed cache
    (both by the inference route, which indexes the whole expert stacks
    by layer), gives the logits of one forward over S + 1 tokens by the
    training route (slices of the stacks scanned), to float32 rounding,
    as does a prefill of all S + 1; and each multiplier changes them."""
    cfg = granite_smoke()
    params = M.init_params(cfg, KEY)
    B, S = 2, 12
    toks = jax.random.randint(jax.random.PRNGKey(3), (B, S + 1), 0,
                              cfg.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(S + 1), toks.shape)

    def served(cfg):
        h, _ = jax.jit(lambda p, t: M.backbone(
            p, cfg, M.embed_tokens(p, cfg, t), positions))(params, toks)
        full = M.logits_fn(params, cfg, h[:, -1:])
        whole, _ = jax.jit(lambda p, t: M.prefill(p, cfg, {"tokens": t}))(
            params, toks)
        _, primed = jax.jit(lambda p, t: M.prefill(p, cfg, {"tokens": t}))(
            params, toks[:, :S])
        grown = M.init_cache(cfg, B, S + 1)
        cache = jax.tree.map(
            lambda d, s: jnp.pad(s, [(0, a - b) for a, b in
                                     zip(d.shape, s.shape)]), grown, primed)
        step, _ = jax.jit(lambda p, c, t: M.decode_step(
            p, cfg, c, t, jnp.int32(S)))(params, cache, toks[:, S:])
        return (np.asarray(full[:, 0]), np.asarray(step[:, 0]),
                np.asarray(whole[:, 0]))

    full, step, whole = served(cfg)
    scale = np.abs(full).max()
    np.testing.assert_allclose(step, full, rtol=0, atol=1e-5 * scale)
    np.testing.assert_allclose(whole, full, rtol=0, atol=1e-5 * scale)
    for name, neutral in [("embedding_multiplier", 1.0),
                          ("attention_multiplier", None),
                          ("residual_multiplier", 1.0),
                          ("logits_scaling", 1.0)]:
        other, _, _ = served(dataclasses.replace(cfg, **{name: neutral}))
        assert np.abs(other - full).max() > 1e-3 * scale, name


def host_routing(params, cfg, tokens):
    """The rows each expert gets in a one-layer model's first decode step
    (position 0, empty cache), recomputed in float64 on the host: attention
    over the one key returns its value, so the router's input is the
    normed residual after the attention add."""
    def rms(x):
        return x / np.sqrt(np.mean(x * x, axis=-1, keepdims=True) + 1e-6)
    lay = jax.tree.map(lambda a: np.asarray(a[0], np.float64),
                       params["layers"])
    x = np.asarray(params["embed"], np.float64)[tokens[:, 0]]
    x = x * cfg.embedding_multiplier
    v = np.einsum("bd,dhk->bhk", rms(x), lay["attn"]["wv"])
    g = cfg.num_heads // cfg.num_kv_heads
    o = np.repeat(v, g, axis=1)                      # query head -> kv head
    a = np.einsum("bhk,hkd->bd", o, lay["attn"]["wo"])
    h = x + cfg.residual_multiplier * a
    logits = rms(h) @ lay["moe"]["router"]
    idx = np.argsort(-logits, axis=-1, kind="stable")[:, :cfg.top_k]
    return np.bincount(idx.ravel(), minlength=cfg.num_experts)


def test_decode_counts_match_host_routing():
    cfg = dataclasses.replace(granite_smoke(), num_layers=1)
    params = M.init_params(cfg, KEY)
    B = 16
    tokens = np.arange(B, dtype=np.int32)[:, None] * 7 % cfg.vocab_size
    want = host_routing(params, cfg, tokens)
    step = jax.jit(lambda p, c, t: M.decode_step(p, cfg, c, t, jnp.int32(0)))
    cache = M.init_cache(cfg, B, 4)
    for _ in range(2):          # the same step twice: every count doubles
        _, cache = step(params, cache, jnp.asarray(tokens))
    c = jax.device_get(cache["moe"])
    np.testing.assert_array_equal(c["rows"][0], 2 * want)
    assert c["touched"][0] == 2 * (want > 0).sum()
    assert c["rows_max"][0] == want.max()
    assert c["steps"][0] == 2


def test_serving_reports_the_counters(tmp_path):
    """``run_serving``'s ``moe.*`` metrics are the sums of the counters the
    decode caches of a straight run of the same steps end with."""
    from repro.launch.serve import (make_prompts, prefill_microbatch,
                                    run_serving, serving_steps)
    cfg = granite_smoke()
    n, mb, plen, steps = 4, 2, 8, 4
    stats = tmp_path / "stats.json"
    run_serving(cfg, num_requests=n, microbatch=mb, prompt_len=plen,
                decode_steps=steps, execution="compiled",
                stats_json=str(stats))
    got = json.loads(stats.read_text())["metrics"]

    params = M.init_params(cfg, jax.random.PRNGKey(0))
    _, decode_one = serving_steps(cfg)
    prompts = make_prompts(cfg, n, plen)
    rows = touched = layer_steps = 0
    rows_max = 0
    for start in range(0, n, mb):
        tok, cache = prefill_microbatch(cfg, params,
                                        prompts[start:start + mb],
                                        plen + steps)
        for i in range(steps - 1):
            tok, cache = decode_one(params, cache, tok,
                                    jnp.int32(plen + i))
        c = jax.device_get(cache["moe"])
        rows += int(c["rows"].sum())
        touched += int(c["touched"].sum())
        layer_steps += int(c["steps"].sum())
        rows_max = max(rows_max, int(c["rows_max"].max()))
    assert layer_steps == (n // mb) * (steps - 1) * cfg.num_layers
    assert rows == layer_steps * mb * cfg.top_k
    assert got["counters"]["moe.rows_routed"] == rows
    assert got["counters"]["moe.layer_steps"] == layer_steps
    assert got["counters"]["moe.experts_touched"] == touched
    assert got["gauges"]["moe.expert_rows_max"] == rows_max
