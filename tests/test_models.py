"""Per-arch reduced-config smoke tests + serve-path equivalence (f)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import (ARCH_NAMES, cell_supported, get_config,
                           get_smoke_config, input_specs)
from repro.models import model as M
from repro.models.common import SHAPES

KEY = jax.random.PRNGKey(0)
B, S = 2, 16

# The SSM smoke configs have d_state == headdim, where a state stored in the
# wrong order still has the right shape; these cases widen d_state.
WIDE_STATE = {"mamba2_1_3b-wide_state": "mamba2_1_3b",
              "zamba2_2_7b-wide_state": "zamba2_2_7b"}


def smoke_config(case):
    if case in WIDE_STATE:
        return dataclasses.replace(get_smoke_config(WIDE_STATE[case]),
                                   ssm_state=32)
    return get_smoke_config(case)


def assert_state_layout(cfg, cache):
    """The SSM state is stored (L, B, heads, headdim, d_state)."""
    if cfg.family in ("ssm", "hybrid"):
        assert cache["ssm"]["state"].shape == (
            cfg.num_layers, B, cfg.ssm_heads, cfg.ssm_headdim, cfg.ssm_state)


def make_batch(cfg, with_labels=True):
    batch = {"tokens": jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)}
    if with_labels:
        batch["labels"] = jax.random.randint(
            jax.random.PRNGKey(9), (B, S), 0, cfg.vocab_size)
    if cfg.family == "encdec":
        batch["frames"] = jax.random.normal(
            KEY, (B, max(S // cfg.encoder_ratio, 1), cfg.d_model),
            jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ARCH_NAMES + list(WIDE_STATE))
class TestSmoke:
    def test_train_step_shapes_and_finite(self, arch):
        cfg = smoke_config(arch)
        params = M.init_params(cfg, KEY)
        batch = make_batch(cfg)
        loss, parts = jax.jit(
            lambda p, b: M.forward_train(p, cfg, b, remat=False))(
            params, batch)
        assert loss.shape == ()
        assert bool(jnp.isfinite(loss)), arch
        assert bool(jnp.isfinite(parts["loss"]))

    def test_train_step_with_remat_matches(self, arch):
        cfg = smoke_config(arch)
        params = M.init_params(cfg, KEY)
        batch = make_batch(cfg)
        l1, _ = jax.jit(lambda p, b: M.forward_train(p, cfg, b,
                                                     remat=False))(
            params, batch)
        l2, _ = jax.jit(lambda p, b: M.forward_train(p, cfg, b,
                                                     remat=True))(
            params, batch)
        np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                                   rtol=1e-5)

    def test_decode_matches_prefill(self, arch):
        cfg = smoke_config(arch)
        params = M.init_params(cfg, KEY)
        batch = make_batch(cfg, with_labels=False)
        logits_full, primed = jax.jit(
            lambda p, b: M.prefill(p, cfg, b))(params, batch)
        cache = M.init_cache(cfg, B, S)
        if cfg.family == "encdec":
            cache["cross_k"] = primed["cross_k"]
            cache["cross_v"] = primed["cross_v"]
        step = jax.jit(lambda p, c, t, pos: M.decode_step(p, cfg, c, t, pos))
        toks = batch["tokens"]
        for i in range(S):
            logits_i, cache = step(params, cache, toks[:, i:i + 1],
                                   jnp.int32(i))
        assert_state_layout(cfg, primed)
        assert_state_layout(cfg, cache)
        diff = float(jnp.max(jnp.abs(logits_i[:, 0] - logits_full[:, 0])))
        assert diff < 2e-2, (arch, diff)

    def test_decode_continues_from_primed_cache(self, arch):
        """prefill cache + decode of one extra token == decode-from-scratch."""
        cfg = smoke_config(arch)
        params = M.init_params(cfg, KEY)
        toks = jax.random.randint(KEY, (B, S + 1), 0, cfg.vocab_size)
        batch = {"tokens": toks[:, :S]}
        if cfg.family == "encdec":
            batch["frames"] = jax.random.normal(
                KEY, (B, max(S // cfg.encoder_ratio, 1), cfg.d_model),
                jnp.float32)
        _, primed = jax.jit(lambda p, b: M.prefill(p, cfg, b))(params, batch)
        # grow KV buffers to S+1 by padding the seq axis
        grown = M.init_cache(cfg, B, S + 1)

        def fill(dst, src):
            pad = [(0, d - s) for d, s in zip(dst.shape, src.shape)]
            return jnp.pad(src, pad).astype(dst.dtype)
        primed_grown = jax.tree.map(fill, grown, primed)
        step = jax.jit(lambda p, c, t, pos: M.decode_step(p, cfg, c, t, pos))
        assert_state_layout(cfg, primed)
        l_primed, after = step(params, primed_grown, toks[:, S:S + 1],
                               jnp.int32(S))
        assert_state_layout(cfg, after)

        scratch = M.init_cache(cfg, B, S + 1)
        if cfg.family == "encdec":
            scratch["cross_k"] = fill(scratch["cross_k"], primed["cross_k"])
            scratch["cross_v"] = fill(scratch["cross_v"], primed["cross_v"])
        for i in range(S + 1):
            l_scratch, scratch = step(params, scratch, toks[:, i:i + 1],
                                      jnp.int32(i))
        diff = float(jnp.max(jnp.abs(l_primed - l_scratch)))
        assert diff < 2e-2, (arch, diff)


@pytest.mark.parametrize("arch", ["mamba2_1_3b", "mamba2_1_3b-wide_state",
                                  "zamba2_2_7b"])
def test_decode_state_kernel_keeps_the_xla_numbers(arch, monkeypatch):
    """Decode steps of a tiny ``ssm`` config, whose state update and
    read-out run in one kernel (``kernels/ssd_decode.py``), give the
    logits and cache of the XLA form within float32 rounding; the first
    step reads a bf16 state, as after prefill, the later ones float32.
    The ``hybrid`` family keeps the XLA form: its step has no kernel."""
    from repro.kernels import ops, ref
    cfg = smoke_config(arch)
    params = M.init_params(cfg, KEY)
    steps = 3
    cache = M.init_cache(cfg, B, steps)
    cache["ssm"]["state"] = jax.random.normal(
        KEY, cache["ssm"]["state"].shape).astype(jnp.bfloat16)
    toks = jax.random.randint(KEY, (B, steps), 0, cfg.vocab_size)

    def decode():
        step = jax.jit(lambda c, t, pos: M.decode_step(params, cfg, c, t,
                                                       pos))
        c, logits = cache, []
        for i in range(steps):
            out, c = step(c, toks[:, i:i + 1], jnp.int32(i))
            logits.append(out)
        jaxpr = str(jax.make_jaxpr(step)(cache, toks[:, :1], jnp.int32(0)))
        return jnp.stack(logits), c, "pallas_call" in jaxpr

    logits, after, kernel = decode()
    monkeypatch.setattr(ops, "ssd_decode", ref.ssd_decode_reference)
    want_logits, want, _ = decode()
    assert kernel == (cfg.family == "ssm")
    assert after["ssm"]["state"].dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want_logits),
                               atol=1e-5, rtol=1e-5)
    for got, ok in zip(jax.tree.leaves(after), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ok),
                                   atol=1e-5, rtol=1e-5)


class TestFullConfigs:
    @pytest.mark.parametrize("arch", ARCH_NAMES)
    def test_exact_assigned_numbers(self, arch):
        cfg = get_config(arch)
        table = {
            "whisper_large_v3": (32, 1280, 20, 20, 5120, 51866),
            "grok_1_314b": (64, 6144, 48, 8, 32768, 131072),
            "granite_moe_3b_a800m": (32, 1536, 24, 8, 512, 49155),
            "nemotron_4_15b": (32, 6144, 48, 8, 24576, 256000),
            "gemma2_27b": (46, 4608, 32, 16, 36864, 256000),
            "codeqwen15_7b": (32, 4096, 32, 32, 13440, 92416),
            "command_r_plus_104b": (64, 12288, 96, 8, 33792, 256000),
            "zamba2_2_7b": (54, 2560, 32, 32, 10240, 32000),
            "mamba2_1_3b": (48, 2048, 0, 0, 0, 50280),
            "chameleon_34b": (48, 8192, 64, 8, 22016, 65536),
        }
        L, d, h, kv, ff, v = table[arch]
        assert cfg.num_layers == L
        assert cfg.d_model == d
        assert cfg.num_heads == h
        assert cfg.num_kv_heads == kv
        assert cfg.d_ff == ff
        assert cfg.vocab_size == v

    def test_moe_settings(self):
        g = get_config("grok_1_314b")
        assert (g.num_experts, g.top_k) == (8, 2)
        gr = get_config("granite_moe_3b_a800m")
        assert (gr.num_experts, gr.top_k) == (40, 8)

    def test_ssm_state_sizes(self):
        assert get_config("mamba2_1_3b").ssm_state == 128
        assert get_config("zamba2_2_7b").ssm_state == 64

    def test_grok_param_count_near_314b(self):
        n = get_config("grok_1_314b").param_count()
        assert 2.6e11 < n < 3.7e11, n

    def test_long_500k_applicability(self):
        runnable = [a for a in ARCH_NAMES
                    if cell_supported(get_config(a),
                                      SHAPES["long_500k"]) is None]
        assert sorted(runnable) == ["mamba2_1_3b", "zamba2_2_7b"]

    @pytest.mark.parametrize("arch", ARCH_NAMES)
    @pytest.mark.parametrize("shape", sorted(SHAPES))
    def test_input_specs_are_abstract(self, arch, shape):
        cfg = get_config(arch)
        sc = SHAPES[shape]
        if cell_supported(cfg, sc):
            pytest.skip("cell skipped by design")
        specs = input_specs(cfg, sc)
        for leaf in jax.tree.leaves(specs):
            assert isinstance(leaf, jax.ShapeDtypeStruct)
        assert specs["tokens"].shape[0] == sc.global_batch


class TestLossTrains:
    def test_tiny_model_loss_decreases(self):
        """A few optimizer steps on repeated data must cut the loss."""
        from repro.data import synthetic_batch
        from repro.train import make_train_step, train_state_init
        cfg = dataclasses.replace(get_smoke_config("codeqwen15_7b"),
                                  num_layers=2)
        state = train_state_init(cfg, KEY)
        step = jax.jit(make_train_step(
            cfg, peak_lr=3e-3, warmup_steps=2, total_steps=40, remat=False))
        b = synthetic_batch(0, 0, 0, 4, 32, cfg.vocab_size)
        batch = {k: jnp.asarray(v) for k, v in b.items()}
        state, m0 = step(state, batch)
        for _ in range(15):
            state, m = step(state, batch)
        assert float(m["loss"]) < float(m0["loss"]) - 0.5, (
            float(m0["loss"]), float(m["loss"]))
