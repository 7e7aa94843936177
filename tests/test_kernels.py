"""Per-kernel shape/dtype sweeps against the jnp oracles (interpret mode).

The chip compiles of the same kernels live in tests/test_tpu_compile.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels import ssd_decode
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.ssd_decode import ssd_decode_bhpn
from repro.kernels.ssd_scan import ssd_scan_bhsd


def rnd(key, shape, dtype, scale=1.0):
    return (jax.random.normal(jax.random.PRNGKey(key), shape,
                              jnp.float32) * scale).astype(dtype)


class TestFlashAttention:
    @pytest.mark.parametrize("b,hq,hkv,sq,sk,d", [
        (1, 1, 1, 32, 32, 16),
        (2, 4, 2, 64, 64, 32),       # GQA 2:1
        (1, 8, 2, 128, 128, 64),     # GQA 4:1
        (2, 2, 2, 48, 80, 32),       # non-square, non-block-multiple
        (1, 4, 4, 17, 33, 8),        # ragged (padding path)
    ])
    def test_shapes_vs_oracle(self, b, hq, hkv, sq, sk, d):
        q = rnd(0, (b, hq, sq, d), jnp.float32)
        k = rnd(1, (b, hkv, sk, d), jnp.float32)
        v = rnd(2, (b, hkv, sk, d), jnp.float32)
        out = flash_attention_bhsd(q, k, v, causal=False,
                                   block_q=32, block_k=32, interpret=True)
        want = ref.mha_reference(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("causal,window,cap", [
        (True, 0, 0.0), (True, 16, 0.0), (False, 0, 0.0),
        (True, 0, 30.0), (True, 8, 50.0), (False, 0, 20.0),
    ])
    def test_mask_and_softcap_variants(self, causal, window, cap):
        q = rnd(3, (2, 4, 64, 32), jnp.float32)
        k = rnd(4, (2, 2, 64, 32), jnp.float32)
        v = rnd(5, (2, 2, 64, 32), jnp.float32)
        out = flash_attention_bhsd(q, k, v, causal=causal, window=window,
                                   logit_cap=cap, block_q=32, block_k=32,
                                   interpret=True)
        want = ref.mha_reference(q, k, v, causal=causal, window=window,
                                 logit_cap=cap)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("dtype,atol", [
        (jnp.float32, 2e-5), (jnp.bfloat16, 2e-2),
    ])
    def test_dtypes(self, dtype, atol):
        q = rnd(6, (1, 2, 64, 32), dtype, 0.5)
        k = rnd(7, (1, 2, 64, 32), dtype, 0.5)
        v = rnd(8, (1, 2, 64, 32), dtype, 0.5)
        out = flash_attention_bhsd(q, k, v, block_q=32, block_k=32,
                                   interpret=True)
        want = ref.mha_reference(q.astype(jnp.float32),
                                 k.astype(jnp.float32),
                                 v.astype(jnp.float32))
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(want), atol=atol,
            rtol=atol)

    def test_block_size_invariance(self):
        q = rnd(9, (1, 2, 128, 32), jnp.float32)
        k = rnd(10, (1, 2, 128, 32), jnp.float32)
        v = rnd(11, (1, 2, 128, 32), jnp.float32)
        o1 = flash_attention_bhsd(q, k, v, block_q=32, block_k=32,
                                  interpret=True)
        o2 = flash_attention_bhsd(q, k, v, block_q=64, block_k=128,
                                  interpret=True)
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                                   atol=2e-5, rtol=2e-5)

    def test_under_jit(self):
        q = rnd(12, (1, 2, 64, 16), jnp.float32)
        k = rnd(13, (1, 1, 64, 16), jnp.float32)
        v = rnd(14, (1, 1, 64, 16), jnp.float32)
        f = jax.jit(lambda a, b, c: flash_attention_bhsd(
            a, b, c, block_q=32, block_k=32, interpret=True))
        np.testing.assert_allclose(
            np.asarray(f(q, k, v)),
            np.asarray(ref.mha_reference(q, k, v)), atol=2e-5, rtol=2e-5)

    def test_caller_scale(self):
        """The score scale comes from the caller: Granite's 1/64 (in place
        of 1/sqrt(64)) gives the oracle's output at that scale."""
        q = rnd(15, (1, 4, 64, 64), jnp.float32, 4.0)
        k = rnd(16, (1, 2, 64, 64), jnp.float32, 4.0)
        v = rnd(17, (1, 2, 64, 64), jnp.float32)
        out = flash_attention_bhsd(q, k, v, scale=1 / 64, block_q=32,
                                   block_k=32, interpret=True)
        want = ref.mha_reference(q, k, v, scale=1 / 64)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)
        default = ref.mha_reference(q, k, v)
        assert np.abs(np.asarray(want) - np.asarray(default)).max() > 1e-2

    def test_model_attention_follows_the_multiplier(self):
        """``attention(use_kernel=True)`` scales the scores as the jnp path
        does, by the configuration's ``attention_multiplier``."""
        from repro.configs import get_smoke_config
        from repro.models.attention import attention, init_attention
        from repro.models.common import KeyGen
        cfg = get_smoke_config("granite_moe_3b_a800m")
        assert cfg.attention_multiplier == 1 / 64
        p = init_attention(KeyGen(jax.random.PRNGKey(18)), cfg, jnp.float32)
        x = rnd(19, (2, 32, cfg.d_model), jnp.float32, 4.0)
        pos = jnp.broadcast_to(jnp.arange(32), (2, 32))
        outs = [attention(p, x, cfg, positions=pos, use_kernel=kern)
                for kern in (False, True)]
        np.testing.assert_allclose(np.asarray(outs[1]), np.asarray(outs[0]),
                                   atol=1e-4, rtol=1e-4)


class TestSSDScan:
    @pytest.mark.parametrize("b,h,s,p,n,chunk", [
        (1, 1, 32, 8, 4, 8),
        (2, 3, 64, 16, 8, 16),
        (1, 2, 128, 32, 16, 32),
        (2, 1, 64, 8, 8, 64),        # single chunk
    ])
    def test_shapes_vs_oracle(self, b, h, s, p, n, chunk):
        x = rnd(0, (b, h, s, p), jnp.float32, 0.5)
        dt = jax.nn.softplus(rnd(1, (b, h, s), jnp.float32))
        a = -jnp.exp(rnd(2, (h,), jnp.float32, 0.3))
        bb = rnd(3, (b, h, s, n), jnp.float32, 0.5)
        cc = rnd(4, (b, h, s, n), jnp.float32, 0.5)
        y, st = ssd_scan_bhsd(x, dt, a, bb, cc, chunk, interpret=True)
        yr, str_ = ref.ssd_reference(x, dt, a, bb, cc)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                                   atol=2e-4, rtol=2e-4)
        np.testing.assert_allclose(np.asarray(st), np.asarray(str_),
                                   atol=2e-4, rtol=2e-4)

    def test_chunk_invariance(self):
        x = rnd(5, (1, 2, 64, 8), jnp.float32, 0.5)
        dt = jax.nn.softplus(rnd(6, (1, 2, 64), jnp.float32))
        a = -jnp.exp(rnd(7, (2,), jnp.float32, 0.3))
        bb = rnd(8, (1, 2, 64, 4), jnp.float32, 0.5)
        cc = rnd(9, (1, 2, 64, 4), jnp.float32, 0.5)
        y1, s1 = ssd_scan_bhsd(x, dt, a, bb, cc, 8, interpret=True)
        y2, s2 = ssd_scan_bhsd(x, dt, a, bb, cc, 32, interpret=True)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                                   atol=2e-4, rtol=2e-4)
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                                   atol=2e-4, rtol=2e-4)

    def test_bf16(self):
        x = rnd(10, (1, 2, 32, 8), jnp.bfloat16, 0.5)
        dt = jax.nn.softplus(rnd(11, (1, 2, 32), jnp.float32))
        a = -jnp.exp(rnd(12, (2,), jnp.float32, 0.3))
        bb = rnd(13, (1, 2, 32, 4), jnp.bfloat16, 0.5)
        cc = rnd(14, (1, 2, 32, 4), jnp.bfloat16, 0.5)
        y, _ = ssd_scan_bhsd(x, dt, a, bb, cc, 8, interpret=True)
        yr, _ = ref.ssd_reference(x.astype(jnp.float32), dt, a,
                                  bb.astype(jnp.float32),
                                  cc.astype(jnp.float32))
        np.testing.assert_allclose(np.asarray(y, np.float32),
                                   np.asarray(yr), atol=5e-2, rtol=5e-2)


class TestSSDDecode:
    """The decode state kernel against the XLA form it replaces, with the
    state bf16 (the first step after prefill) and float32 (every later
    step)."""

    @staticmethod
    def inputs(layers, b, h, p, n, state_dtype):
        states = rnd(30, (layers, b, h, p, n), state_dtype)
        decay = jnp.exp(-jax.nn.softplus(rnd(31, (b, h), jnp.float32)))
        x = rnd(32, (b, h, p), jnp.bfloat16)
        bdt = rnd(33, (b, h, n), jnp.float32, 0.5)
        c = rnd(34, (b, h, n), jnp.bfloat16)
        return states, decay, x, bdt, c

    @pytest.mark.parametrize("state_dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("layers,b,h,p,n,block_heads", [
        (2, 2, 8, 16, 16, 8),
        (3, 1, 4, 8, 32, 4),
        (3, 2, 16, 16, 16, 8),       # two blocks of heads
        (2, 3, 24, 8, 16, 8),        # three blocks of heads
    ])
    def test_matches_the_xla_form(self, monkeypatch, layers, b, h, p, n,
                                  block_heads, state_dtype):
        monkeypatch.setattr(ssd_decode, "BLOCK_BYTES",
                            block_heads * p * n * 4)
        assert ssd_decode._heads_per_block(h, p, n) == block_heads
        states, decay, x, bdt, c = self.inputs(layers, b, h, p, n,
                                               state_dtype)
        out = jnp.zeros((layers, b, h, p, n), jnp.float32)
        layer = jnp.int32(layers - 1)
        got, y = jax.jit(lambda *a: ssd_decode_bhpn(*a, interpret=True))(
            states, out, layer, decay, x, bdt, c)
        want, y_want = ref.ssd_decode_reference(states, out, layer, decay,
                                                x, bdt, c)
        assert got.dtype == y.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-6, rtol=1e-6)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_want),
                                   atol=1e-5, rtol=1e-5)

    def test_first_step_rounds_the_update_to_the_input_state(self):
        """With a bf16 input state the outer-product term is rounded to
        bf16 before the float32 add, as the XLA form does; unrounded, the
        new state would differ."""
        states, decay, x, bdt, c = self.inputs(1, 2, 8, 16, 16,
                                               jnp.bfloat16)
        out = jnp.zeros(states.shape, jnp.float32)
        got, _ = ssd_decode_bhpn(states, out, jnp.int32(0), decay, x, bdt,
                                 c, interpret=True)
        want, _ = ref.ssd_decode_reference(states, out, 0, decay, x, bdt, c)
        unrounded = (states[0].astype(jnp.float32) * decay[..., None, None]
                     + jnp.einsum("bhp,bhk->bhpk", x, bdt))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-6, rtol=1e-6)
        assert float(jnp.abs(got[0] - unrounded).max()) > 1e-4

    def test_writes_only_its_layer(self):
        """Layer ``layer`` of the output stack is written; its other layers
        and the input stack are left as they were."""
        states, decay, x, bdt, c = self.inputs(4, 2, 8, 16, 16, jnp.float32)
        out = jnp.full(states.shape, 7.0, jnp.float32)
        before = np.asarray(states)
        got, _ = jax.jit(lambda *a: ssd_decode_bhpn(*a, interpret=True))(
            states, out, jnp.int32(2), decay, x, bdt, c)
        got = np.asarray(got)
        assert not np.any(got[2] == 7.0)
        assert np.all(np.delete(got, 2, axis=0) == 7.0)
        np.testing.assert_array_equal(np.asarray(states), before)


class TestModelScanAgreement:
    """The associative-scan jnp path must equal the sequential oracle and the
    Pallas kernel — three implementations, one math."""

    def test_three_way_agreement(self):
        from repro.models.ssm import ssd_chunked
        b, h, s, p, n = 2, 4, 64, 8, 4
        x = rnd(20, (b, s, h, p), jnp.float32, 0.5)    # model layout
        dt = jax.nn.softplus(rnd(21, (b, s, h), jnp.float32))
        a = -jnp.exp(rnd(22, (h,), jnp.float32, 0.3))
        bb = rnd(23, (b, s, 1, n), jnp.float32, 0.5)   # one group
        cc = rnd(24, (b, s, 1, n), jnp.float32, 0.5)
        y_model, st_model = ssd_chunked(x, dt, a, bb, cc, chunk=16)
        # oracle layout
        xt = jnp.transpose(x, (0, 2, 1, 3))
        dtt = jnp.transpose(dt, (0, 2, 1))
        bt = jnp.repeat(jnp.transpose(bb, (0, 2, 1, 3)), h, axis=1)
        ct = jnp.repeat(jnp.transpose(cc, (0, 2, 1, 3)), h, axis=1)
        y_ref, st_ref = ref.ssd_reference(xt, dtt, a, bt, ct)
        y_kern, st_kern = ssd_scan_bhsd(xt, dtt, a, bt, ct, 16,
                                        interpret=True)
        y_model_t = jnp.transpose(y_model, (0, 2, 1, 3))
        np.testing.assert_allclose(np.asarray(y_model_t),
                                   np.asarray(y_ref), atol=2e-4, rtol=2e-4)
        np.testing.assert_allclose(np.asarray(y_kern),
                                   np.asarray(y_ref), atol=2e-4, rtol=2e-4)
        # states: model layout (B,H,N,P)
        np.testing.assert_allclose(np.asarray(st_model),
                                   np.asarray(st_ref), atol=2e-4, rtol=2e-4)
