"""Chip compiles of the Pallas kernels and of the serving steps, at the
widths the models run them at.

Each test lowers a program for one chip of a described (not attached) TPU
v5e topology.  A kernel, lowered with ``interpret=False``, must be accepted
by Mosaic: the compiled program carries a ``tpu_custom_call``.  The serving
steps must keep their cache's layout and their programs: the compiled
programs are read for copies, temporaries and instruction counts.  Nothing
runs; this is what the chip's compiler would refuse or re-lay out, caught
without the chip.

The topology is described inside a module fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.  The persistent compilation cache is off around these compiles,
since an entry compiled for a described chip cannot be read back here.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import flash_attention_bhsd, ssd_scan_bhsd
from repro.models import model as M
from repro.train.steps import make_decode_step, make_prefill_step


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_flash_attention_compiles_at_granite_widths(one_chip):
    cfg = get_config("granite_moe_3b_a800m")
    b, s, d = 1, 2048, cfg.resolved_head_dim
    q = _spec((b, cfg.num_heads, s, d), jnp.bfloat16, one_chip)
    kv = _spec((b, cfg.num_kv_heads, s, d), jnp.bfloat16, one_chip)
    compiled = jax.jit(lambda q, k, v: flash_attention_bhsd(
        q, k, v, causal=True, interpret=False)).lower(q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ssd_scan_compiles_at_mamba2_widths(one_chip):
    cfg = get_config("mamba2_1_3b")
    b, s = 1, 2048
    h, p, n = cfg.ssm_heads, cfg.ssm_headdim, cfg.ssm_state
    x = _spec((b, h, s, p), jnp.bfloat16, one_chip)
    dt = _spec((b, h, s), jnp.float32, one_chip)
    a = _spec((h,), jnp.float32, one_chip)
    bc = _spec((b, h, s, n), jnp.bfloat16, one_chip)
    compiled = jax.jit(lambda x, dt, a, b, c: ssd_scan_bhsd(
        x, dt, a, b, c, cfg.ssm_chunk, interpret=False)).lower(
            x, dt, a, bc, bc).compile()
    assert "tpu_custom_call" in compiled.as_text()


MAMBA2_MB = 16


def _mamba2_decode(one_chip, state_dtype):
    """mamba2-1.3b's served decode step (mb 16), compiled with its cache's
    state in ``state_dtype``: bf16 out of prefill, float32 after."""
    cfg = get_config("mamba2_1_3b")

    def spec(path, a):
        state = "state" in jax.tree_util.keystr(path)
        return _spec(a.shape, state_dtype if state else a.dtype, one_chip)
    params = jax.eval_shape(lambda k: M.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    params = jax.tree_util.tree_map_with_path(spec, params)
    cache = jax.tree_util.tree_map_with_path(
        spec, jax.eval_shape(lambda: M.init_cache(cfg, MAMBA2_MB, 1)))
    tokens = _spec((MAMBA2_MB, 1), jnp.int32, one_chip)
    pos = _spec((), jnp.int32, one_chip)
    return jax.jit(make_decode_step(cfg)).lower(
        params, cache, tokens, pos).compile()


@pytest.fixture(scope="module")
def mamba2_decode(one_chip):
    """The decode step of every step after the first (float32 state)."""
    return _mamba2_decode(one_chip, jnp.float32)


def test_mamba2_decode_step_keeps_the_state_layout(mamba2_decode):
    """The decode step at the served microbatch, with the float32 state of
    every step after the first, updates each layer's state in the layout
    the cache stores: no copy re-lays out a layer's state, and no
    temporary holds one (the state stays in the output stack)."""
    cfg = get_config("mamba2_1_3b")
    mb = MAMBA2_MB
    h, p, n = cfg.ssm_heads, cfg.ssm_headdim, cfg.ssm_state
    assert jax.eval_shape(lambda: M.init_cache(cfg, mb, 1))[
        "ssm"]["state"].shape == (cfg.num_layers, mb, h, p, n)
    compiled = mamba2_decode

    layer_state = re.compile(
        rf"= f32\[(1,)?{mb},{h},({p},{n}|{n},{p})\]\S* copy\(")
    copies = [line.strip() for line in compiled.as_text().splitlines()
              if layer_state.search(line)]
    assert not copies, copies
    one_layer = mb * h * p * n * 4
    assert compiled.memory_analysis().temp_size_in_bytes < one_layer


def _computations(text):
    """The compiled module's computations: name -> (header, body lines)."""
    comps, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?(%\S+) \(", line)
        if head and not line.startswith(" "):
            name = head.group(1)
            comps[name] = (line, [])
        elif name and line.startswith("  "):
            comps[name][1].append(line)
    return comps


@pytest.mark.parametrize("state_dtype", ["float32", "bfloat16"])
def test_mamba2_decode_updates_the_state_in_one_kernel(
        state_dtype, mamba2_decode, one_chip):
    """Both decode programs (bf16 state in on the first step, float32 on
    every later one) update each layer's state in one Pallas kernel inside
    the layer loop: the kernel reads the layer's state in place from the
    input stack and writes it into a float32 output stack that starts as
    an unfilled buffer (``AllocateBuffer``).  No fusion reads the state
    for the read-out's reduce, no copy holds a stack or a layer of state,
    and the temporaries stay under one layer's state."""
    cfg = get_config("mamba2_1_3b")
    L, mb = cfg.num_layers, MAMBA2_MB
    h, p, n = cfg.ssm_heads, cfg.ssm_headdim, cfg.ssm_state
    compiled = (mamba2_decode if state_dtype == "float32" else
                _mamba2_decode(one_chip, jnp.dtype(state_dtype)))
    text = compiled.as_text()
    comps = _computations(text)

    loops = re.findall(r" while\(.*?body=(%[^,\s]+)", text)
    assert len(loops) == 1, loops
    body = "\n".join(comps[loops[0]][1])
    assert body.count('custom_call_target="tpu_custom_call"') == 1
    assert text.count('custom_call_target="tpu_custom_call"') == 1

    state = rf"(f32|bf16)\[({L},|1,)?{mb},{h},({p},{n}|{n},{p})\]"
    readers = [name for name, (header, lines) in comps.items()
               if re.search(state, header)
               and any(" reduce(" in line for line in lines)]
    assert not readers, readers
    copies = [line.strip()[:160] for line in text.splitlines()
              if re.search(rf"= {state}\S* copy\(", line)]
    assert not copies, copies

    entry = next(lines for header, lines in comps.values()
                 if header.startswith("ENTRY"))
    stack = re.escape(f"f32[{L},{mb},{h},{p},{n}]")
    made = [line.strip()[:160] for line in entry if re.search(
        rf"= {stack}\S* (?!parameter|get-tuple-element)[a-z-]+\(", line)]
    assert len(made) == 1 and 'custom_call_target="AllocateBuffer"' in \
        made[0], made
    one_layer = mb * h * p * n * 4
    assert compiled.memory_analysis().temp_size_in_bytes < one_layer


def _ops(compiled):
    """The number of HLO instructions of a compiled program."""
    return sum(1 for line in compiled.as_text().splitlines()
               if re.match(r"\s*(ROOT )?%\S+ = ", line))


def test_mamba2_steps_keep_their_programs(mamba2_decode, one_chip):
    """mamba2-1.3b's decode and prefill programs (the benchmarked cell's,
    at mb 16 and 512-token prompts, the shared ``embed_tokens`` and
    ``logits_fn`` included) keep their temporaries and instruction count:
    prefill's since before granite's multipliers existed (branched on in
    Python at their neutral values), decode's since its state update and
    read-out became one kernel."""
    cfg = get_config("mamba2_1_3b")
    params = jax.tree.map(
        lambda a: _spec(a.shape, a.dtype, one_chip),
        jax.eval_shape(lambda k: M.init_params(cfg, k),
                       jax.random.PRNGKey(0)))
    prefill = jax.jit(make_prefill_step(cfg)).lower(
        params, {"tokens": _spec((MAMBA2_MB, 512), jnp.int32, one_chip)}
    ).compile()
    decode = mamba2_decode
    assert (decode.memory_analysis().temp_size_in_bytes, _ops(decode)) == (
        193_536, 474)
    assert (prefill.memory_analysis().temp_size_in_bytes, _ops(prefill)) == (
        582_951_936, 759)


def _granite_step(one_chip, make_step, *args):
    """granite-3.0-3b-a800m's ``make_step`` compiled at full width, with
    ``args`` built from its shapes."""
    cfg = get_config("granite_moe_3b_a800m")
    shape = functools.partial(_spec, sharding=one_chip)
    params = jax.tree.map(
        lambda a: shape(a.shape, a.dtype),
        jax.eval_shape(lambda k: M.init_params(cfg, k),
                       jax.random.PRNGKey(0)))
    return jax.jit(make_step(cfg)).lower(
        params, *jax.tree.map(lambda a: shape(a.shape, a.dtype), args)
    ).compile()


GRANITE_MB, GRANITE_CACHE, GRANITE_PROMPT = 8, 576, 512


@pytest.fixture(scope="module")
def granite_decode(one_chip):
    """The served decode step: mb 8 over a 576-token cache."""
    cfg = get_config("granite_moe_3b_a800m")
    cache = jax.eval_shape(
        lambda: M.init_cache(cfg, GRANITE_MB, GRANITE_CACHE))
    return _granite_step(one_chip, make_decode_step, cache,
                         jax.ShapeDtypeStruct((GRANITE_MB, 1), jnp.int32),
                         jax.ShapeDtypeStruct((), jnp.int32))


@pytest.fixture(scope="module")
def granite_prefill(one_chip):
    """The served prefill step: mb 8 prompts of 512 tokens."""
    return _granite_step(one_chip, make_prefill_step, {
        "tokens": jax.ShapeDtypeStruct((GRANITE_MB, GRANITE_PROMPT),
                                       jnp.int32)})


def test_granite_dropless_decode_is_a_grouped_matmul(granite_decode):
    """granite-3.0-3b-a800m's decode step at the served microbatch runs its
    experts as grouped matmuls over the 64 routed rows (the TPU's
    ``ragged-dot`` kernel, three per layer), not as a matmul over all 40
    experts' slots."""
    cfg = get_config("granite_moe_3b_a800m")
    rows = GRANITE_MB * cfg.top_k
    text = granite_decode.as_text()
    gmm = re.findall(r"%ragged-dot-none\S* = bf16\[(\d+),(\d+)\]", text)
    assert sorted(gmm) == sorted([(str(rows), str(cfg.d_ff))] * 2
                                 + [(str(rows), str(cfg.d_model))])
    # the capacity route's buffer of 8 slots per expert is gone
    assert f"bf16[1,{cfg.num_experts},8," not in text


@pytest.mark.parametrize("step", ["granite_decode", "granite_prefill"])
def test_granite_grouped_matmuls_read_the_experts_in_place(step, request):
    """Decode and prefill feed each grouped matmul the whole 32-layer
    expert stack, viewed as 32 x 40 groups (a bitcast of the parameter),
    so no instruction copies a layer's (40, ...) stack out of it; the
    decode step's temporaries stay under one layer's three stacks."""
    cfg = get_config("granite_moe_3b_a800m")
    compiled = request.getfixturevalue(step)
    text = compiled.as_text()
    e, d, f = cfg.num_experts, cfg.d_model, cfg.d_ff
    staged = re.findall(rf"= bf16\[{e},({d},{f}|{f},{d})\]", text)
    assert not staged, staged
    types = dict(re.findall(r"^\s*(?:ROOT )?(%\S+) = (\S+)", text, re.M))
    gmm = re.findall(r"%ragged-dot-none\S* = .*? custom-call\(([^)]*)\)",
                     text)
    assert len(gmm) == 3
    flat = f"bf16[{cfg.num_layers * e},"
    for operands in gmm:
        weights = [types[o.split("*/")[-1].strip()] for o in
                   operands.split(",")]
        assert any(t.startswith(flat) for t in weights), weights
    if step == "granite_decode":
        one_layer = 3 * e * d * f * 2
        assert compiled.memory_analysis().temp_size_in_bytes < one_layer


@pytest.mark.parametrize("step,program", [
    ("granite_decode", (15_746_560, 901)),
    ("granite_prefill", (206_484_480, 826)),
])
def test_granite_steps_keep_their_programs(step, program, request):
    """Granite's programs contain no SSM op, so the mamba2 decode kernel
    leaves them as they were: the same temporaries and instruction count
    for the served decode and prefill steps."""
    compiled = request.getfixturevalue(step)
    assert (compiled.memory_analysis().temp_size_in_bytes,
            _ops(compiled)) == program
