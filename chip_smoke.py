"""Smoke run of the serving path on one TPU chip, at published width.

Serves granite-moe-3b-a800m (full width, random weights from a seed)
through the graph engine's normal entry point, ``run_serving``: once on a
compiled Pipeline, then twice through a resident EngineManager, whose second
session must be a template-cache hit.  The served greedy tokens must equal a
plain reference that runs the same jitted prefill and decode steps straight,
with no engine.  Then both Pallas kernels run compiled (``interpret=False``)
at model widths and are compared with their jnp oracles.

Every phase prints its compile and wall seconds on its own line; the last
line is one JSON object naming the device.  Any failure exits non-zero at
once, and a machine without a TPU is refused before anything runs.

Run from the repository root on a machine with one chip:

    python chip_smoke.py
"""
from __future__ import annotations

import json
import sys
import time
from contextlib import contextmanager
from pathlib import Path

import numpy as np

ARCH = "granite_moe_3b_a800m"
REQUESTS, MICROBATCH, PROMPT, DECODE, NODES = 8, 4, 512, 32, 2
# relative to the largest reference value: bf16 inputs and outputs
KERNEL_TOL = 2e-2


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def fail(msg: str) -> None:
    print(f"[chip_smoke] FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


class CompileClock:
    """Seconds JAX spends in backend compiles (persistent-cache reads
    included), read per phase."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax) -> None:
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_: object) -> None:
        if event == self.EVENT:
            self.total += duration


def peak_bytes(dev) -> int | None:
    return (dev.memory_stats() or {}).get("peak_bytes_in_use")


@contextmanager
def phase(name: str, clock: CompileClock, dev):
    """Log a phase's compile and wall seconds, and the device's bytes in
    use at its end with their peak since the process started."""
    c0, t0 = clock.total, time.monotonic()
    log(f"phase {name}: start")
    yield
    log(f"phase {name}: compile_s={clock.total - c0:.3f} "
        f"wall_s={time.monotonic() - t0:.3f} "
        f"bytes_in_use={(dev.memory_stats() or {}).get('bytes_in_use')} "
        f"peak_bytes_in_use={peak_bytes(dev)}")


def count_files(path: Path) -> int:
    return sum(1 for p in path.rglob("*") if p.is_file()) \
        if path.is_dir() else 0


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want))
                 / max(float(np.max(np.abs(want))), 1e-6))


def run_kernels(jax, jnp, clock: CompileClock, dev) -> None:
    from repro.configs import get_config
    from repro.kernels import flash_attention_bhsd, ref, ssd_scan_bhsd

    key = jax.random.PRNGKey(1)
    ks = jax.random.split(key, 8)
    highest = jax.default_matmul_precision("highest")

    g = get_config(ARCH)
    with phase("flash_attention", clock, dev):
        b, s, d = 1, 2048, g.resolved_head_dim
        q = jax.random.normal(ks[0], (b, g.num_heads, s, d), jnp.bfloat16)
        k = jax.random.normal(ks[1], (b, g.num_kv_heads, s, d), jnp.bfloat16)
        v = jax.random.normal(ks[2], (b, g.num_kv_heads, s, d), jnp.bfloat16)
        out = jax.jit(lambda q, k, v: flash_attention_bhsd(
            q, k, v, causal=True, interpret=False))(q, k, v)
        with highest:
            want = ref.mha_reference(q.astype(jnp.float32),
                                     k.astype(jnp.float32),
                                     v.astype(jnp.float32))
        err = rel_err(out, want)
        log(f"flash_attention b{b} hq{g.num_heads} hkv{g.num_kv_heads} "
            f"s{s} d{d} bf16: max_rel_err={err:.3e} (tol {KERNEL_TOL})")
        if not err <= KERNEL_TOL:
            fail(f"flash_attention differs from ref: {err:.3e}")

    m = get_config("mamba2_1_3b")
    with phase("ssd_scan", clock, dev):
        b, s = 1, 2048
        h, p, n = m.ssm_heads, m.ssm_headdim, m.ssm_state
        x = (jax.random.normal(ks[3], (b, h, s, p)) * 0.5).astype(jnp.bfloat16)
        dt = jax.nn.softplus(jax.random.normal(ks[4], (b, h, s)) - 1.0)
        a = -jnp.exp(jax.random.normal(ks[5], (h,)) * 0.3)
        bb = (jax.random.normal(ks[6], (b, h, s, n)) * 0.5).astype(
            jnp.bfloat16)
        cc = (jax.random.normal(ks[7], (b, h, s, n)) * 0.5).astype(
            jnp.bfloat16)
        y, st = jax.jit(lambda *args: ssd_scan_bhsd(
            *args, m.ssm_chunk, interpret=False))(x, dt, a, bb, cc)
        with highest:
            yr, str_ = ref.ssd_reference(
                x.astype(jnp.float32), dt, a, bb.astype(jnp.float32),
                cc.astype(jnp.float32))
        ey, es = rel_err(y, yr), rel_err(st, str_)
        log(f"ssd_scan b{b} h{h} s{s} p{p} n{n} chunk{m.ssm_chunk} bf16: "
            f"max_rel_err y={ey:.3e} state={es:.3e} (tol {KERNEL_TOL})")
        if not (ey <= KERNEL_TOL and es <= KERNEL_TOL):
            fail(f"ssd_scan differs from ref: y {ey:.3e}, state {es:.3e}")


def main() -> None:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"no TPU: JAX found platform {dev.platform!r}; this smoke run "
             "needs one TPU chip and does not fall back")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    log(f"device platform={device['platform']} kind={device['kind']} "
        f"count={device['count']}")

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.launch.compile_cache import setup_compile_cache
    from repro.launch.serve import make_prompts, reference_tokens, run_serving
    from repro.models import model as M

    cache_dir = setup_compile_cache()
    cache_before = count_files(cache_dir)
    log(f"compile cache {cache_dir}: {cache_before} files before")
    clock = CompileClock(jax)
    cfg = get_config(ARCH)
    shape = dict(num_requests=REQUESTS, microbatch=MICROBATCH,
                 prompt_len=PROMPT, decode_steps=DECODE, num_nodes=NODES)

    with phase("init_params", clock, dev):
        params = jax.block_until_ready(
            M.init_params(cfg, jax.random.PRNGKey(0)))
        nbytes = sum(a.nbytes for a in jax.tree.leaves(params))
        log(f"{cfg.name}: {nbytes} parameter bytes")

    with phase("serve_compiled", clock, dev):
        single = run_serving(cfg, execution="compiled", params=params,
                             **shape)
    with phase("serve_manager_sessions2", clock, dev):
        multi = run_serving(cfg, sessions=2, params=params, **shape)
        if multi["template_hits"] != 1:
            fail(f"second manager session was not a template hit: "
                 f"{multi['template_hits']} hits")
        log("second manager session: template-cache hit")

    with phase("reference", clock, dev):
        want = reference_tokens(cfg, params,
                                make_prompts(cfg, REQUESTS, PROMPT),
                                microbatch=MICROBATCH, decode_steps=DECODE)
    for name, res in (("pipeline", single), ("manager", multi)):
        got = res["responses"]
        if got.shape != want.shape or not np.array_equal(got, want):
            fail(f"{name} tokens differ from the reference: "
                 f"{int(np.sum(got != want))} of {want.size} differ")
        log(f"{name} tokens {got.shape} equal the reference")

    run_kernels(jax, jnp, clock, dev)

    log(f"smoke reading, not a benchmark: serve_compiled "
        f"{single['gen_tokens_per_s']:.1f} tok/s (compiles included), "
        f"manager {multi['gen_tokens_per_s']:.1f} tok/s over 2 sessions")
    log(f"peak_bytes_in_use={peak_bytes(dev)}")
    cache_after = count_files(cache_dir)
    log(f"compile cache gained entries: {cache_after > cache_before} "
        f"({cache_before} -> {cache_after} files)")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
