"""Kernel checks: Pallas (interpret mode) vs jnp oracle + VMEM budget.

No timing: interpret mode measures the interpreter, not the kernel.  The
rows are (a) max residuals against the oracle at bench shapes, (b) the
analytic VMEM working set per BlockSpec (must fit the ~16 MiB v5e VMEM),
and (c) arithmetic intensity of the tile (MXU utilisation potential).
The first line names the platform the residuals were computed on.
"""
from __future__ import annotations

from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.ssd_scan import ssd_scan_bhsd


def vmem_flash(block_q: int, block_k: int, d: int) -> int:
    """bytes: q + k + v tiles (bf16) + scratch (f32 acc/m/l) + scores."""
    return (block_q * d * 2 + 2 * block_k * d * 2
            + block_q * d * 4 + 2 * block_q * 4
            + block_q * block_k * 4)


def vmem_ssd(chunk: int, p: int, n: int) -> int:
    return (chunk * p * 2 + 2 * chunk * n * 2 + chunk * 4
            + n * p * 4 + chunk * chunk * 4 + chunk * p * 2)


def run() -> List[Tuple[str, float, str]]:
    rows = []
    key = jax.random.PRNGKey(0)

    # flash attention residual at a bench shape
    b, hq, hkv, s, d = 1, 4, 2, 256, 64
    q = jax.random.normal(key, (b, hq, s, d), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, hkv, s, d), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, hkv, s, d), jnp.float32)
    out = flash_attention_bhsd(q, k, v, block_q=128, block_k=128,
                               interpret=True)
    want = ref.mha_reference(q, k, v)
    resid = float(jnp.max(jnp.abs(out - want)))
    rows.append(("flash_attn_max_resid", resid,
                 f"shape=b{b}h{hq}kv{hkv}s{s}d{d};interpret=True"))
    for bq, bk, hd in [(128, 128, 128), (256, 128, 128), (128, 128, 64)]:
        vm = vmem_flash(bq, bk, hd)
        inten = (2 * bq * bk * hd * 2) / max(vmem_flash(bq, bk, hd), 1)
        rows.append((f"flash_vmem_bytes[bq={bq},bk={bk},d={hd}]",
                     float(vm), f"fits_16MiB={vm < 16*2**20};"
                     f"flops_per_byte={inten:.1f}"))

    # ssd residual
    b2, h2, s2, p2, n2 = 1, 4, 512, 64, 64
    x = jax.random.normal(key, (b2, h2, s2, p2), jnp.float32) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(3),
                                           (b2, h2, s2)))
    a = -jnp.exp(jax.random.normal(jax.random.PRNGKey(4), (h2,)) * 0.3)
    bb = jax.random.normal(jax.random.PRNGKey(5), (b2, h2, s2, n2)) * 0.5
    cc = jax.random.normal(jax.random.PRNGKey(6), (b2, h2, s2, n2)) * 0.5
    y, _ = ssd_scan_bhsd(x, dt, a, bb, cc, chunk=128, interpret=True)
    yr, _ = ref.ssd_reference(x, dt, a, bb, cc)
    resid = float(jnp.max(jnp.abs(y - yr)))
    rows.append(("ssd_scan_max_resid", resid,
                 f"shape=b{b2}h{h2}s{s2}p{p2}n{n2};interpret=True"))
    for ch, pp, nn in [(256, 64, 128), (128, 64, 64)]:
        vm = vmem_ssd(ch, pp, nn)
        rows.append((f"ssd_vmem_bytes[Q={ch},P={pp},N={nn}]",
                     float(vm), f"fits_16MiB={vm < 16*2**20}"))
    return rows


def main() -> None:
    dev = jax.devices()[0]
    print(f"# platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())}")
    for name, val, extra in run():
        print(f"{name},{val:g},{extra}")


if __name__ == "__main__":
    main()
