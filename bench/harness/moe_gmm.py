"""The grouped expert matmuls of a dropless MoE against their roofline.

A dropless MoE layer runs its experts as three grouped matmuls over the
routed rows (``jax.lax.ragged_dot``; on the TPU each is a
``%ragged-dot-…`` custom call, beside one ``%ragged-dot-metadata`` call
that only sorts out the groups and is not counted).  For ``rows`` routed
rows (tokens times ``top_k``), per layer:

* FLOPs: ``2 * rows * d_model * d_ff`` for each of the three matmuls;
* bytes: each matmul's rows in and out in bf16 (``rows * (d_model +
  d_ff)`` elements each), plus the bf16 weights of ``min(E, rows)``
  experts for each of the three: every expert the rows could reach.  Where
  the kernel skips an expert no row reached, this overstates the bytes,
  and the share with them;
* the bound: ``max(FLOPs / peak bf16 FLOP/s, bytes / HBM bytes/s)``.

A traced execution of the step serves ``rows`` = mb * top_k (decode) or
mb * prompt_len * top_k (prefill), read from the ``bench.*`` span around
its dispatch.  The share is the bound over the device time of the step's
grouped matmuls, summed over the executions.  That time is the
``%ragged-dot`` ops' and that of the ops which stage a layer's expert
weights for them: the kernel takes each weight operand as a buffer of its
own, so the compiled step slices the layer's ``(E, d, f)`` / ``(E, f, d)``
stack out of the stacked weights into one (in on-chip memory at decode),
and the kernel then reads that copy.  The staging ops are matched by their
result type alone (``bf16[E,d,f]`` / ``bf16[E,f,d]``): another op of the
step with that result type would be counted too, and a compiler that
staged the weights in another shape would leave the staging out.
"""
from __future__ import annotations

import re
from typing import Any, Dict, Optional

from . import trace

BF16 = 2
_GMM = re.compile(r"^%ragged-dot(?!-metadata)")


def gmm_flops(a: Dict[str, Any], rows: int) -> int:
    """FLOPs of one layer's three grouped matmuls over ``rows`` rows."""
    return 3 * 2 * rows * a["d_model"] * a["d_ff"]


def gmm_bytes(a: Dict[str, Any], rows: int) -> int:
    """Bytes one layer's three grouped matmuls must move: the rows in and
    out, and the weights of every expert the rows could reach."""
    d, f = a["d_model"], a["d_ff"]
    experts = min(a["num_experts"], rows)
    return 3 * BF16 * (rows * (d + f) + experts * d * f)


def gmm_bound_s(a: Dict[str, Any], rows: int, peaks: Dict[str, float]) -> float:
    """Seconds the whole model's grouped matmuls take at the roofline."""
    return a["num_layers"] * max(gmm_flops(a, rows) / peaks["bf16_flops_per_s"],
                                 gmm_bytes(a, rows) / peaks["hbm_bytes_per_s"])


def rows_served(a: Dict[str, Any], fn: str, span: Dict[str, Any]) -> int:
    """Routed rows per layer of one execution of ``fn``."""
    tokens = span["mb"] * (span["prompt_len"] if fn == "prefill_step" else 1)
    return tokens * a["top_k"]


def _split(label: str, fn: str):
    """``<program>:%name <result type> <opcode>`` -> (name, type) where the
    program is ``fn``, else None."""
    prog, sep, op = label.partition(":")
    if not sep or prog != fn:
        return None
    parts = op.split(" ")
    return parts[0], parts[1] if len(parts) > 1 else ""


def is_gmm_kernel(label: str, fn: str) -> bool:
    """Whether a device op is one of ``fn``'s grouped matmul kernels."""
    op = _split(label, fn)
    return op is not None and bool(_GMM.match(op[0]))


def is_gmm_op(label: str, fn: str, a: Dict[str, Any]) -> bool:
    """Whether a device op is one of ``fn``'s grouped matmul kernels or
    stages a layer's expert weights for them."""
    op = _split(label, fn)
    if op is None:
        return False
    e, d, f = a["num_experts"], a["d_model"], a["d_ff"]
    return bool(_GMM.match(op[0])) or op[1] in (f"bf16[{e},{d},{f}]",
                                                f"bf16[{e},{f},{d}]")


def _first_ops(run: Any, fn: str):
    """The first device's ops, or None where the trace holds no grouped
    matmul kernel of ``fn``."""
    tr = run.trace
    if tr is None or not tr.ops or "num_experts" not in run.arch:
        return None
    first = tr.ops[sorted(tr.ops)[0]]
    if not any(is_gmm_kernel(name, fn) for name, _, _ in first):
        return None
    return first


def kernel_ms(run: Any, fn: str) -> Optional[float]:
    """Device time of the grouped matmul kernels alone (no staging, no
    metadata call) per traced execution of ``fn``, in milliseconds; None
    where the trace holds no such kernel."""
    first = _first_ops(run, fn)
    if first is None:
        return None
    ops = [(s, e) for name, s, e in first if is_gmm_kernel(name, fn)]
    times = [sum(e - s for s, e in ops if s0 <= s < e0)
             for (_, s0, e0), _ in trace.matched(run.trace, fn)]
    times = [t for t in times if t > 0]
    return sum(times) / len(times) / 1e6 if times else None


def roofline_pct(run: Any, fn: str) -> Optional[float]:
    """The grouped matmuls' share of their roofline over the traced
    executions of ``fn`` (``decode_one`` or ``prefill_step``), in percent;
    None where the trace holds no grouped matmul kernel."""
    first = _first_ops(run, fn)
    if first is None:
        return None
    ops = [(s, e) for name, s, e in first if is_gmm_op(name, fn, run.arch)]
    bound = busy = 0.0
    for (_, s0, e0), span in trace.matched(run.trace, fn):
        t = sum(e - s for s, e in ops if s0 <= s < e0)
        if t > 0:
            busy += t / 1e9
            bound += gmm_bound_s(run.arch, rows_served(run.arch, fn, span),
                                 run.peaks)
    return 100.0 * bound / busy if busy > 0 else None
