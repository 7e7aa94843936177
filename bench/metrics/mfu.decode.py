"""mfu.decode -- the whole decode step against the chip's peak.

Model FLOPs of the decode executions in the trace (``harness/flops.py``:
the work any implementation must do for those rows at those positions)
over their device time times the peak bf16 FLOP/s, in percent.  Moves
``gen_tokens_per_s``.
"""
from harness import flops, trace


def read(run):
    if run.trace is None:
        return None
    pairs = trace.matched(run.trace, "decode_one")
    if not pairs:
        return None
    work = sum(flops.decode_flops(run.arch, d["mb"], d["prompt_len"] + d["step"])
               for _, d in pairs)
    secs = sum(e - s for (_, s, e), _ in pairs) / 1e9
    return 100.0 * work / (secs * run.peaks["bf16_flops_per_s"])
