"""decode_step_ms -- model steps (``launch/serve.py`` -> ``train/steps.py``).

Mean device time of one execution of the jitted decode step
(``decode_one``) in the trace, in milliseconds.  Moves
``gen_tokens_per_s``.
"""
from harness import trace


def read(run):
    if run.trace is None:
        return None
    ex = trace.executions(run.trace, "decode_one")
    return sum(e - s for _, s, e in ex) / len(ex) / 1e6 if ex else None
