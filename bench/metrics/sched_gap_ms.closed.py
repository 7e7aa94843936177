"""sched_gap_ms.closed -- scheduler layer (``core/exec_compiled.py``).

Per session, the execute wall minus its longest chain of app walls (one
microbatch's prefill and decode, then the assemble), from the session's
``Timeline``; the mean over the window's sessions, in milliseconds.  It
is the time the frontier scheduler adds around the apps.  Moves
``gen_tokens_per_s``.
"""


def read(run):
    g = [r.exec_wall - r.longest_chain for r in run.records
         if r.ok and r.exec_wall is not None and r.longest_chain is not None]
    return 1e3 * sum(g) / len(g) if g else None
