"""The engine's own spans in a traced run, on the profiler's clock.

With ``TelemetryConfig(timeline=True)`` the engine stamps, for every
registry app of a session, when its wave handed it to dispatch
(``t_ready``), when it ran (``t_start``, ``t_end``) and the app that
activated it; for every ``execute_frontier`` call, its span; and on a
running profiler trace, an ``engine.anchor`` at each call's entry and exit
and at each app's stamp (``repro.core.telemetry``).  This module reduces them to the scheduler's
and the manager's per-layer numbers:

* ``summary(session)``: a compact record of one session, taken when it
  resolves, before the manager frees it;
* ``app_wait_ms``: per session, the sum over its registry apps of
  ``t_start - t_ready``, the time runnable apps waited for a worker;
* ``activation_delay_ms``: per session, the sum of ``t_ready`` less the
  end of the app that completed the last input, the time apps waited at
  the wave barrier with their inputs done;
* ``idle_shares``: the first device's idle time in the traced window, in
  percent of it, split by what the engine did meanwhile: ``sched``, some
  session was inside ``execute_frontier`` and no registry app of any
  session ran; ``no_session``, no session was inside it (admission,
  materialisation, the report, the client's turnaround).

The stamps are put on the trace's clock by a line fitted through the
anchors.  A program without these stamps (or a trace without two anchors,
or without a device plane) gives None, never 0.
"""
from __future__ import annotations

from dataclasses import dataclass
from pathlib import Path
from typing import List, Optional, Sequence, Tuple

import numpy as np

from . import trace as tr_mod

Interval = Tuple[float, float]


@dataclass
class SessionSpans:
    """One session's execute spans and, per registry app, its group and
    ``(t_ready, t_start, t_end, cause_end)``, in monotonic seconds."""
    exec_spans: List[Interval]
    groups: List[str]
    stamps: np.ndarray          # (apps, 4)


def summary(session) -> Optional[SessionSpans]:
    """The record of a resolved session, or None where it has no
    timeline or the program stamps no ``t_ready``."""
    tl = session.timeline
    if tl is None or not hasattr(tl, "causes"):
        return None
    ids = np.flatnonzero(~np.isnan(tl.t_ready))
    _, cause_end = tl.causes()
    stamps = np.stack([tl.t_ready[ids], tl.t_start[ids], tl.t_end[ids],
                       cause_end[ids]], axis=1)
    groups = [session.pgt.group_of(int(i)).name for i in ids]
    return SessionSpans(list(tl.exec_spans), groups, stamps)


def _mean_ms(spans: Sequence[Optional[SessionSpans]], a: int,
             b: int) -> Optional[float]:
    per = [float(np.sum(s.stamps[:, a] - s.stamps[:, b]))
           for s in spans if s is not None]
    return 1e3 * sum(per) / len(per) if per else None


def app_wait_ms(spans: Sequence[Optional[SessionSpans]]) -> Optional[float]:
    """Mean over sessions of the summed ``t_start - t_ready``."""
    return _mean_ms(spans, 1, 0)


def activation_delay_ms(spans: Sequence[Optional[SessionSpans]]
                        ) -> Optional[float]:
    """Mean over sessions of the summed ``t_ready - t_end(cause)``."""
    return _mean_ms(spans, 0, 3)


def anchors(xplane: Path) -> List[Tuple[int, float]]:
    """The ``engine.anchor`` pairs of a saved profile; none where the
    program emits none."""
    try:
        from repro.core.telemetry import read_anchors
    except ImportError:
        return []
    return read_anchors(xplane)


def _union(ivs) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(ivs):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _intersect(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Intersection of two unions (each sorted and disjoint)."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _length(ivs: List[Interval]) -> float:
    return sum(e - s for s, e in ivs)


def device_gaps(tr) -> List[Interval]:
    """The first device's idle intervals inside the traced window (ns)."""
    win = tr_mod.window_ns(tr)
    if not tr.ops or win is None:
        return []
    busy = _union((s, e) for _, s, e in tr.ops[sorted(tr.ops)[0]])
    idle, cur = [], win[0]
    for s, e in _intersect(busy, [win]):
        if s > cur:
            idle.append((cur, s))
        cur = e
    if win[1] > cur:
        idle.append((cur, win[1]))
    return idle


def idle_shares(tr, pairs: Sequence[Tuple[int, float]],
                spans: Sequence[Optional[SessionSpans]]) -> Optional[dict]:
    """``{"sched": %, "no_session": %}`` of the traced window, or None
    where the trace has no device plane or fewer than two anchors."""
    win = tr_mod.window_ns(tr)
    spans = [s for s in spans if s is not None]
    if not tr.ops or win is None or len(pairs) < 2 or not spans:
        return None
    from repro.core.telemetry import fit_clock
    fit = fit_clock(pairs)

    def mapped(ivs):
        ivs = np.asarray(ivs, dtype=np.float64).reshape(-1, 2)
        return _union(map(tuple, fit.to_trace_ns(ivs)))
    executing = mapped([iv for s in spans for iv in s.exec_spans])
    running = mapped(np.concatenate([s.stamps[:, 1:3] for s in spans]))
    idle = device_gaps(tr)
    idle_exec = _intersect(idle, executing)
    sched = _length(idle_exec) - _length(_intersect(idle_exec, running))
    no_session = _length(idle) - _length(idle_exec)
    width = win[1] - win[0]
    return {"sched": 100.0 * sched / width,
            "no_session": 100.0 * no_session / width}
