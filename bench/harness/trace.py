"""From a profiler trace to per-layer numbers.

``load_xplane`` reads the ``.xplane.pb`` the JAX profiler writes into a
``Trace``: the device's op and program executions (the ``XLA Ops`` and
``XLA Modules`` lines of each ``/device:TPU:n`` plane; the DMA engines'
``Async XLA Ops`` are not counted as busy), the host's dispatches
(``PjitFunction(<fn>)``), the benchmark's own spans (``bench.*``, from
``TraceAnnotation``) and its two marks ``bench.mark.open`` and
``bench.mark.close``, which bound the traced window on the trace's own
clock.  A ``Trace`` is also saved and loaded as JSON, which is how
``bench/tests`` checks the reduction on a small recorded trace.

The reduction assumes the trace was started while the device was idle,
before the first dispatch it covers, as the harness does.  Then a
program's executions on the device run in the order of their host
dispatches, and the k-th execution of ``jit_<fn>`` in the trace is the
k-th dispatch of ``<fn>``; the ``bench.*`` span around a dispatch says
what it served (rows, prompt length, and for decode its step).  Where two
threads dispatch at the same instant the device may take them in the
other order; the metrics are sums over the pairs, which such a swap
leaves unchanged.
"""
from __future__ import annotations

import json
import re
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Tuple

Event = Tuple[str, float, float]            # name, start_ns, end_ns

_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_MODULE_NAME = re.compile(r"^(?:jit_)?([^(]+)")
_LAYOUT = re.compile(r"\{[^{}]*\}")
MARK = "bench.mark."


@dataclass
class Trace:
    ops: Dict[str, List[Event]] = field(default_factory=dict)       # per device
    modules: Dict[str, List[Event]] = field(default_factory=dict)   # per device
    # per host thread line: bench spans and dispatches, with span args
    spans: Dict[str, List[Tuple[str, float, float, Dict]]] = \
        field(default_factory=dict)
    dispatches: Dict[str, List[Event]] = field(default_factory=dict)
    marks: Dict[str, float] = field(default_factory=dict)           # ns

    def to_json(self) -> str:
        return json.dumps({"ops": self.ops, "modules": self.modules,
                           "spans": self.spans,
                           "dispatches": self.dispatches,
                           "marks": self.marks})

    @classmethod
    def from_json(cls, text: str) -> "Trace":
        d = json.loads(text)
        return cls(ops={k: [tuple(e) for e in v] for k, v in d["ops"].items()},
                   modules={k: [tuple(e) for e in v]
                            for k, v in d["modules"].items()},
                   spans={k: [tuple(e) for e in v]
                          for k, v in d["spans"].items()},
                   dispatches={k: [tuple(e) for e in v]
                               for k, v in d["dispatches"].items()},
                   marks=dict(d.get("marks", {})))


def _stat(v):
    return v if isinstance(v, (int, float, str)) else str(v)


def op_label(hlo: str) -> str:
    """``%fusion.7 = bf16[8,1536]{1,0:T(8,128)} fusion(...), kind=...`` ->
    ``%fusion.7 bf16[8,1536] fusion``: the instruction, its result type
    without layouts (``tuple`` for a tuple) and its opcode."""
    name, sep, rest = hlo.partition(" = ")
    if not sep:
        return hlo[:80]
    rest = _LAYOUT.sub("", rest)
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                break
        typ, rest = "tuple", rest[i + 1:].lstrip()
    else:
        typ, _, rest = rest.partition(" ")
    return f"{name} {typ} {rest.split('(', 1)[0]}"


def _attribute(ops: List[Event], modules: List[Event]) -> List[Event]:
    """Each op named ``<program>:<op label>`` by the module execution it
    lies in (``?`` where none holds it).  Both lists sorted by start."""
    out, j = [], 0
    for name, s, e in ops:
        while j < len(modules) and modules[j][2] < s:
            j += 1
        inside = j < len(modules) and modules[j][1] <= s
        prog = program_name(modules[j][0]) if inside else "?"
        out.append((f"{prog}:{op_label(name)}", s, e))
    return out


def load_xplane(path: Path) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    tr = Trace()
    for plane in pd.planes:
        if _DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dst = tr.ops.setdefault(plane.name, [])
                elif line.name == "XLA Modules":
                    dst = tr.modules.setdefault(plane.name, [])
                else:
                    continue
                dst.extend((e.name, e.start_ns, e.end_ns) for e in line.events)
        elif plane.name == "/host:CPU":
            for i, line in enumerate(plane.lines):
                key = f"{i}:{line.name}"
                for e in line.events:
                    if e.name.startswith(MARK):
                        tr.marks[e.name[len(MARK):]] = e.start_ns
                    elif e.name.startswith("bench."):
                        tr.spans.setdefault(key, []).append(
                            (e.name, e.start_ns, e.end_ns,
                             {k: _stat(v) for k, v in e.stats}))
                    elif e.name.startswith("PjitFunction("):
                        tr.dispatches.setdefault(key, []).append(
                            (e.name[len("PjitFunction("):-1],
                             e.start_ns, e.end_ns))
    for d in (tr.ops, tr.modules, tr.spans, tr.dispatches):
        for v in d.values():
            v.sort(key=lambda e: e[1])
    tr.ops = {k: _attribute(v, tr.modules.get(k, []))
              for k, v in tr.ops.items()}
    return tr


def program_name(module: str) -> str:
    """``jit_decode_one(12)`` -> ``decode_one``: the jitted function's
    name, stable across compiles and runs."""
    m = _MODULE_NAME.match(module)
    return m.group(1) if m else module


def union_ns(events: List[Event],
             window: Optional[Tuple[float, float]] = None) -> float:
    """Length of the union of the events' intervals, within ``window``
    (ns) where one is given."""
    lo, hi = window or (-float("inf"), float("inf"))
    total, cur_s, cur_e = 0.0, None, None
    for _, s, e in sorted(events, key=lambda x: x[1]):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy_s(tr: Trace) -> float:
    """Seconds of the traced window in which an op ran, averaged over the
    traced devices."""
    win = window_ns(tr)
    if not tr.ops or win is None:
        return 0.0
    return sum(union_ns(v, win) for v in tr.ops.values()) / len(tr.ops) / 1e9


def executions(tr: Trace, fn: str) -> List[Event]:
    """The first device's executions of the program jitted from ``fn``."""
    if not tr.modules:
        return []
    first = sorted(tr.modules)[0]
    return [e for e in tr.modules[first] if program_name(e[0]) == fn]


def served_dispatches(tr: Trace, fn: str) -> List[Dict]:
    """Each outermost host dispatch of ``fn``, in dispatch order.  One made
    inside a recorded ``bench.*`` span carries the span's args and
    ``step``, its index among the span's dispatches of ``fn``; one whose
    span was still open when the trace stopped (so it was not recorded)
    carries ``span: None``."""
    out = []
    for line, evs in tr.dispatches.items():
        spans = tr.spans.get(line, [])
        last_end = -1.0
        steps: Dict[int, int] = defaultdict(int)
        for name, s, e in evs:
            if name != fn or s < last_end:
                continue                    # a nested event of one dispatch
            last_end = e
            d = {"start": s, "span": None}
            for j, (span, ss, se, args) in enumerate(spans):
                if ss <= s <= se:
                    d.update(args, span=span, step=steps[j])
                    steps[j] += 1
                    break
            out.append(d)
    out.sort(key=lambda d: d["start"])
    return out


def matched(tr: Trace, fn: str) -> List[Tuple[Event, Dict]]:
    """Device executions of ``fn`` paired with the dispatches that made
    them, in order, where the dispatch's span was recorded."""
    return [(ex, d) for ex, d in zip(executions(tr, fn),
                                     served_dispatches(tr, fn))
            if d["span"] is not None]


def self_times(ops: List[Event]) -> Dict[str, float]:
    """Seconds per op name, each op's time less that of the ops nested in
    it (a ``while`` holds its body's ops)."""
    tot: Dict[str, float] = defaultdict(float)
    stack: List[List] = []              # [name, start, end, nested ns]

    def done(item):
        tot[item[0]] += (item[2] - item[1] - item[3]) / 1e9
    for name, s, e in sorted(ops, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][2] <= s:
            done(stack.pop())
        if stack:
            stack[-1][3] += min(e, stack[-1][2]) - s
        stack.append([name, s, e, 0.0])
    for item in stack:
        done(item)
    return tot


def top_ops(tr: Trace, n: int = 10) -> List[List]:
    """The ``n`` device operations that took most time on the first
    device, by self time summed over their executions."""
    if not tr.ops:
        return []
    tot = self_times(tr.ops[sorted(tr.ops)[0]])
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(tr: Trace, n: int = 10) -> List[List]:
    """Idle time of the first device inside the traced window, summed by
    what the host was doing at each gap's middle: the names of the
    ``bench.*`` spans open then, joined by ``+``, or ``host.idle``.
    Largest first."""
    win = window_ns(tr)
    if not tr.ops or win is None:
        return []
    gaps, cur = [], win[0]
    for _, s, e in sorted(tr.ops[sorted(tr.ops)[0]], key=lambda x: x[1]):
        if s >= win[1]:
            break
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if win[1] > cur:
        gaps.append((cur, win[1]))
    spans = [sp for v in tr.spans.values() for sp in v]
    tot: Dict[str, float] = defaultdict(float)
    for s, e in gaps:
        mid = (s + e) / 2
        names = sorted({sp[0] for sp in spans if sp[1] <= mid <= sp[2]})
        tot["+".join(names) or "host.idle"] += (e - s) / 1e9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def window_ns(tr: Trace) -> Optional[Tuple[float, float]]:
    """The traced window: from the ``open`` mark to the ``close`` mark, or
    where a trace has none, the first and last instant any event covers."""
    if "open" in tr.marks and "close" in tr.marks:
        return tr.marks["open"], tr.marks["close"]
    pts = [t for d in (tr.ops, tr.modules, tr.dispatches)
           for v in d.values() for e in v for t in (e[1], e[2])]
    pts += [t for v in tr.spans.values() for sp in v for t in (sp[1], sp[2])]
    return (min(pts), max(pts)) if pts else None
