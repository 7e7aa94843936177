"""Array-native engine telemetry (paper §4.2, §5).

DALiuGE's managers expose the runtime status of every drop up the MM/DIM/NM
hierarchy so operators can watch a million-task pipeline execute; the
follow-up "Empirical Evaluation On the Applicability of the DALiuGE
Execution Framework" diagnoses pipeline behaviour from exactly that
per-drop status/timing data.  The compiled path deliberately publishes no
per-drop events — this module restores the *observability* without giving
back the throughput, by keeping telemetry in the same shape as the engine:
flat parallel arrays, stamped wave-at-a-time.

Three layers, all off by default and enabled via :class:`TelemetryConfig`:

* :class:`Timeline` — per-drop ``t_start``/``t_end`` (float64 monotonic
  seconds), wave index and executing-node arrays on a
  ``CompiledSession``.  Batch fast paths (noop/identity/sleep and data
  drops) stamp whole waves vectorized; real Python apps are stamped
  individually around the registry call, so speculation and retries show
  their true durations.  Registry apps also get ``t_ready`` (when their
  wave handed them to dispatch) and, at read time, the app that
  activated them (:meth:`Timeline.causes`); each ``execute_frontier``
  call adds one execute span.  The call's entry and exit and each app's
  stamp put an ``engine.anchor`` on a running ``jax.profiler`` trace, so
  :func:`fit_clock` can map the stamps onto the trace's clock.
* :class:`MetricsRegistry` — process-local counters/gauges/fixed-bucket
  histograms (no external deps), wired into ``execute_frontier`` (waves,
  frontier sizes, dispatch batches), ``EngineManager`` (admission,
  queue depth, session-latency histogram, template cache traffic) and
  the dispatcher's resilience policies (retries, speculative wins,
  recoveries).
* :func:`export_chrome_trace` — Perfetto / chrome://tracing JSON: one
  track per cluster node, one slice per drop (or one aggregated slice
  per wave-batch above ``batch_threshold``), plus a pipeline-span track
  (translate/map/deploy/execute).  A 100k-drop session opens directly in
  ``ui.perfetto.dev``.

Overhead is gated: ``bench_execute.py --telemetry`` measures instrumented
vs clean drops/s and ``scripts/check_bench.py`` enforces the committed
``telemetry_overhead_pct`` ceiling (see ``docs/observability.md``).
"""
from __future__ import annotations

import json
import sys
import threading
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from .pgt import KIND_DATA, csr_gather_with_counts

__all__ = [
    "ANCHOR", "ClockFit", "Counter", "Gauge", "Histogram",
    "MetricsRegistry", "Span", "TelemetryConfig", "Timeline",
    "emit_anchor", "export_chrome_trace", "fit_clock", "read_anchors",
    "FRONTIER_BUCKETS", "LATENCY_BUCKETS_S",
]

# default fixed bucket grids (upper bounds; one overflow slot is appended)
LATENCY_BUCKETS_S = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                     0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0)
FRONTIER_BUCKETS = (1.0, 10.0, 100.0, 1e3, 1e4, 1e5, 1e6, 1e7)


@dataclass(frozen=True)
class TelemetryConfig:
    """What the engine records.  Everything defaults off (or free):
    a default-constructed config must leave the hot path untouched —
    ``tests/test_telemetry.py`` asserts no session arrays are allocated.

    * ``timeline`` — allocate + stamp the per-drop :class:`Timeline`
      arrays (4 × num_drops extra memory, a few array writes per wave);
    * ``metrics`` — create/attach a :class:`MetricsRegistry` and update
      it at wave/session granularity;
    * ``spans`` — record translate/map/deploy/execute :class:`Span`\\ s
      on the ``Pipeline`` (a handful of appends per run, kept on);
    * ``trace_batch_threshold`` — per-(node, wave) drop count above
      which :func:`export_chrome_trace` emits one aggregated slice
      instead of per-drop slices.
    """

    timeline: bool = False
    metrics: bool = False
    spans: bool = True
    trace_batch_threshold: int = 64


@dataclass
class Span:
    """One named pipeline stage interval (monotonic seconds)."""

    name: str
    t_start: float
    t_end: float

    @property
    def duration(self) -> float:
        return self.t_end - self.t_start


# ---------------------------------------------------------------------------
# Per-drop timelines
# ---------------------------------------------------------------------------


class Timeline:
    """Parallel per-drop timing arrays over one ``CompiledSession``.

    * ``t_start`` / ``t_end`` — float64 ``time.monotonic()`` stamps
      (NaN until the drop reaches a terminal state);
    * ``wave`` — int32 scheduler wave index (-1 = never stamped);
    * ``node`` — int32 id of the node that *executed* the drop — the
      placement node except for speculative straggler duplicates, where
      the winning node is recorded.

    Stamping is two-speed.  ``stamp_batch`` — the call the vectorized
    fast paths make once per wave batch — only appends ``(ids, t0, t1,
    wave)`` to a pending list: O(1) per *batch*, so the execute hot
    path pays a dozen list appends per million drops instead of
    million-element scatters (the scatters also trash the LLC mid-run,
    which taxes the scheduler's own ``ufunc.at`` passes — measured,
    that pushed instrumented overhead past 10%; deferral holds it near
    zero, gated by ``telemetry_overhead_pct`` in the bench).  The
    scatters replay once, lazily, on first array access via the
    ``t_start``/``t_end``/``wave`` properties.  Callers hand over the
    ``ids`` array (always a fresh fancy-index subset in the scheduler)
    and must not mutate it afterwards.

    The arrays themselves allocate *lazily*, at the first scalar stamp
    or read — not when telemetry is enabled.  Filling ~24 bytes/drop of
    fresh pages right before execute wipes the LLC that holds the warm
    template CSR arrays, which measured ~4% on the 1M execute wall all
    by itself; a purely fast-path run now allocates nothing until
    someone actually reads the timeline.

    ``node`` is pre-filled with the placement at allocation — the batch
    fast paths always execute on the placement node, so only scalar
    stamps ever rewrite an entry (speculative winner on a different
    node).  ``stamp`` — used where the dispatcher lands a registry app
    (``_Dispatch._land``) — writes through immediately: real apps
    are micro-seconds-plus each, and their true per-drop timings must
    not be clobbered by a later batch replay.  Scalar and batch stamps
    always target distinct indices (one writer per drop), so replay
    order does not matter; batch stamps come from the single scheduler
    thread, and only the allocation itself is locked (scalar stamps
    race in from pool workers).

    Where the time between apps goes:

    * ``t_ready`` — when the wave handed a registry app to dispatch
      (``_Dispatch._run_apps``), stamped like ``stamp_batch``:
      one deferred ``(ids, t)`` per wave, its own array allocated on
      first read.  ``t_start - t_ready`` is the time a runnable app
      waited for a worker (behind its node's batch or a full pool);
    * :meth:`causes` — the producer app whose ``t_end`` completed an
      app's last input, from the in-CSR at read time;
      ``t_ready - t_end(cause)`` is the time the app waited at the wave
      barrier after its own inputs were done;
    * ``exec_spans`` — ``(t0, t1)`` of each ``execute_frontier`` call
      (a resume adds one), bracketed by ``engine.anchor`` instants on a
      running ``jax.profiler`` trace (:func:`emit_anchor`); each scalar
      ``stamp`` adds one more, so a session's anchors cover its apps.
    """

    __slots__ = ("pgt", "session_id", "_t_start", "_t_end", "_wave",
                 "_node", "_t_ready", "epoch", "max_wave", "_pending",
                 "_pending_ready", "_alloc_lock", "chunks", "exec_spans")

    def __init__(self, session: Any) -> None:
        self.pgt = session.pgt
        self.session_id = session.session_id
        self._t_start: Optional[np.ndarray] = None
        self._t_end: Optional[np.ndarray] = None
        self._wave: Optional[np.ndarray] = None
        self._node: Optional[np.ndarray] = None
        self._t_ready: Optional[np.ndarray] = None
        self.epoch = time.monotonic()     # export timebase reference
        self.max_wave = -1                # resume continues from here
        self._pending: List[tuple] = []   # deferred batch stamps
        self._pending_ready: List[tuple] = []   # deferred (ids, t_ready)
        # (t0, t1) per execute_frontier call; t1 is NaN while it runs
        self.exec_spans: List[Tuple[float, float]] = []
        self._alloc_lock = threading.Lock()
        # streaming chunk spans: (consumer idx, seq, t0, t1) per chunk
        # processed by the compiled lane.  A plain list — chunks are
        # application-granular, and appends under the GIL are atomic
        # enough for the multi-threaded consumer lanes.
        self.chunks: List[tuple] = []

    def _ensure(self) -> None:
        """Allocate the stamp arrays on first use.  Double-checked on
        ``_wave``, which is published last — an unlocked reader that
        sees it non-None sees fully initialized arrays (GIL-ordered)."""
        if self._wave is None:
            with self._alloc_lock:
                if self._wave is None:
                    n = self.pgt.num_drops
                    self._t_start = np.full(n, np.nan, dtype=np.float64)
                    self._t_end = np.full(n, np.nan, dtype=np.float64)
                    self._node = self.pgt.node_ids.astype(np.int32,
                                                          copy=True)
                    self._wave = np.full(n, -1, dtype=np.int32)

    @property
    def t_start(self) -> np.ndarray:
        self._replay()
        return self._t_start

    @property
    def t_end(self) -> np.ndarray:
        self._replay()
        return self._t_end

    @property
    def wave(self) -> np.ndarray:
        self._replay()
        return self._wave

    @property
    def node(self) -> np.ndarray:
        self._ensure()
        return self._node

    def _replay(self) -> None:
        """Materialize deferred batch stamps into the arrays (three 1-D
        scalar-broadcast scatters per batch — NumPy's fastest scatter
        path; a 2-D ``(n, 2)`` row scatter or a structured-dtype
        scatter both measure 3-5x slower)."""
        self._ensure()
        if not self._pending:
            return
        pending, self._pending = self._pending, []
        for ids, t0, t1, wave in pending:
            self._t_start[ids] = t0
            self._t_end[ids] = t1
            self._wave[ids] = wave

    def stamp_batch(self, ids: np.ndarray, t0: float, t1: float,
                    wave: int) -> None:
        """Deferred stamp for one wave's fast-path batch (O(1); the
        caller must not mutate ``ids`` afterwards)."""
        self._pending.append((ids, t0, t1, wave))
        if wave > self.max_wave:
            self.max_wave = wave

    def stamp(self, i: int, t0: float, t1: float, wave: int,
              node: Optional[int] = None) -> None:
        """Immediate scalar stamp for one registry-app execution (and an
        ``engine.anchor``, so that the anchors of a trace span its apps,
        not only the edges of the execute calls)."""
        emit_anchor(session=self.session_id, at="app")
        self._ensure()
        self._t_start[i] = t0
        self._t_end[i] = t1
        self._wave[i] = wave
        if node is not None:
            self._node[i] = node
        if wave > self.max_wave:
            self.max_wave = wave

    def stamp_ready(self, ids: np.ndarray, t: float) -> None:
        """Deferred ready stamp for one wave's registry apps (O(1); the
        caller must not mutate ``ids`` afterwards)."""
        self._pending_ready.append((ids, t))

    @property
    def t_ready(self) -> np.ndarray:
        """When each registry app was handed to dispatch (NaN for every
        other drop)."""
        if self._t_ready is None:
            self._t_ready = np.full(self.pgt.num_drops, np.nan,
                                    dtype=np.float64)
        pending, self._pending_ready = self._pending_ready, []
        for ids, t in pending:
            self._t_ready[ids] = t
        return self._t_ready

    def begin_execute(self) -> float:
        """Open this session's span of one ``execute_frontier`` call."""
        emit_anchor(session=self.session_id, at="enter")
        t0 = time.monotonic()
        self.exec_spans.append((t0, float("nan")))
        return t0

    def end_execute(self, t0: float) -> None:
        """Close the span ``begin_execute`` opened at ``t0``."""
        self.exec_spans[-1] = (t0, time.monotonic())
        emit_anchor(session=self.session_id, at="exit")

    def causes(self) -> Tuple[np.ndarray, np.ndarray]:
        """What activated each readied app: ``(cause, cause_end)``.

        ``cause[i]`` is the app whose ``t_end`` completed app ``i``'s
        last input: the latest-ending producer of its input data drops
        (or an app wired to it directly).  For an app no other app feeds
        (a source app) it is -1, and ``cause_end[i]`` is the start of
        the execute span that readied it; otherwise ``cause_end[i]`` is
        ``t_end[cause[i]]``.  Drops never readied read -1 and NaN."""
        t_ready, t_end = self.t_ready, self.t_end
        n = self.pgt.num_drops
        cause = np.full(n, -1, dtype=np.int64)
        cause_end = np.full(n, np.nan, dtype=np.float64)
        ids = np.flatnonzero(~np.isnan(t_ready))
        if ids.size == 0:
            return cause, cause_end
        indptr, cols = self.pgt.in_csr()
        ins, cnt = csr_gather_with_counts(indptr, cols, ids)
        dst = np.repeat(ids, cnt)
        data = self.pgt.kind_arr[ins] == KIND_DATA
        up, cnt2 = csr_gather_with_counts(indptr, cols, ins[data])
        prod = np.concatenate((ins[~data], up))
        cons = np.concatenate((dst[~data], np.repeat(dst[data], cnt2)))
        if cons.size:
            end = t_end[prod]
            order = np.lexsort((np.where(np.isnan(end), -np.inf, end),
                                cons))
            last = order[np.r_[np.flatnonzero(np.diff(cons[order])),
                               cons.size - 1]]
            cause[cons[last]] = prod[last]
            cause_end[cons[last]] = end[last]
        src = ids[cause[ids] < 0]
        if src.size and self.exec_spans:
            starts = np.array([s for s, _ in self.exec_spans])
            k = np.searchsorted(starts, t_ready[src], side="right") - 1
            cause_end[src] = starts[np.maximum(k, 0)]
        return cause, cause_end

    def stamp_chunk(self, i: int, seq: int, t0: float, t1: float) -> None:
        """Record one processed stream chunk (consumer ``i``, chunk
        ``seq``).  Called from lane consumer threads."""
        self.chunks.append((int(i), int(seq), t0, t1))

    def chunk_spans(self) -> np.ndarray:
        """Chunk spans as a float64 array of rows (idx, seq, t0, t1) —
        what the streaming bench computes overlap fractions from."""
        if not self.chunks:
            return np.empty((0, 4), dtype=np.float64)
        return np.asarray(self.chunks, dtype=np.float64)

    def stamped(self) -> np.ndarray:
        """Ids of drops that have been stamped (wave >= 0)."""
        return np.flatnonzero(self.wave >= 0)


# ---------------------------------------------------------------------------
# Clock anchors: Timeline stamps on a jax.profiler trace's clock
# ---------------------------------------------------------------------------

ANCHOR = "engine.anchor"


def emit_anchor(**args: Any) -> None:
    """Put one ``engine.anchor`` instant, carrying ``mono_ns`` (this
    process's ``time.monotonic_ns()``) and ``args``, on a running
    ``jax.profiler`` trace.  Does nothing in a process that has not
    imported JAX: the engine never loads it."""
    mono_ns = time.monotonic_ns()
    if "jax" not in sys.modules:
        return
    from jax.profiler import TraceAnnotation
    with TraceAnnotation(ANCHOR, mono_ns=mono_ns, **args):
        pass


def read_anchors(xspace: Union[str, Path]) -> List[Tuple[int, float]]:
    """``(mono_ns, trace_ns)`` of every ``engine.anchor`` in a saved
    profile (the ``*.xplane.pb`` that ``jax.profiler`` writes), in trace
    order; ``trace_ns`` is on the clock of the profile's other events."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(str(xspace)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name == ANCHOR:
                    stats = {k: v for k, v in e.stats}
                    out.append((int(stats["mono_ns"]), float(e.start_ns)))
    return sorted(out, key=lambda a: a[1])


@dataclass(frozen=True)
class ClockFit:
    """A line from monotonic time to a trace's nanoseconds:
    ``trace_ns = trace0_ns + offset_ns + slope * (mono_ns - mono0_ns)``.
    ``residual_ns`` is the largest distance of an anchor from it."""

    mono0_ns: int
    trace0_ns: float
    slope: float
    offset_ns: float
    residual_ns: float

    def to_trace_ns(self, t: Union[float, np.ndarray]
                    ) -> Union[float, np.ndarray]:
        """A ``Timeline`` stamp (monotonic seconds) on the trace's clock."""
        return self.trace0_ns + self.offset_ns + self.slope * (
            np.asarray(t, dtype=np.float64) * 1e9 - self.mono0_ns)


def fit_clock(anchors: Sequence[Tuple[int, float]]) -> ClockFit:
    """Least-squares line through ``(mono_ns, trace_ns)`` anchor pairs
    (two or more, at distinct instants), drift included.  Both clocks
    are taken relative to the first anchor, so float64 keeps them to the
    nanosecond."""
    if len(anchors) < 2:
        raise ValueError(f"a clock fit needs two anchors, got {len(anchors)}")
    mono0, trace0 = int(anchors[0][0]), float(anchors[0][1])
    x = np.array([int(m) - mono0 for m, _ in anchors], dtype=np.float64)
    y = np.array([t for _, t in anchors], dtype=np.float64) - trace0
    if np.ptp(x) == 0:
        raise ValueError("the anchors share one instant")
    slope, offset = np.polyfit(x, y, 1)
    resid = float(np.abs(offset + slope * x - y).max())
    return ClockFit(mono0, trace0, float(slope), float(offset), resid)


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------


class Counter:
    """Monotonic counter.  ``inc`` takes one uncontended lock — callers
    sit at wave/session granularity, never per-drop."""

    __slots__ = ("name", "_value", "_lock")

    def __init__(self, name: str) -> None:
        self.name = name
        self._value = 0
        self._lock = threading.Lock()

    def inc(self, n: Union[int, float] = 1) -> None:
        with self._lock:
            self._value += n

    @property
    def value(self) -> Union[int, float]:
        return self._value


class Gauge:
    """Instantaneous value (queue depth, open sessions)."""

    __slots__ = ("name", "_value", "_lock")

    def __init__(self, name: str) -> None:
        self.name = name
        self._value = 0.0
        self._lock = threading.Lock()

    def set(self, v: float) -> None:
        with self._lock:
            self._value = v

    def inc(self, n: float = 1.0) -> None:
        with self._lock:
            self._value += n

    def dec(self, n: float = 1.0) -> None:
        with self._lock:
            self._value -= n

    def set_max(self, v: float) -> None:
        """Raise the value to ``v`` if ``v`` is larger (a running max)."""
        with self._lock:
            self._value = max(self._value, v)

    @property
    def value(self) -> float:
        return self._value


class Histogram:
    """Fixed-bucket histogram: ``uppers[i]`` is the inclusive upper
    bound of bucket ``i``; one extra overflow slot catches the rest.
    Counts live in one int64 array — ``observe_many`` bins a whole
    value array with ``searchsorted`` + ``bincount``."""

    __slots__ = ("name", "uppers", "counts", "count", "sum", "_lock")

    def __init__(self, name: str,
                 buckets: Sequence[float] = LATENCY_BUCKETS_S) -> None:
        self.name = name
        self.uppers = np.asarray(sorted(buckets), dtype=np.float64)
        if self.uppers.size == 0:
            raise ValueError("histogram needs at least one bucket")
        self.counts = np.zeros(self.uppers.size + 1, dtype=np.int64)
        self.count = 0
        self.sum = 0.0
        self._lock = threading.Lock()

    def observe(self, value: float) -> None:
        i = int(np.searchsorted(self.uppers, value, side="left"))
        with self._lock:
            self.counts[i] += 1
            self.count += 1
            self.sum += value

    def observe_many(self, values: np.ndarray) -> None:
        values = np.asarray(values, dtype=np.float64)
        idx = np.searchsorted(self.uppers, values, side="left")
        binned = np.bincount(idx, minlength=self.counts.size)
        with self._lock:
            self.counts += binned
            self.count += int(values.size)
            self.sum += float(values.sum())

    def percentile(self, q: float) -> float:
        """Upper bound of the bucket holding the ``q``-quantile
        observation (conservative — bucket resolution)."""
        with self._lock:
            total = self.count
            if total == 0:
                return 0.0
            target = q * total
            cum = np.cumsum(self.counts)
        i = int(np.searchsorted(cum, target, side="left"))
        if i >= self.uppers.size:
            return float("inf")
        return float(self.uppers[i])

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "buckets": [float(u) for u in self.uppers],
                "counts": [int(c) for c in self.counts],
                "count": int(self.count),
                "sum": float(self.sum),
            }


class MetricsRegistry:
    """Get-or-create registry of named metrics.

    Creation takes the registry lock once; afterwards callers hold the
    metric object and update it directly (each metric has its own tiny
    lock), so N concurrent manager sessions never serialize on the
    registry itself.  ``snapshot()`` returns plain JSON-serialisable
    Python values — what ``launch/serve.py --stats-json`` dumps.
    """

    def __init__(self) -> None:
        self._metrics: Dict[str, Any] = {}
        self._lock = threading.Lock()

    def _get(self, name: str, cls, *args):
        m = self._metrics.get(name)
        if m is None:
            with self._lock:
                m = self._metrics.get(name)
                if m is None:
                    m = cls(name, *args)
                    self._metrics[name] = m
        if not isinstance(m, cls):
            raise ValueError(
                f"metric {name!r} already registered as "
                f"{type(m).__name__}, not {cls.__name__}")
        return m

    def counter(self, name: str) -> Counter:
        return self._get(name, Counter)

    def gauge(self, name: str) -> Gauge:
        return self._get(name, Gauge)

    def histogram(self, name: str,
                  buckets: Sequence[float] = LATENCY_BUCKETS_S
                  ) -> Histogram:
        return self._get(name, Histogram, buckets)

    def snapshot(self) -> Dict[str, Dict[str, Any]]:
        with self._lock:
            metrics = list(self._metrics.values())
        out: Dict[str, Dict[str, Any]] = {
            "counters": {}, "gauges": {}, "histograms": {}}
        for m in metrics:
            if isinstance(m, Counter):
                v = m.value
                out["counters"][m.name] = \
                    int(v) if isinstance(v, (int, np.integer)) else float(v)
            elif isinstance(m, Gauge):
                out["gauges"][m.name] = float(m.value)
            elif isinstance(m, Histogram):
                out["histograms"][m.name] = m.snapshot()
        return out


# ---------------------------------------------------------------------------
# Perfetto / chrome-tracing export
# ---------------------------------------------------------------------------

_TID_PIPELINE = 1      # span track
_TID_NODE0 = 2         # node tracks start here (tid = node_id + 2)


def export_chrome_trace(session: Any, path: Union[str, Path], *,
                        spans: Optional[Sequence[Span]] = None,
                        batch_threshold: int = 64) -> Dict[str, int]:
    """Write one session's timeline as chrome-tracing JSON for Perfetto.

    Track layout: one process per session, one thread track per cluster
    node (thread 1 is the pipeline-span track).  Per-(node, wave) drop
    groups with at most ``batch_threshold`` members get one "X" slice
    per drop (named by uid); larger groups collapse into a single
    aggregated wave slice spanning min ``t_start`` .. max ``t_end`` with
    the drop count in ``args`` — a 100k-drop wave is one slice, not
    100k.  Returns a summary dict (event/slice/track counts).
    """
    tl: Optional[Timeline] = getattr(session, "timeline", None)
    if tl is None:
        raise ValueError(
            "session has no timeline — run it with "
            "TelemetryConfig(timeline=True)")
    pgt = tl.pgt
    ids = tl.stamped()
    events: List[Dict[str, Any]] = []
    pid = 1
    events.append({"ph": "M", "pid": pid, "tid": 0, "name": "process_name",
                   "args": {"name": f"session {session.session_id}"}})
    events.append({"ph": "M", "pid": pid, "tid": _TID_PIPELINE,
                   "name": "thread_name", "args": {"name": "pipeline"}})
    tracks = 1
    for nid, node_name in enumerate(pgt.node_names):
        events.append({"ph": "M", "pid": pid, "tid": _TID_NODE0 + nid,
                       "name": "thread_name", "args": {"name": node_name}})
        tracks += 1
    unplaced_tid = _TID_NODE0 + len(pgt.node_names)

    # common timebase: earliest stamp across drops, chunks and spans
    chunk_rows = tl.chunk_spans()
    bases = []
    if ids.size:
        bases.append(float(np.nanmin(tl.t_start[ids])))
    if chunk_rows.shape[0]:
        bases.append(float(chunk_rows[:, 2].min()))
    for sp in spans or ():
        bases.append(sp.t_start)
    t_base = min(bases) if bases else tl.epoch

    def us(t: float) -> float:
        return round((t - t_base) * 1e6, 3)

    slices = 0
    for sp in spans or ():
        events.append({
            "ph": "X", "pid": pid, "tid": _TID_PIPELINE, "name": sp.name,
            "ts": us(sp.t_start),
            "dur": max(round(sp.duration * 1e6, 3), 0.01)})
        slices += 1

    if ids.size:
        waves = tl.wave[ids]
        nodes = tl.node[ids]
        # pack (node, wave) -> group key; node -1 maps to the last track
        nkey = np.where(nodes >= 0, nodes,
                        len(pgt.node_names)).astype(np.int64)
        key = nkey * (int(waves.max()) + 1) + waves
        order = np.argsort(key, kind="stable")
        bounds = np.flatnonzero(np.diff(key[order])) + 1
        used_unplaced = False
        for grp in np.split(order, bounds):
            g = ids[grp]
            nid = int(nodes[grp[0]])
            wave = int(waves[grp[0]])
            tid = _TID_NODE0 + nid if nid >= 0 else unplaced_tid
            used_unplaced |= nid < 0
            if g.size > batch_threshold:
                t0 = float(np.nanmin(tl.t_start[g]))
                t1 = float(np.nanmax(tl.t_end[g]))
                events.append({
                    "ph": "X", "pid": pid, "tid": tid,
                    "name": f"wave {wave} [{g.size} drops]",
                    "ts": us(t0),
                    "dur": max(round((t1 - t0) * 1e6, 3), 0.01),
                    "args": {"wave": wave, "drops": int(g.size)}})
                slices += 1
            else:
                state = session.drop_state
                from .session import _ST_NAMES
                for i in g.tolist():
                    events.append({
                        "ph": "X", "pid": pid, "tid": tid,
                        "name": pgt.uid_of(i),
                        "ts": us(float(tl.t_start[i])),
                        "dur": max(round(
                            (float(tl.t_end[i])
                             - float(tl.t_start[i])) * 1e6, 3), 0.01),
                        "args": {"wave": wave,
                                 "state": _ST_NAMES[state[i]]}})
                    slices += 1
        if used_unplaced:
            events.append({"ph": "M", "pid": pid, "tid": unplaced_tid,
                           "name": "thread_name",
                           "args": {"name": "unplaced"}})
            tracks += 1

    # streaming chunk spans: one slice per processed chunk on the
    # consumer's node track — this is where producer/consumer overlap
    # becomes visible (chunk slices sitting under a producer's slice)
    node_ids = pgt.node_ids
    for idx, seq, t0, t1 in tl.chunks:
        nid = int(node_ids[idx])
        tid = _TID_NODE0 + nid if nid >= 0 else unplaced_tid
        events.append({
            "ph": "X", "pid": pid, "tid": tid,
            "name": f"{pgt.uid_of(int(idx))} · chunk {int(seq)}",
            "ts": us(float(t0)),
            "dur": max(round((float(t1) - float(t0)) * 1e6, 3), 0.01),
            "args": {"chunk": int(seq)}})
        slices += 1

    path = Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    with open(path, "w") as fh:
        json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, fh)
    return {"events": len(events), "slices": slices, "tracks": tracks,
            "drops_stamped": int(ids.size)}
