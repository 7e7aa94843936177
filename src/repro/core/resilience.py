"""Resilience for the compiled engine (paper §3.6 + §7, array-native).

``core.fault`` implements node-failure migration, straggler speculation
and bounded retries for the *object* engine — per-drop Python objects,
per-drop recursion.  This module is the same failure model lifted onto
the ``CompiledPGT`` / ``CompiledSession`` state arrays, where the
compiled path's 100x throughput advantage lives:

* **Node failure + lineage recovery** — :class:`CompiledFaultManager`
  computes the lost set (non-terminal drops on dead nodes plus volatile
  COMPLETED memory payloads there) and its upstream closure with
  vectorized reverse-CSR traversals (``pgt.in_csr`` + ``csr_gather``),
  remaps lost drops onto live nodes round-robin, resets state/payload
  rows in bulk and lets ``execute_frontier`` resume mid-wave — the
  scheduler re-derives its readiness counters from the state array.

* **Straggler speculation** — a policy of the compiled dispatcher
  (``exec_compiled.DispatchPolicy``): each app of a wave is its own task
  on its node's pool, and an app slower than ``factor`` x the median
  completed duration is duplicated onto the least-loaded live node
  (:class:`_Speculation` keeps that state).  The first writer lands in
  the dense payload table; the loser's staged writes are dropped — no
  payload corruption, unlike raw double-execution.

* **Bounded retry** — the same dispatcher re-runs a node batch's failed
  apps (exponential backoff, no terminal sleep) instead of the object
  path's per-app ``with_retries`` wrapper.

The object engine remains the semantic oracle: compiled recovery must
produce the same final status counts and payload values as
``fault.FaultManager.recover`` on identical failure scripts
(``tests/test_resilience_equiv.py`` enforces it).
"""
from __future__ import annotations

import statistics
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Set, Tuple

import numpy as np

from .exec_compiled import DispatchPolicy, ExecHooks, execute_frontier
from .managers import MasterDropManager
from .pgt import KIND_DATA, csr_gather
from .procpool import WorkerLost
from .session import (PK_FILE, PK_MEMORY, PK_NULL, ST_COMPLETED, ST_INIT,
                      CompiledSession)

__all__ = [
    "CompiledFaultManager", "FailureScript", "NodeFailureInterrupt",
    "ResilienceConfig", "ResilienceStats", "RetryPolicy",
    "StragglerPolicy", "execute_resilient",
]


# ---------------------------------------------------------------------------
# Policy / configuration
# ---------------------------------------------------------------------------


@dataclass
class RetryPolicy:
    """Bounded re-attempts for registry apps (transient-failure guard)."""
    max_attempts: int = 3
    backoff: float = 0.0           # seconds; exponential: backoff * 2^k


@dataclass
class StragglerPolicy:
    """Speculative duplicate dispatch for slow apps (wave-deadline based).

    An app still uncommitted after ``factor`` x the median completed app
    duration (but at least ``min_runtime`` seconds) is duplicated onto the
    least-loaded live node; first writer wins."""
    factor: float = 3.0
    min_runtime: float = 0.05
    poll: float = 0.01


@dataclass
class FailureScript:
    """Scripted node death: kill ``node`` once the terminal-drop fraction
    reaches ``at_fraction`` (0.0 = before the first wave)."""
    node: str
    at_fraction: float = 0.5


@dataclass
class ResilienceConfig:
    failures: List[FailureScript] = field(default_factory=list)
    stragglers: Optional[StragglerPolicy] = None
    retry: Optional[RetryPolicy] = None


@dataclass
class ResilienceStats:
    recoveries: int = 0
    recovered_drops: int = 0
    speculative_wins: int = 0
    speculative_losses: int = 0
    retries: int = 0
    failed_nodes: List[str] = field(default_factory=list)
    recovery_seconds: float = 0.0      # lost-set closure+remap+reset, total


# ---------------------------------------------------------------------------
# Node failure + array-native lineage recovery
# ---------------------------------------------------------------------------


class CompiledFaultManager:
    """Array-native mirror of :class:`repro.core.fault.FaultManager`.

    Same failure model, no per-drop recursion: the lost set and its
    upstream closure are computed with bulk boolean masks and reverse-CSR
    gathers, so a 100k-drop recovery costs milliseconds (benchmarked by
    ``bench_execute.py --tier recovery``).
    """

    def __init__(self, session: CompiledSession,
                 master: MasterDropManager) -> None:
        self.session = session
        self.master = master
        self.stats = ResilienceStats()
        # one drop-id array per recovery pass (reset + remapped)
        self.recovered: List[np.ndarray] = []
        self._nid_dead: Optional[np.ndarray] = None   # set by lost_set()
        self._root_data: Optional[np.ndarray] = None  # bool cache

    # -- failure injection -------------------------------------------------
    def fail_node(self, node: str) -> None:
        nm = self.master.node_managers()[node]
        nm.fail()
        if node not in self.stats.failed_nodes:
            self.stats.failed_nodes.append(node)

    # -- lost set ----------------------------------------------------------
    def lost_set(self) -> np.ndarray:
        """Drop ids that must be re-executed after node death.

        Mirrors ``FaultManager.recover`` steps 1-3, vectorized:

        1. dead placement mask over ``pgt.node_ids``;
        2. initial lost set = non-terminal (INIT) drops on dead nodes
           plus COMPLETED *memory*-payload data drops there (memory died
           with the node; file payloads survive on shared storage; root
           data drops are pipeline inputs — durable by contract);
        3. upstream closure over the reverse CSR: a lost data drop pulls
           in its COMPLETED producers (they must re-run to regenerate the
           payload); a lost COMPLETED app pulls in every COMPLETED input
           whose payload is no longer readable (not durable).

        Unlike the oracle's per-drop recursion — which also walks and
        "resets" the not-yet-run INIT region upstream of lost drops (a
        no-op reset) — the closure expands only through the COMPLETED
        lineage that genuinely needs recomputation, so its cost scales
        with the recompute set, not the unexecuted graph.  Final states
        and payloads are identical (``tests/test_resilience_equiv.py``).
        """
        s, pgt = self.session, self.session.pgt
        dead_names = self.master.dead_nodes()
        if not dead_names:
            return np.empty(0, dtype=np.int64)
        # node-id lookup table beats np.isin (no sort of node_ids);
        # after the initial dead scan everything below operates on
        # subsets, so the closure scales with the lost set, not with n
        nid_dead = np.zeros(len(pgt.node_names), dtype=bool)
        nid_dead[[pgt.node_id_for(n) for n in dead_names]] = True
        self._nid_dead = nid_dead          # reused by recover()
        state = s.drop_state
        n = pgt.num_drops
        kind = pgt.kind_arr
        pk = s.payload_kind
        present = s.payload_present
        if self._root_data is None:
            self._root_data = (kind == KIND_DATA) & (pgt.in_degrees() == 0)
        root_data = self._root_data
        if s.node_slices:
            # the deploy/recovery-maintained per-node slices ARE the
            # dead placement set — no full-graph scan needed
            parts = [s.node_slices[nm] for nm in dead_names
                     if nm in s.node_slices]
            if not parts:
                return np.empty(0, dtype=np.int64)
            didx = parts[0] if len(parts) == 1 else np.concatenate(parts)
        else:
            didx = np.flatnonzero(nid_dead[pgt.node_ids])
        if didx.size == 0:
            return np.empty(0, dtype=np.int64)
        dst = state[didx]
        dvol = (kind[didx] == KIND_DATA) & (pk[didx] == PK_MEMORY)
        sel = didx[~root_data[didx]
                   & ((dst == ST_INIT) | ((dst == ST_COMPLETED) & dvol))]
        if sel.size == 0:
            return np.empty(0, dtype=np.int64)
        lost = np.zeros(n, dtype=bool)
        lost[sel] = True
        chunks = [sel]

        in_indptr, in_cols = pgt.in_csr()
        frontier = sel
        while frontier.size:
            is_d = kind[frontier] == KIND_DATA
            data_f = frontier[is_d]
            # only COMPLETED apps are reset-with-recompute; INIT apps on
            # dead nodes just migrate (their inputs are either durable,
            # already in the lost set, or will be produced on resume)
            app_f = frontier[~is_d]
            app_f = app_f[state[app_f] == ST_COMPLETED]
            parts = []
            if data_f.size:
                # COMPLETED producers of a lost data drop must re-run
                # (INIT producers simply run on resume)
                preds = csr_gather(in_indptr, in_cols, data_f)
                parts.append(preds[state[preds] == ST_COMPLETED])
            if app_f.size:
                # a re-run app needs every input payload readable: file
                # payloads are durable wherever they were written; memory
                # and null payloads need the value present AND the node
                # alive; root data drops are durable by contract.
                # Evaluated per gathered input - O(|ins|), not O(n).
                ins = csr_gather(in_indptr, in_cols, app_f)
                durable = (pk[ins] == PK_FILE) | (
                    ((pk[ins] == PK_NULL) | present[ins])
                    & ~nid_dead[pgt.node_ids[ins]])
                durable |= root_data[ins]
                parts.append(
                    ins[(state[ins] == ST_COMPLETED) & ~durable])
            if not parts:
                break
            cand = np.concatenate(parts) if len(parts) > 1 else parts[0]
            if cand.size == 0:
                break
            new = np.unique(cand)          # subset sort, no O(n) scan
            new = new[~lost[new]]
            if new.size == 0:
                break
            lost[new] = True
            chunks.append(new)
            frontier = new
        return np.concatenate(chunks) if len(chunks) > 1 else chunks[0]

    # -- recovery ----------------------------------------------------------
    def recover(self) -> np.ndarray:
        """Migrate lost drops onto live nodes and make the session
        resumable.  Returns the recovered drop-id array.

        Bulk operations only: one closure pass, one round-robin remap of
        ``node_ids``, one state/payload reset, one slice re-registration.
        ``execute_frontier`` then resumes mid-wave — its readiness
        counters are re-derived from the state array on entry.
        """
        t0 = time.monotonic()
        if not self.master.dead_nodes():
            return np.empty(0, dtype=np.int64)
        live = sorted(self.master.live_node_managers())
        if not live:
            raise RuntimeError("no live nodes left to migrate onto")
        s, pgt = self.session, self.session.pgt
        lost = self.lost_set()
        if s.stream is not None and lost.size:
            # a lost streaming consumer has irrecoverably consumed part
            # of its ring — pull its source data (and their producers)
            # into the lost set so the stream replays from chunk 0
            lost = s.stream.expand_lost(lost)
        if lost.size:
            # migrate only the lost drops placed on dead nodes; lost
            # lineage already on live nodes (producers pulled in by the
            # closure) re-runs in place — no pointless migration
            moved = lost[self._nid_dead[pgt.node_ids[lost]]]
            live_ids = np.fromiter((pgt.node_id_for(n) for n in live),
                                   dtype=np.int32, count=len(live))
            pgt.node_ids[moved] = live_ids[
                np.arange(moved.size, dtype=np.int64) % live_ids.size]
            s.drop_state[lost] = ST_INIT
            lost_data = lost[pgt.kind_arr[lost] == KIND_DATA]
            s.payloads[lost_data] = None
            s.payload_present[lost_data] = False
            # round-robin strides give each target node its slice directly
            moved_by_node = {live[t]: moved[t::live_ids.size]
                             for t in range(live_ids.size)}
            self.master.refresh_compiled_slices(s, pgt, moved_by_node)
            if s.stream is not None:
                mask = np.zeros(len(pgt), dtype=bool)
                mask[lost] = True
                s.stream.invalidate(mask)
            self.recovered.append(lost)
        s.reopen()
        s.recoveries += 1
        s.recovered_drops += int(lost.size)
        self.stats.recoveries += 1
        self.stats.recovered_drops += int(lost.size)
        self.stats.recovery_seconds += time.monotonic() - t0
        if s.metrics is not None:
            s.metrics.counter("resilience.recoveries").inc()
            s.metrics.counter("resilience.recovered_drops").inc(
                int(lost.size))
        return lost


# ---------------------------------------------------------------------------
# Straggler speculation — the state the dispatcher's fan-out consults
# ---------------------------------------------------------------------------


class _Speculation:
    """What ``_Dispatch`` needs to speculate: the running threshold and
    the least-loaded live node a duplicate goes to, with the per-node
    count of in-flight tasks behind both."""

    def __init__(self, master: MasterDropManager,
                 policy: StragglerPolicy) -> None:
        self.master = master
        self.policy = policy
        self.poll = policy.poll
        self._lock = threading.Lock()
        # bounded window: the straggler threshold tracks recent behaviour
        # and the per-poll median stays O(window), not O(run history)
        self._durations: deque = deque(maxlen=256)
        self._rr = 0                      # round-robin tie-break cursor
        self._inflight: Dict[str, int] = {}

    def start(self, node: str, n: int) -> None:
        with self._lock:
            self._inflight[node] = self._inflight.get(node, 0) + n

    def done(self, node: str, t0: Optional[float] = None) -> None:
        """A task on ``node`` ended; a primary's wall since ``t0`` feeds
        the threshold."""
        with self._lock:
            self._inflight[node] = self._inflight.get(node, 1) - 1
            if t0 is not None:
                self._durations.append(time.monotonic() - t0)

    def threshold(self) -> Optional[float]:
        with self._lock:
            durs = list(self._durations)   # bounded snapshot (maxlen)
        if len(durs) < 3:
            return None
        return max(self.policy.factor * statistics.median(durs),
                   self.policy.min_runtime)

    def target(self, home: str):
        """The least-loaded live node other than ``home`` (round-robin
        among ties), charged one task; None if there is none."""
        live = self.master.live_node_managers()
        cands = [nm for n, nm in sorted(live.items()) if n != home]
        if not cands:
            return None
        with self._lock:
            low = min(self._inflight.get(nm.name, 0) for nm in cands)
            tied = [nm for nm in cands
                    if self._inflight.get(nm.name, 0) == low]
            target = tied[self._rr % len(tied)]
            self._rr += 1
            self._inflight[target.name] = \
                self._inflight.get(target.name, 0) + 1
        return target


# ---------------------------------------------------------------------------
# The resilient execution loop
# ---------------------------------------------------------------------------


class NodeFailureInterrupt(Exception):
    """Control-flow signal: a failure script fired at a wave boundary."""

    def __init__(self, nodes: List[str]) -> None:
        super().__init__(f"node failure injected: {nodes}")
        self.nodes = nodes


def execute_resilient(session: CompiledSession, master: MasterDropManager,
                      config: ResilienceConfig, timeout: float = 60.0,
                      fault_manager: Optional[CompiledFaultManager] = None,
                      hooks: Optional[ExecHooks] = None,
                      stream=None) -> Tuple[bool, ResilienceStats]:
    """Run a deployed compiled session under a resilience policy.

    Drives ``execute_frontier`` with hooks: scripted node failures fire at
    wave boundaries (where every drop is terminal or INIT — no in-flight
    state), recovery resets/remaps the lost lineage, and the loop resumes
    the scheduler until the graph finishes or the deadline expires.

    ``hooks`` merges user observability into the internal failure-script
    hooks: a user ``on_wave`` runs before the failure check, and
    ``on_stream_chunk``/``on_backpressure`` pass straight through.
    ``stream`` forwards to :func:`execute_frontier` unchanged.
    """
    fm = fault_manager or CompiledFaultManager(session, master)
    stats = fm.stats
    # one policy across resumes: its epoch fences work left in flight
    policy = DispatchPolicy(
        retry=config.retry, stats=stats,
        speculation=None if config.stragglers is None
        else _Speculation(master, config.stragglers))
    pending = sorted(config.failures, key=lambda f: f.at_fraction)
    fired: Set[int] = set()
    user_wave = hooks.on_wave if hooks is not None else None

    def on_wave(sess: CompiledSession, completed: int, total: int) -> None:
        if user_wave is not None:
            user_wave(sess, completed, total)
        frac = completed / max(total, 1)
        trig = [f for f in pending
                if id(f) not in fired and frac >= f.at_fraction]
        if trig:
            fired.update(id(f) for f in trig)
            raise NodeFailureInterrupt([f.node for f in trig])

    hooks = ExecHooks(
        on_wave=on_wave if (pending or user_wave is not None) else None,
        on_stream_chunk=hooks.on_stream_chunk if hooks is not None else None,
        on_backpressure=hooks.on_backpressure if hooks is not None else None)
    deadline = time.monotonic() + timeout
    while True:
        budget = deadline - time.monotonic()
        if budget <= 0:
            return False, stats
        try:
            # executors recomputed per resume so freshly-dead nodes drop
            # out of the executor map
            finished = execute_frontier(
                session, timeout=budget, hooks=hooks,
                executors=master.node_executors(), stream=stream,
                policy=policy)
            return finished, stats
        except (NodeFailureInterrupt, WorkerLost) as nf:
            # scripted failure (wave boundary) or a real worker-process
            # death (mid-wave SIGKILL / crash / wedge): same recovery path
            for node in nf.nodes:
                nm = master.node_managers().get(node)
                if nm is not None and nm.info.alive:
                    fm.fail_node(node)
                elif node not in stats.failed_nodes:
                    # worker death already flipped info.alive via on_lost;
                    # keep the failure ledger consistent with fail_node
                    stats.failed_nodes.append(node)
            # invalidate BEFORE the state reset: a leftover thread landing
            # between recover() and a later invalidate() would pass the
            # epoch check against just-reset rows
            policy.invalidate()
            fm.recover()
