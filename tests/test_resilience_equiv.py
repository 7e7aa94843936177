"""Compiled resilience vs the object-path oracle (the PR-3 contract).

``fault.FaultManager`` (object engine) is the semantic oracle for node
failure + lineage recovery; ``resilience.CompiledFaultManager`` must
produce the same final status counts and payload values on identical
failure scripts, across chain / fan-out / fan-in / multi-island
topologies.  Straggler speculation and the dispatch-layer retry policy
are exercised on the compiled path (the object path has its own
``StragglerWatcher`` / ``with_retries`` tests in ``test_system.py``).
"""
import multiprocessing
import os
import signal
import sys
import threading
import time

import numpy as np
import pytest

from repro.core import (AppDrop, AppState, CompiledFaultManager,
                        CompiledSession, DropState, EngineConfig,
                        FailureScript, Pipeline, ResilienceConfig,
                        RetryPolicy, StragglerPolicy, StragglerWatcher,
                        TelemetryConfig, Timeline, execute_frontier,
                        execute_resilient, register_app, with_retries)
from repro.dsl import GraphBuilder


@register_app("rz_double")
def _double(inputs, outputs, app):
    v = sum(i.read() for i in inputs) if inputs else 1
    for o in outputs:
        o.write(v * 2)


@register_app("rz_sum")
def _sum(inputs, outputs, app):
    v = sum(i.read() for i in inputs)
    for o in outputs:
        o.write(v)


# ---------------------------------------------------------------------------
# topologies (nonzero time/volume so the mapper spreads drops over nodes)
# ---------------------------------------------------------------------------


def chain_lg():
    g = GraphBuilder("rz_chain")
    g.data("src")
    g.component("a1", app="rz_double", time=1.0)
    g.data("d1", volume=10)
    g.component("a2", app="rz_double", time=1.0)
    g.data("d2", volume=10)
    g.component("a3", app="rz_double", time=1.0)
    g.data("out")
    g.chain("src", "a1", "d1", "a2", "d2", "a3", "out")
    return g.graph()


def fan_lg(width=6):
    """Fan-out (scatter) then fan-in (gather)."""
    g = GraphBuilder("rz_fan")
    g.data("src", volume=10)
    with g.scatter("sc", width):
        g.component("w", app="rz_double", time=1.0)
        g.data("mid", volume=10)
        g.component("w2", app="rz_double", time=1.0)
        g.data("mid2", volume=10)
    with g.gather("ga", width):
        g.component("r", app="rz_sum", time=1.0)
    g.data("out")
    g.chain("src", "w", "mid", "w2", "mid2", "r", "out")
    return g.graph()


def fanin_lg(k=5):
    """Pure fan-in: k independent sources reduced by one aggregate."""
    g = GraphBuilder("rz_fanin")
    for i in range(k):
        g.data(f"s{i}")
        g.component(f"w{i}", app="rz_double", time=1.0)
        g.data(f"m{i}", volume=10)
        g.chain(f"s{i}", f"w{i}", f"m{i}")
    g.component("agg", app="rz_sum", time=1.0)
    g.data("out")
    for i in range(k):
        g.connect(f"m{i}", "agg")
    g.connect("agg", "out")
    return g.graph()


TOPOLOGIES = [
    ("chain", chain_lg, {"src": 3}, "d1"),
    ("fan", fan_lg, {"src": 3}, "mid#1"),
    ("fanin", fanin_lg, {f"s{i}": i + 1 for i in range(5)}, "m1"),
]


def _object_run_fail_recover(lg, inputs, probe_uid, num_nodes=3,
                             num_islands=1):
    """Oracle: run to completion, kill the node holding ``probe_uid``,
    recover, wait; return (status, states, values)."""
    with Pipeline(num_nodes=num_nodes, num_islands=num_islands,
                  algorithm="none") as p:
        rep = p.run(lg, inputs=dict(inputs))
        assert rep.ok, rep.errors
        dead = p.session.drops[probe_uid].node
        p.fault_manager.fail_node(dead)
        recovered = p.fault_manager.recover()
        assert p.session.wait(10)
        states = {u: d.state for u, d in p.session.drops.items()}
        values = {u: d.read() for u, d in p.session.drops.items()
                  if d.state is DropState.COMPLETED
                  and getattr(d, "payload", None) is not None
                  and d.payload.exists()}
        return p.session.status(), states, values, dead, recovered


def _compiled_run_fail_recover(lg, inputs, probe_uid, num_nodes=3,
                               num_islands=1, dead_node=None):
    """Compiled: same script through CompiledFaultManager."""
    with Pipeline(num_nodes=num_nodes, num_islands=num_islands,
                  algorithm="none", execution="compiled") as p:
        rep = p.run(lg, inputs=dict(inputs))
        assert rep.ok, rep.errors
        s = p.session
        dead = dead_node or \
            s.pgt.node_names[int(s.pgt.node_ids[s.index_of(probe_uid)])]
        fm = p.fault_manager
        assert isinstance(fm, CompiledFaultManager)
        fm.fail_node(dead)
        recovered = fm.recover()
        assert execute_frontier(s, timeout=10)
        uids = [s.pgt.uid_of(i) for i in range(s.num_drops)]
        states = {u: s.state_of(u) for u in uids}
        values = {}
        for u in uids:
            if s.state_of(u) is DropState.COMPLETED:
                try:
                    values[u] = s.read(u)
                except Exception:
                    pass
        return s.status(), states, values, dead, recovered


# ---------------------------------------------------------------------------
# compiled recovery ≡ object oracle
# ---------------------------------------------------------------------------


class TestCompiledRecoveryMatchesOracle:
    @pytest.mark.parametrize("name,factory,inputs,probe",
                             [t for t in TOPOLOGIES],
                             ids=[t[0] for t in TOPOLOGIES])
    def test_post_run_failure_script(self, name, factory, inputs, probe):
        st_o, states_o, val_o, dead_o, rec_o = _object_run_fail_recover(
            factory(), inputs, probe)
        st_c, states_c, val_c, dead_c, rec_c = _compiled_run_fail_recover(
            factory(), inputs, probe, dead_node=dead_o)
        assert st_c == st_o
        assert states_c == states_o
        # oracle values are the superset present after its recovery; every
        # oracle-readable payload must match the compiled table
        for u, v in val_o.items():
            assert val_c.get(u, v) == v, u
        # the probe drop held a volatile memory payload on the dead node:
        # both paths must actually have re-executed lineage
        assert rec_o, "oracle recovered nothing - bad scenario"
        assert rec_c.size > 0, "compiled recovered nothing"

    def test_multi_island(self):
        st_o, states_o, val_o, dead, _ = _object_run_fail_recover(
            fan_lg(4), {"src": 2}, "mid#0", num_nodes=4, num_islands=2)
        st_c, states_c, val_c, _, _ = _compiled_run_fail_recover(
            fan_lg(4), {"src": 2}, "mid#0", num_nodes=4, num_islands=2,
            dead_node=dead)
        assert st_c == st_o
        assert states_c == states_o
        assert val_c["out"] == val_o["out"]

    def test_mid_run_scripted_failure_converges(self):
        """Kill a node at 50% completion mid-run; the resilient loop must
        recover and finish with the oracle's clean-run values."""
        with Pipeline(num_nodes=4, execution="compiled",
                      algorithm="none") as p:
            rep = p.run(fan_lg(), inputs={"src": 3})
            assert rep.ok
            clean = {u: p.session.read(u)
                     for u in ("out",)}
        with Pipeline(num_nodes=4, execution="compiled", algorithm="none",
                      resilience=ResilienceConfig(failures=[
                          FailureScript("node1", at_fraction=0.5)])) as p:
            rep = p.run(fan_lg(), inputs={"src": 3})
            assert rep.ok, rep.errors
            assert rep.recoveries == 1
            assert rep.recovered_drops > 0
            assert p.session.read("out") == clean["out"]
            assert p.session.recoveries == 1

    def test_mid_run_multi_island_failure(self):
        with Pipeline(num_nodes=4, num_islands=2, execution="compiled",
                      algorithm="none",
                      resilience=ResilienceConfig(failures=[
                          FailureScript("node0", at_fraction=0.3),
                          FailureScript("node3", at_fraction=0.6)])) as p:
            rep = p.run(fan_lg(), inputs={"src": 3})
            assert rep.ok, rep.errors
            assert rep.recoveries == 2
            # oracle value for fan_lg(width=6): sum of 6 * (3*2*2) = 72
            assert p.session.read("out") == 72


# ---------------------------------------------------------------------------
# lost-set closure semantics (unit level, manual placement)
# ---------------------------------------------------------------------------


def _manual_compiled(lg, placement, num_nodes=2):
    """Translate + deploy with an explicit drop->node placement."""
    from repro.core import make_cluster, unroll
    pgt = unroll(lg)
    for uid, node in placement.items():
        pgt.drops[uid].node = node
    master, nodes = make_cluster(num_nodes)
    session = CompiledSession("s-manual", pgt)
    master.deploy_compiled(session, pgt)
    return master, session, pgt


class TestLostSetClosure:
    CHAIN = ["src", "a1", "d1", "a2", "d2", "a3", "out"]

    def _chain(self, payload_d1="memory", tmp_path=None):
        g = GraphBuilder("rz_closure")
        g.data("src")
        g.component("a1", app="rz_double")
        g.data("d1", payload=payload_d1)
        g.component("a2", app="rz_double")
        g.data("d2")
        g.component("a3", app="rz_double")
        g.data("out")
        g.chain(*self.CHAIN)
        lg = g.graph()
        return lg

    def test_memory_payload_closure_pulls_producers(self):
        # d1, d2 on node1; everything else node0.  Killing node1 loses the
        # volatile d1/d2 payloads; closure must add their producers a1, a2
        # (re-run) but NOT the durable root src.
        placement = {u: "node0" for u in self.CHAIN}
        placement["d1"] = placement["d2"] = "node1"
        master, s, pgt = _manual_compiled(self._chain(), placement)
        s.write("src", 2)
        assert execute_frontier(s, timeout=10)
        fm = CompiledFaultManager(s, master)
        fm.fail_node("node1")
        lost = set(pgt.uid_of(int(i)) for i in fm.lost_set())
        assert lost == {"a1", "d1", "a2", "d2"}
        fm.recover()
        assert execute_frontier(s, timeout=10)
        assert s.read("out") == 16

    def test_file_payload_is_durable(self, tmp_path):
        # same placement, but d1 is file-backed: it survives node death,
        # so the closure stops there - only d2's lineage re-runs.
        placement = {u: "node0" for u in self.CHAIN}
        placement["d1"] = placement["d2"] = "node1"
        master, s, pgt = _manual_compiled(
            self._chain(payload_d1="file"), placement)
        pgt.drops["d1"].params["path"] = str(tmp_path / "d1.pkl")
        s.write("src", 2)
        assert execute_frontier(s, timeout=10)
        fm = CompiledFaultManager(s, master)
        fm.fail_node("node1")
        lost = set(pgt.uid_of(int(i)) for i in fm.lost_set())
        assert lost == {"a2", "d2"}
        fm.recover()
        assert execute_frontier(s, timeout=10)
        assert s.read("out") == 16

    def test_pending_drops_on_dead_node_remap(self):
        # kill before execution: everything non-terminal on node1 must be
        # remapped onto node0 and still execute to the right values.
        placement = {u: "node0" for u in self.CHAIN}
        placement["a2"] = placement["d2"] = "node1"
        master, s, pgt = _manual_compiled(self._chain(), placement)
        s.write("src", 2)
        fm = CompiledFaultManager(s, master)
        fm.fail_node("node1")
        recovered = fm.recover()
        assert recovered.size > 0
        assert not np.isin(pgt.node_ids,
                           pgt.node_id_for("node1"))[recovered].any()
        assert execute_frontier(s, timeout=10)
        assert s.read("out") == 16

    def test_slices_reregistered_after_recovery(self):
        placement = {u: "node0" for u in self.CHAIN}
        placement["d1"] = "node1"
        master, s, pgt = _manual_compiled(self._chain(), placement)
        s.write("src", 2)
        assert execute_frontier(s, timeout=10)
        fm = CompiledFaultManager(s, master)
        fm.fail_node("node1")
        fm.recover()
        total = sum(len(v) for v in s.node_slices.values())
        assert total == pgt.num_drops
        for node, idx in s.node_slices.items():
            assert (pgt.node_ids[idx] == pgt.node_id_for(node)).all()

    def test_no_live_nodes_raises(self):
        placement = {u: "node0" for u in self.CHAIN}
        master, s, pgt = _manual_compiled(placement=placement,
                                          lg=self._chain(), num_nodes=1)
        fm = CompiledFaultManager(s, master)
        fm.fail_node("node0")
        with pytest.raises(RuntimeError, match="no live nodes"):
            fm.recover()


# ---------------------------------------------------------------------------
# straggler speculation (compiled)
# ---------------------------------------------------------------------------


class TestCompiledStragglers:
    def test_speculative_win_no_corruption(self):
        release = threading.Event()

        @register_app("rz_slow_once")
        def slow_once(inputs, outputs, app):
            # the first executor to run this blocks 10x+ longer than the
            # rest of the wave; the speculative duplicate returns fast
            if not release.is_set():
                release.set()
                time.sleep(1.5)
            for o in outputs:
                o.write(42)

        @register_app("rz_pause")
        def pause(inputs, outputs, app):
            time.sleep(0.03)
            for o in outputs:
                o.write(7)

        g = GraphBuilder("rz_strag")
        g.data("src")
        for i in range(4):
            g.component(f"fast{i}", app="rz_pause", time=1.0)
            g.data(f"df{i}")
            g.chain("src", f"fast{i}", f"df{i}")
        g.component("slow", app="rz_slow_once", time=1.0)
        g.data("slow_out")
        g.chain("src", "slow", "slow_out")
        t0 = time.monotonic()
        with Pipeline(num_nodes=2, execution="compiled", algorithm="none",
                      resilience=ResilienceConfig(
                          stragglers=StragglerPolicy(
                              factor=3.0, min_runtime=0.05,
                              poll=0.01))) as p:
            rep = p.run(g.graph(), timeout=10, inputs={"src": 1})
            wall = time.monotonic() - t0
            assert rep.ok, rep.errors
            assert rep.speculative_wins >= 1
            # first-writer-wins: the committed payloads are intact
            assert p.session.read("slow_out") == 42
            for i in range(4):
                assert p.session.read(f"df{i}") == 7
            assert wall < 1.4, "speculation should beat the straggler"


# ---------------------------------------------------------------------------
# dispatch-layer retry policy (compiled)
# ---------------------------------------------------------------------------


class TestRetryPolicy:
    def test_transient_failure_retried(self):
        calls = {"n": 0}

        @register_app("rz_flaky")
        def flaky(inputs, outputs, app):
            calls["n"] += 1
            if calls["n"] < 3:
                raise RuntimeError("transient")
            for o in outputs:
                o.write("recovered")

        g = GraphBuilder("rz_retry")
        g.data("src")
        g.component("f", app="rz_flaky")
        g.data("out")
        g.chain("src", "f", "out")
        with Pipeline(num_nodes=1, execution="compiled",
                      resilience=ResilienceConfig(
                          retry=RetryPolicy(max_attempts=3))) as p:
            rep = p.run(g.graph(), inputs={"src": 1})
            assert rep.ok, rep.errors
            assert p.session.read("out") == "recovered"
            assert rep.retries == 2
            assert p.session.retries == 2

    def test_exhausted_retries_error(self):
        @register_app("rz_always_fail")
        def always_fail(inputs, outputs, app):
            raise RuntimeError("permanent")

        g = GraphBuilder("rz_retry2")
        g.data("src")
        g.component("f", app="rz_always_fail")
        g.data("out")
        g.chain("src", "f", "out")
        with Pipeline(num_nodes=1, execution="compiled",
                      resilience=ResilienceConfig(
                          retry=RetryPolicy(max_attempts=2))) as p:
            rep = p.run(g.graph(), inputs={"src": 1})
            assert not rep.ok
            assert rep.retries == 1
            assert p.session.state_of("f") is DropState.ERROR

    def test_resilience_requires_compiled(self):
        with pytest.raises(ValueError, match="compiled"):
            Pipeline(execution="objects",
                     resilience=ResilienceConfig())


# ---------------------------------------------------------------------------
# one dispatcher: the landing rule and the fan-out under every policy
# ---------------------------------------------------------------------------


@register_app("rz_raise")
def _raise(inputs, outputs, app):
    for o in outputs:
        o.write("partial")          # dropped with the failed attempt
    raise RuntimeError("intentional")


@register_app("rz_fail_once")
def _fail_once(inputs, outputs, app):
    """Fails the first time it runs, then doubles its input.  The marker
    is a file, so a worker process and a retry in it agree."""
    marker = app.meta["marker"]
    if not os.path.exists(marker):
        open(marker, "w").close()
        raise RuntimeError("transient")
    _double(inputs, outputs, app)


_SEEN: list = []                  # (uid, thread name) per rz_where run
_BARRIER = {"b": None}            # a wave's apps meet here when set
_FAILED: set = set()              # rz_flaky_once apps that failed once


@register_app("rz_where")
def _where(inputs, outputs, app):
    _SEEN.append((app.uid, threading.current_thread().name))
    if _BARRIER["b"] is not None:
        _BARRIER["b"].wait()
    for o in outputs:
        o.write(app.uid)


def landing_lg(marker):
    """Three payload writers, one app that raises, one that fails once."""
    g = GraphBuilder("rz_landing")
    g.data("src")
    for k in range(3):
        g.component(f"w{k}", app="rz_double", time=1.0)
        g.data(f"o{k}", volume=10)
        g.chain("src", f"w{k}", f"o{k}")
    g.component("bad", app="rz_raise", time=1.0)
    g.data("bad_out")
    g.chain("src", "bad", "bad_out")
    g.component("flaky", app="rz_fail_once", time=1.0, marker=marker)
    g.data("flaky_out")
    g.chain("src", "flaky", "flaky_out")
    return g.graph()


@register_app("rz_flaky_once")
def _flaky_once(inputs, outputs, app):
    if app.uid not in _FAILED:
        _FAILED.add(app.uid)
        raise RuntimeError("transient")
    for o in outputs:
        o.write(app.uid)


def wave_lg(n, app="rz_where"):
    g = GraphBuilder("rz_wave")
    g.data("src")
    for k in range(n):
        g.component(f"w{k}", app=app)
        g.data(f"o{k}")
        g.chain("src", f"w{k}", f"o{k}")
    return g.graph()


def _placed_wave(n, nodes, app="rz_where"):
    """``wave_lg(n)`` with app ``w{k}`` and its output on node k % nodes."""
    placement = {"src": "node0"}
    for k in range(n):
        placement[f"w{k}"] = placement[f"o{k}"] = f"node{k % nodes}"
    master, s, pgt = _manual_compiled(wave_lg(n, app), placement,
                                      num_nodes=nodes)
    s.write("src", 1)
    _SEEN.clear()
    return master, s, pgt


POLICIES = {
    "none": None,
    "retry": ResilienceConfig(retry=RetryPolicy(max_attempts=2)),
    # speculation's per-app tasks and poll loop, with no duplicate fired:
    # a duplicate's win would stamp another node
    "stragglers": ResilienceConfig(stragglers=StragglerPolicy(
        factor=1e3, min_runtime=60.0)),
}


class TestOneDispatcher:
    @pytest.mark.parametrize("workers", ["thread", "process"])
    @pytest.mark.parametrize("policy", list(POLICIES))
    def test_one_landing_rule(self, policy, workers, tmp_path, monkeypatch):
        stamps = []
        real = Timeline.stamp

        def counted(tl, i, t0, t1, wave, node=None):
            stamps.append((i, t0, t1, node))
            real(tl, i, t0, t1, wave, node)

        monkeypatch.setattr(Timeline, "stamp", counted)
        cfg = EngineConfig(execution="compiled", num_nodes=2,
                           algorithm="none", workers=workers,
                           resilience=POLICIES[policy],
                           telemetry=TelemetryConfig(timeline=True))
        with Pipeline(cfg) as p:
            rep = p.run(landing_lg(str(tmp_path / "marker")), timeout=60,
                        inputs={"src": 1})
            s, pgt = p.session, p.session.pgt
            retried = policy == "retry"
            # one retry each for the app that raises and the flaky one
            assert rep.retries == (2 if retried else 0)
            done = {f"w{k}" for k in range(3)} | {f"o{k}" for k in range(3)}
            done |= {"src"} | ({"flaky", "flaky_out"} if retried else set())
            for i in range(pgt.num_drops):
                u = pgt.uid_of(i)
                want = DropState.COMPLETED if u in done else DropState.ERROR
                assert s.state_of(u) is want, u
            for k in range(3):
                assert s.read(f"o{k}") == 2
            # a failed attempt's writes never land, whatever the policy
            assert not s.payload_present[s.index_of("bad_out")]
            errs = {r.uid: r.error_info.strip().splitlines()[-1]
                    for r in s.errors()}
            assert errs["bad"] == "RuntimeError: intentional"
            if retried:
                assert s.read("flaky_out") == 2
            else:
                assert errs["flaky"] == "RuntimeError: transient"
                assert not s.payload_present[s.index_of("flaky_out")]
            # one stamp per registry app, on the node that executed it
            apps = sorted(s.index_of(u)
                          for u in ("w0", "w1", "w2", "bad", "flaky"))
            assert sorted(i for i, *_ in stamps) == apps
            for i, t0, t1, node in stamps:
                assert t0 <= t1
                assert node == pgt.node_ids[i]
                assert s.timeline.node[i] == pgt.node_ids[i]

    def test_retry_only_wave_overlaps_two_node_pools(self):
        """A retry policy keeps the plain path's node overlap: the two
        apps meet at a barrier, which a serial wave would break."""
        master, s, _ = _placed_wave(2, nodes=2)
        _BARRIER["b"] = threading.Barrier(2, timeout=5)
        try:
            ok, stats = execute_resilient(
                s, master, ResilienceConfig(retry=RetryPolicy(2)),
                timeout=30)
        finally:
            _BARRIER["b"] = None
            master.shutdown()
        assert ok and stats.retries == 0
        assert set(s.status()) == {"COMPLETED"}
        threads = dict(_SEEN)
        assert threads["w0"].startswith("ndm-node0_")
        assert threads["w1"].startswith("ndm-node1_")

    def test_concurrent_landings_lose_no_count(self):
        """Eight node pools land and retry at once, with the interpreter
        switching threads as often as it can: every retry is counted and
        every payload lands."""
        n = 64
        master, s, _ = _placed_wave(n, nodes=8, app="rz_flaky_once")
        _FAILED.clear()
        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:
            ok, stats = execute_resilient(
                s, master, ResilienceConfig(retry=RetryPolicy(2)),
                timeout=60)
        finally:
            sys.setswitchinterval(old)
            master.shutdown()
        assert ok
        assert stats.retries == s.retries == n
        assert set(s.status()) == {"COMPLETED"}
        for k in range(n):
            assert s.read(f"o{k}") == f"w{k}"

    @pytest.mark.parametrize("nodes", [1, 2])
    def test_serving_fan_out(self, nodes):
        """The serving benchmark's case: a wave whose registry apps all
        sit on one thread-backed node runs inline in the thread that
        called ``execute_frontier``, in node-sorted order.  A wave over
        two nodes runs on their two pools."""
        master, s, pgt = _placed_wave(4, nodes)
        try:
            assert execute_frontier(s, timeout=30,
                                    executors=master.node_executors())
        finally:
            master.shutdown()
        assert set(s.status()) == {"COMPLETED"}
        uids = [f"w{k}" for k in range(4)]
        threads = dict(_SEEN)
        if nodes == 1:
            assert [u for u, _ in _SEEN] == sorted(uids, key=pgt.index_of)
            assert set(threads.values()) == {threading.current_thread().name}
        else:
            for k, u in enumerate(uids):
                assert threads[u].startswith(f"ndm-node{k % 2}_"), threads


# ---------------------------------------------------------------------------
# real-process SIGKILL mid-wave (workers="process" recovery tier)
# ---------------------------------------------------------------------------


@register_app("rz_kill_node0")
def _kill_node0(inputs, outputs, app):
    """Doubles its input — except the first time it runs inside node0's
    *worker process*, where it SIGKILLs itself mid-wave.  The gate makes
    the same graph fault-free on the object engine (no worker processes)
    and after recovery (the drop migrates off node0)."""
    if (multiprocessing.parent_process() is not None
            and getattr(app, "node", None) == "node0"):
        os.kill(os.getpid(), signal.SIGKILL)
    v = sum(i.read() for i in inputs) if inputs else 1
    for o in outputs:
        o.write(v * 2)


def kill_lg(width=6):
    g = GraphBuilder("rz_kill")
    g.data("src", volume=10)
    with g.scatter("sc", width):
        g.component("w", app="rz_kill_node0", time=1.0)
        g.data("mid", volume=10)
        g.component("w2", app="rz_kill_node0", time=1.0)
        g.data("mid2", volume=10)
    with g.gather("ga", width):
        g.component("r", app="rz_sum", time=1.0)
    g.data("out")
    g.chain("src", "w", "mid", "w2", "mid2", "r", "out")
    return g.graph()


class TestProcessSIGKILLRecovery:
    """A worker process dying of a real SIGKILL must recover through the
    same lineage machinery as scripted node failures, with final values
    equal to the fault-free object-engine oracle."""

    def test_sigkill_mid_wave_matches_fault_free_oracle(self):
        with Pipeline(num_nodes=2, algorithm="none") as p:
            rep = p.run(kill_lg(), inputs={"src": 3})
            assert rep.ok, rep.errors
            oracle = {u: d.read() for u, d in p.session.drops.items()
                      if d.state is DropState.COMPLETED
                      and getattr(d, "payload", None) is not None
                      and d.payload.exists()}
            status_o = p.session.status()
        with Pipeline(num_nodes=2, algorithm="none", execution="compiled",
                      workers="process",
                      resilience=ResilienceConfig()) as p:
            rep = p.run(kill_lg(), timeout=120, inputs={"src": 3})
            assert rep.ok, rep.errors
            assert rep.recoveries >= 1, "SIGKILL never triggered recovery"
            assert rep.recovered_drops > 0
            assert "node0" in p.fault_manager.stats.failed_nodes
            s = p.session
            assert s.status() == status_o
            for u, v in oracle.items():
                assert s.read(u) == v, u


# ---------------------------------------------------------------------------
# satellite regressions in core.fault (object path)
# ---------------------------------------------------------------------------


class TestFaultSatellites:
    def test_with_retries_no_terminal_sleep(self):
        """The backoff sleep after the FINAL failed attempt was pure
        added latency before the re-raise."""
        def boom(inputs, outputs, app):
            raise RuntimeError("nope")

        class FakeApp:
            meta: dict = {}
        wrapped = with_retries(boom, max_attempts=2, backoff=0.2)
        t0 = time.monotonic()
        with pytest.raises(RuntimeError):
            wrapped([], [], FakeApp())
        elapsed = time.monotonic() - t0
        # one inter-attempt sleep (0.2s); the old terminal sleep added
        # another 0.4s (0.2 * 2^1) before raising
        assert elapsed < 0.45, elapsed

    def test_straggler_picks_least_loaded_round_robin(self):
        """_speculate targeted nms[0] unconditionally; it must prefer the
        least-loaded live node and rotate through ties."""
        g = GraphBuilder("rz_pick")
        g.data("src")
        g.component("a", app="rz_double", time=1.0)
        g.data("out")
        g.chain("src", "a", "out")
        with Pipeline(num_nodes=4, algorithm="none") as p:
            rep = p.run(g.graph(), inputs={"src": 1})
            assert rep.ok
            watcher = StragglerWatcher(p.session, p.master)
            nms = [nm for nm in p.master.node_managers().values()]
            # load up one node with a fake RUNNING app
            busy = nms[0].name
            app = p.session.drops["a"]
            assert isinstance(app, AppDrop)
            app.exec_state = AppState.RUNNING
            app.node = busy
            picks = {watcher._pick_target(nms).name for _ in range(6)}
            assert busy not in picks          # least-loaded wins
            assert len(picks) >= 2            # ties rotate round-robin
            watcher.stop()


# ---------------------------------------------------------------------------
# hypothesis: random failure scripts converge on both engines
# ---------------------------------------------------------------------------


def _layered_lg(width, depth, payload, tmpdir):
    g = GraphBuilder("rz_rand")
    g.data("src")
    with g.scatter("sc", width):
        for i in range(depth):
            g.component(f"w{i}", app="rz_double", time=1.0)
            g.data(f"d{i}", volume=10)
    with g.gather("ga", width):
        g.component("r", app="rz_sum", time=1.0)
    # a payload-kind probe OUTSIDE the scatter (file paths are per-uid)
    g.data("gmid", payload=payload,
           **({"path": f"{tmpdir}/gmid.pkl"} if payload == "file" else {}))
    g.component("tail", app="rz_double", time=1.0)
    g.data("out")
    names = ["src"] + [n for i in range(depth) for n in (f"w{i}", f"d{i}")]
    names += ["r", "gmid", "tail", "out"]
    g.chain(*names)
    return g.graph()


def _check_failure_script_equivalence(width, depth, payload, dead_idx,
                                      tmpdir, num_nodes=3):
    lg_o = _layered_lg(width, depth, payload, f"{tmpdir}/o")
    lg_c = _layered_lg(width, depth, payload, f"{tmpdir}/c")
    dead = f"node{dead_idx % num_nodes}"

    with Pipeline(num_nodes=num_nodes, algorithm="none") as p:
        rep = p.run(lg_o, inputs={"src": 1})
        assert rep.ok, rep.errors
        clean = p.session.drops["out"].read()
        p.fault_manager.fail_node(dead)
        p.fault_manager.recover()
        assert p.session.wait(10)
        assert p.session.drops["out"].read() == clean
        status_o = p.session.status()

    with Pipeline(num_nodes=num_nodes, algorithm="none",
                  execution="compiled") as p:
        rep = p.run(lg_c, inputs={"src": 1})
        assert rep.ok, rep.errors
        assert p.session.read("out") == clean
        fm = p.fault_manager
        fm.fail_node(dead)
        fm.recover()
        assert execute_frontier(p.session, timeout=10)
        assert p.session.read("out") == clean
        assert p.session.status() == status_o


def test_failure_script_examples(tmp_path):
    """Deterministic spot-checks (run even without hypothesis)."""
    _check_failure_script_equivalence(3, 2, "memory", 0, str(tmp_path))
    _check_failure_script_equivalence(2, 3, "file", 1, str(tmp_path))


try:
    from hypothesis import given, settings, strategies as st
except ImportError:                                    # pragma: no cover
    pass
else:
    import tempfile

    @settings(max_examples=10, deadline=None)
    @given(width=st.integers(1, 4), depth=st.integers(1, 3),
           payload=st.sampled_from(["memory", "file"]),
           dead_idx=st.integers(0, 2))
    def test_random_failure_scripts_converge(width, depth, payload,
                                             dead_idx):
        with tempfile.TemporaryDirectory() as tmpdir:
            _check_failure_script_equivalence(width, depth, payload,
                                              dead_idx, tmpdir)
