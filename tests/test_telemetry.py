"""Observability across the compiled stack (``core/telemetry.py``).

* **timelines** — per-drop ``t_start``/``t_end``/``wave``/``node``
  arrays: stamped for every terminal drop, consistent along edges,
  lazily allocated (off = no arrays at all, on = nothing allocated
  until first read);
* **waits** — ``t_ready``, the activating app and the execute spans:
  the serving graph's worker wait and wave-barrier delay, source apps,
  resumes, and ``engine.anchor`` on a profiler trace's clock;
* **metrics** — the lock-cheap registry: unit semantics, thread
  safety, the scheduler / EngineManager / resilience wiring (incl.
  N temporally-concurrent manager sessions sharing one registry);
* **trace export** — Perfetto/Chrome JSON: valid file, expected
  slice/track counts, wave aggregation above the batch threshold;
* **lifecycle events** — compiled sessions on the EventBus
  (sessionStarted/Finished/Failed, dropFailed with a summary) and the
  final ``on_wave`` report where consumers observe completed == total.
"""
import json
import os
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from repro.core import (EngineManager, AdmissionError, GraphTemplate,
                        MetricsRegistry, Pipeline, ResilienceConfig,
                        RetryPolicy, TelemetryConfig, execute_frontier,
                        export_chrome_trace, make_cluster, register_app)
from repro.core import telemetry
from repro.core.exec_compiled import ExecHooks
from repro.core.telemetry import (ClockFit, Counter, Gauge, Histogram,
                                  fit_clock, read_anchors)
from repro.dsl import GraphBuilder

TEL = TelemetryConfig(timeline=True, metrics=True)

# rendezvous for proving manager sessions are temporally concurrent
# (same idiom as test_serving: a timed-out barrier raises in the app,
# failing the session instead of hanging the test)
_BARRIER = {"b": None}


@register_app("tel_double")
def _double(inputs, outputs, app):
    v = inputs[0].read() if inputs else 1
    for o in outputs:
        o.write(v * 2)


@register_app("tel_slow")
def _slow(inputs, outputs, app):
    time.sleep(0.05)
    for o in outputs:
        o.write("slow")


@register_app("tel_boom")
def _boom(inputs, outputs, app):
    raise RuntimeError("boom for telemetry")


@register_app("tel_barrier")
def _barrier(inputs, outputs, app):
    b = _BARRIER["b"]
    if b is not None:
        b.wait(timeout=10.0)
    for o in outputs:
        o.write(inputs[0].read() if inputs else None)


def chain_lg(name="tel", app="tel_double"):
    g = GraphBuilder(name)
    g.data("src")
    g.component("a", app=app)
    g.data("mid")
    g.component("b", app="noop")
    g.data("out")
    g.chain("src", "a", "mid", "b", "out")
    return g.graph()


def fan_lg(width, name="telfan"):
    g = GraphBuilder(name)
    g.data("src")
    with g.scatter("sc", width):
        g.component("w", app="identity", time=0.0)
        g.data("mid")
    g.chain("src", "w", "mid")
    return g.graph()


# ---------------------------------------------------------------------------
# metrics registry units
# ---------------------------------------------------------------------------


class TestRegistry:
    def test_counter_gauge_histogram_semantics(self):
        reg = MetricsRegistry()
        c = reg.counter("c")
        c.inc()
        c.inc(4)
        g = reg.gauge("g")
        g.set(10.0)
        g.inc()
        g.dec(3.0)
        h = reg.histogram("h", (1.0, 10.0))
        for v in (0.5, 5.0, 5.0, 100.0):
            h.observe(v)
        snap = reg.snapshot()
        assert snap["counters"]["c"] == 5
        assert snap["gauges"]["g"] == 8.0
        hs = snap["histograms"]["h"]
        assert hs["count"] == 4
        assert hs["counts"] == [1, 2, 1]      # <=1, <=10, overflow
        assert json.dumps(snap)               # JSON-safe by contract

    def test_same_name_returns_same_instrument(self):
        reg = MetricsRegistry()
        assert reg.counter("x") is reg.counter("x")
        with pytest.raises(ValueError):
            reg.gauge("x")                    # registered as a Counter

    def test_histogram_percentile(self):
        h = Histogram("lat", (0.01, 0.1, 1.0))
        h.observe_many([0.005] * 90)
        h.observe_many([0.5] * 10)
        assert h.percentile(0.5) <= 0.01
        assert h.percentile(0.99) == 1.0

    def test_thread_safety_exact_totals(self):
        reg = MetricsRegistry()
        c = reg.counter("hits")
        h = reg.histogram("obs", (10.0, 100.0))
        n_threads, per = 8, 500

        def work():
            for i in range(per):
                c.inc()
                h.observe(float(i % 200))

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value == n_threads * per
        assert reg.snapshot()["histograms"]["obs"]["count"] == \
            n_threads * per


# ---------------------------------------------------------------------------
# per-drop timelines
# ---------------------------------------------------------------------------


class TestTimeline:
    def test_stamps_cover_all_drops_and_respect_edges(self):
        with Pipeline(num_nodes=2, workers_per_node=2,
                      execution="compiled", telemetry=TEL) as p:
            rep = p.run(chain_lg(), inputs={"src": 21})
            assert rep.ok, rep.errors
            s = p.session
            tl = s.timeline
            n = s.pgt.num_drops
            stamped = tl.stamped()
            assert stamped.size == n
            assert np.all(np.isfinite(tl.t_start[stamped]))
            assert np.all(tl.t_end[stamped] >= tl.t_start[stamped])
            # wave strictly increases along the chain src -> a -> ... -> out
            order = [s.pgt.index_of(nm)
                     for nm in ("src", "a", "mid", "b", "out")]
            waves = tl.wave[order]
            assert np.all(np.diff(waves) > 0), waves
            # fast paths ran on their placement node
            assert np.array_equal(tl.node[stamped],
                                  s.pgt.node_ids[stamped])

    def test_python_app_duration_is_real(self):
        with Pipeline(num_nodes=1, execution="compiled",
                      telemetry=TEL) as p:
            rep = p.run(chain_lg("telslow", app="tel_slow"),
                        inputs={"src": 1})
            assert rep.ok, rep.errors
            tl = p.session.timeline
            i = p.session.pgt.index_of("a")
            assert tl.t_end[i] - tl.t_start[i] >= 0.045

    def test_error_drops_are_stamped(self):
        with Pipeline(num_nodes=1, execution="compiled",
                      telemetry=TEL) as p:
            rep = p.run(chain_lg("telboom", app="tel_boom"),
                        inputs={"src": 1})
            assert not rep.ok
            tl = p.session.timeline
            i = p.session.pgt.index_of("a")
            assert tl.wave[i] >= 0
            assert np.isfinite(tl.t_end[i])

    def test_off_by_default_allocates_nothing(self, monkeypatch):
        anchors = []
        monkeypatch.setattr(telemetry, "emit_anchor",
                            lambda **kw: anchors.append(kw))
        with Pipeline(num_nodes=1, execution="compiled") as p:
            rep = p.run(chain_lg("teloff"), inputs={"src": 1})
            assert rep.ok
            assert p.session.timeline is None
            assert p.session.metrics is None
        with EngineManager(num_nodes=2, workers_per_node=2) as mgr:
            t = mgr.submit(serving_lg("teloffmgr"), inputs={"reqs": 0})
            assert t.result(30).ok
            assert t.session.timeline is None
        assert anchors == []
        # the same run with the timeline on: one anchor per call edge and
        # one per registry app (the noop is a fast path)
        with Pipeline(num_nodes=1, execution="compiled", telemetry=TEL) as p:
            assert p.run(chain_lg("telon"), inputs={"src": 1}).ok
        assert [a["at"] for a in anchors] == ["enter", "app", "exit"]

    def test_arrays_allocate_lazily_on_first_read(self):
        # the fast-path run must not allocate the big arrays (cache
        # pollution is the measured overhead, see bench --telemetry);
        # they materialize on first access
        with Pipeline(num_nodes=1, execution="compiled",
                      telemetry=TEL) as p:
            rep = p.run(fan_lg(32), inputs={"src": 1})
            assert rep.ok
            tl = p.session.timeline
            assert tl._wave is None and tl._pending
            stamped = tl.stamped()              # forces replay
            assert not tl._pending
            assert stamped.size == p.session.pgt.num_drops


# ---------------------------------------------------------------------------
# where the time between apps goes: t_ready, causes, execute spans
# ---------------------------------------------------------------------------

PREFILL_S, DECODE_S = 0.05, 0.2


def _sleeper(secs):
    def app(inputs, outputs, app):
        time.sleep(secs)
        for o in outputs:
            o.write(secs)
    return app


register_app("tel_prefill")(_sleeper(PREFILL_S))
register_app("tel_decode")(_sleeper(DECODE_S))
register_app("tel_assemble")(_sleeper(0.0))


def serving_lg(name="telserve", n_micro=2):
    """The LM serving graph's shape (``reqs`` scattered into microbatches
    of prefill -> decode, gathered by assemble), with sleeping apps."""
    g = GraphBuilder(name)
    g.data("reqs")
    with g.scatter("mb", n_micro):
        g.component("prefill", app="tel_prefill", time=0.5)
        g.data("kv", volume=1e6)
        g.component("decode", app="tel_decode", time=1.0)
        g.data("gen")
    with g.gather("all", n_micro):
        g.component("assemble", app="tel_assemble", time=0.01)
    g.data("responses")
    g.chain("reqs", "prefill", "kv", "decode", "gen")
    g.connect("gen", "assemble")
    g.chain("assemble", "responses")
    return g.graph()


@pytest.fixture(scope="module")
def served():
    """One serving session through the manager, as the benchmark runs it:
    2 nodes x 2 workers, 2 sessions at once, timeline on."""
    with EngineManager(num_nodes=2, workers_per_node=2, max_concurrent=2,
                       telemetry=TelemetryConfig(timeline=True)) as mgr:
        t = mgr.submit(serving_lg(), inputs={"reqs": 0}, timeout=30)
        assert t.result(30).ok
        s = t.session
        tl = s.timeline
        yield s, tl, {s.pgt.uid_of(i): i for i in range(s.pgt.num_drops)}


class TestWaits:
    def test_same_node_microbatch_waits_one_app_wall(self, served):
        s, tl, ix = served
        apps = [ix[f"{g}#{m}"] for g in ("prefill", "decode")
                for m in (0, 1)]
        # the mapper puts the whole session on one node, and a node's
        # batch of a wave runs in one worker, one app after another
        assert len(set(s.pgt.node_ids[apps].tolist())) == 1
        wait = tl.t_start - tl.t_ready
        wall = tl.t_end - tl.t_start
        for g in ("prefill", "decode"):
            first, second = ix[f"{g}#0"], ix[f"{g}#1"]
            assert wait[second] == pytest.approx(wall[first], abs=0.02)
            assert wait[first] < 0.02
        assert wall[ix["decode#0"]] >= DECODE_S

    def test_wave_barrier_shows_as_activation_delay(self, served):
        _, tl, ix = served
        cause, cause_end = tl.causes()
        d0 = ix["decode#0"]
        # decode#0's input was done when prefill#0 ended, but the wave
        # went on until prefill#1 ended
        assert cause[d0] == ix["prefill#0"]
        assert cause_end[d0] == tl.t_end[ix["prefill#0"]]
        delay = tl.t_ready[d0] - cause_end[d0]
        assert delay == pytest.approx(PREFILL_S, abs=0.02)
        assert cause[ix["decode#1"]] == ix["prefill#1"]
        # the gather is activated by the later decode
        assert cause[ix["assemble#0"]] == ix["decode#1"]

    def test_source_app_cause_is_execute_start(self, served):
        _, tl, ix = served
        cause, cause_end = tl.causes()
        (t0, t1), = tl.exec_spans
        for m in (0, 1):
            i = ix[f"prefill#{m}"]
            assert cause[i] == -1 and cause_end[i] == t0
        assert t0 <= np.nanmin(tl.t_ready) and np.nanmax(tl.t_end) <= t1
        # data drops are never readied
        assert np.isnan(tl.t_ready[ix["kv#0"]])
        assert np.isnan(cause_end[ix["kv#0"]])

    def test_resumed_session_keeps_one_span_per_call(self):
        master, nodes = make_cluster(1, 1, 2)
        try:
            tpl = GraphTemplate.build(serving_lg("telresume"), nodes, dop=4)
            s = tpl.materialize("resumed", master=master)
            s.enable_timeline()
            s.write("reqs", 0)

            def stop_at_decode(sess, done, total):
                if done and not calls:
                    calls.append(done)
                    raise RuntimeError("stop")
            calls = []
            hooks = ExecHooks(on_wave=stop_at_decode)
            with pytest.raises(RuntimeError, match="stop"):
                execute_frontier(s, timeout=30, hooks=hooks)
            assert execute_frontier(s, timeout=30, hooks=hooks)
            tl = s.timeline
            assert len(tl.exec_spans) == 2
            (a0, a1), (b0, b1) = tl.exec_spans
            assert a0 < a1 <= b0 < b1
            # an app readied in the second call has its own span's start
            # or a producer as its cause, never the first span's start
            cause, cause_end = tl.causes()
            ready = np.flatnonzero(tl.t_ready >= b0)
            assert ready.size and np.all(cause_end[ready] >= a0)
            src = ready[cause[ready] == -1]
            assert np.all(cause_end[src] == b0)
        finally:
            master.shutdown()

    def test_anchors_put_the_timeline_on_the_trace_clock(self, tmp_path):
        import jax
        jax.profiler.start_trace(str(tmp_path))
        try:
            with EngineManager(num_nodes=2, workers_per_node=2,
                               telemetry=TelemetryConfig(timeline=True)
                               ) as mgr:
                tickets = [mgr.submit(serving_lg("telanchor"),
                                      inputs={"reqs": k}, timeout=30)
                           for k in range(2)]
                assert all(t.result(30).ok for t in tickets)
        finally:
            jax.profiler.stop_trace()
        anchors = read_anchors(next(tmp_path.rglob("*.xplane.pb")))
        # enter, exit and 5 registry apps per session
        assert len(anchors) == 2 * (2 + 5)
        fit = fit_clock(anchors)
        assert abs(fit.slope - 1.0) < 1e-3
        assert fit.residual_ns < 1e6
        for t in tickets:
            (t0, t1), = t.session.timeline.exec_spans
            m0, m1 = fit.to_trace_ns(np.array([t0, t1]))
            assert m0 < m1 and m1 - m0 == pytest.approx((t1 - t0) * 1e9,
                                                         rel=1e-3)

    def test_clock_fit_recovers_offset_and_drift(self):
        mono = [10**15 + k * 10**9 for k in range(5)]
        trace = [123.0 + 1.00002 * (m - mono[0]) for m in mono]
        fit = fit_clock(list(zip(mono, trace)))
        assert isinstance(fit, ClockFit)
        assert fit.slope == pytest.approx(1.00002, rel=1e-9)
        assert fit.residual_ns < 1e-3
        assert fit.to_trace_ns(mono[2] / 1e9) == pytest.approx(trace[2],
                                                               abs=1.0)
        with pytest.raises(ValueError):
            fit_clock(list(zip(mono, trace))[:1])

    def test_importing_core_does_not_load_jax(self):
        src = Path(__file__).resolve().parents[1] / "src"
        env = dict(os.environ, PYTHONPATH=str(src))
        code = "import sys, repro.core; sys.exit('jax' in sys.modules)"
        assert subprocess.run([sys.executable, "-c", code], env=env,
                              timeout=120).returncode == 0


# ---------------------------------------------------------------------------
# scheduler + manager + resilience metrics wiring
# ---------------------------------------------------------------------------


class TestEngineMetrics:
    def test_exec_counters_match_run_shape(self):
        with Pipeline(num_nodes=2, workers_per_node=2,
                      execution="compiled", telemetry=TEL) as p:
            rep = p.run(chain_lg("telm"), inputs={"src": 1})
            assert rep.ok
            snap = p.metrics.snapshot()
            n = p.session.pgt.num_drops
            waves = int(p.session.timeline.max_wave) + 1
            assert snap["counters"]["exec.waves"] == waves
            assert snap["counters"]["exec.drops_completed"] == n
            assert snap["counters"]["exec.drops_errored"] == 0
            assert snap["counters"]["exec.dispatch_batches"] >= 1
            assert snap["histograms"]["exec.frontier_size"]["count"] == \
                waves

    def test_manager_concurrent_sessions_share_registry(self):
        n_sessions = 3
        _BARRIER["b"] = threading.Barrier(n_sessions)
        try:
            with EngineManager(num_nodes=2, workers_per_node=2,
                               max_concurrent=n_sessions,
                               telemetry=TEL) as mgr:
                lg = chain_lg("telconc", app="tel_barrier")
                tickets = [mgr.submit(lg, inputs={"src": k}, timeout=30,
                                      block=True)
                           for k in range(n_sessions)]
                for t in tickets:
                    assert t.result().ok
                for t in tickets:
                    assert t.session.timeline is not None
            # post-close: every done-callback has run
            snap = mgr.metrics.snapshot()
            assert snap["counters"]["manager.submitted"] == n_sessions
            assert snap["counters"]["manager.completed"] == n_sessions
            assert snap["counters"]["manager.failed"] == 0
            assert snap["counters"]["templates.misses"] == 1
            assert snap["counters"]["templates.hits"] == n_sessions - 1
            assert snap["gauges"]["manager.queue_depth"] == 0
            lat = snap["histograms"]["manager.session_latency_s"]
            assert lat["count"] == n_sessions
            # sessions genuinely overlapped: each ran the barrier app, so
            # total exec waves is n_sessions * per-session waves
            assert snap["counters"]["exec.waves"] % n_sessions == 0
        finally:
            _BARRIER["b"] = None

    def test_admission_rejection_counted(self):
        evt = threading.Event()

        @register_app("tel_gated")
        def gated(inputs, outputs, app):
            assert evt.wait(timeout=10.0)
            for o in outputs:
                o.write(None)

        g = GraphBuilder("telrej")
        g.data("src")
        g.component("w", app="tel_gated")
        g.data("out")
        g.chain("src", "w", "out")
        lg = g.graph()
        with EngineManager(num_nodes=1, max_concurrent=1, max_pending=0,
                           telemetry=TEL) as mgr:
            t1 = mgr.submit(lg, inputs={"src": 1}, timeout=30,
                            block=True)
            with pytest.raises(AdmissionError):
                mgr.submit(lg, inputs={"src": 2}, block=False)
            evt.set()
            assert t1.result().ok
            assert mgr.metrics.snapshot()["counters"][
                "manager.rejected"] == 1
        assert mgr.stats()["metrics"]["counters"][
            "manager.submitted"] == 1

    def test_resilience_retry_counter_and_timeline(self):
        calls = {"n": 0}

        @register_app("tel_flaky")
        def flaky(inputs, outputs, app):
            calls["n"] += 1
            if calls["n"] < 3:
                raise RuntimeError("transient")
            for o in outputs:
                o.write("ok")

        g = GraphBuilder("telretry")
        g.data("src")
        g.component("f", app="tel_flaky")
        g.data("out")
        g.chain("src", "f", "out")
        with Pipeline(num_nodes=1, execution="compiled", telemetry=TEL,
                      resilience=ResilienceConfig(
                          retry=RetryPolicy(max_attempts=3))) as p:
            rep = p.run(g.graph(), inputs={"src": 1})
            assert rep.ok, rep.errors
            assert p.metrics.snapshot()["counters"][
                "resilience.retries"] == 2
            tl = p.session.timeline
            i = p.session.pgt.index_of("f")
            assert tl.wave[i] >= 0 and np.isfinite(tl.t_end[i])


# ---------------------------------------------------------------------------
# lifecycle events + hooks
# ---------------------------------------------------------------------------


class TestLifecycle:
    def _collect(self, session):
        events = []
        session.bus.subscribe_all(
            lambda e: events.append((e.type, e.source_uid, e.data)))
        return events

    def test_session_events_on_clean_run(self):
        with Pipeline(num_nodes=1, execution="compiled") as p:
            p.translate(chain_lg("tellife"))
            p.deploy()
            events = self._collect(p.session)
            rep = p.execute(inputs={"src": 1}, timeout=30)
            assert rep.ok
        types = [t for t, _, _ in events]
        assert types[0] == "sessionStarted"
        assert types[-1] == "sessionFinished"
        assert "sessionFailed" not in types

    def test_session_events_on_failed_run(self):
        with Pipeline(num_nodes=1, execution="compiled") as p:
            p.translate(chain_lg("tellifef", app="tel_boom"))
            p.deploy()
            events = self._collect(p.session)
            rep = p.execute(inputs={"src": 1}, timeout=30)
            assert not rep.ok
        fails = [(t, u, d) for t, u, d in events if t == "dropFailed"]
        assert fails and "boom for telemetry" in fails[0][2]["summary"]
        assert events[-1][0] == "sessionFailed"
        assert events[-1][2]["errors"] >= 1

    def test_final_wave_hook_observes_total(self):
        master, nodes = make_cluster(1, 1, 2)
        try:
            tpl = GraphTemplate.build(chain_lg("telhook"), nodes, dop=4)
            s = tpl.materialize("hooked", master=master)
            s.write("src", 1)
            seen = []
            hooks = ExecHooks(
                on_wave=lambda sess, done, total: seen.append(
                    (done, total)))
            assert execute_frontier(s, timeout=30, hooks=hooks,
                                    executors=master.node_executors())
            n = s.pgt.num_drops
            assert seen[0] == (0, n)
            assert seen[-1] == (n, n)       # consumers see completion
            done = [d for d, _ in seen]
            assert done == sorted(done)
        finally:
            master.shutdown()


# ---------------------------------------------------------------------------
# Perfetto trace export
# ---------------------------------------------------------------------------


class TestTraceExport:
    def test_trace_is_valid_and_complete(self, tmp_path):
        path = tmp_path / "trace.json"
        with Pipeline(num_nodes=2, workers_per_node=2,
                      execution="compiled", telemetry=TEL) as p:
            rep = p.run(chain_lg("teltrace"), inputs={"src": 1})
            assert rep.ok
            info = p.export_trace(str(path))
            n = p.session.pgt.num_drops
        doc = json.loads(path.read_text())
        assert doc["displayTimeUnit"] == "ms"
        evs = doc["traceEvents"]
        assert len(evs) == info["events"]
        slices = [e for e in evs if e.get("ph") == "X"]
        # below threshold: one slice per drop, plus the pipeline spans
        span_slices = [e for e in slices if e["tid"] == 1]
        assert {e["name"] for e in span_slices} >= \
            {"translate", "deploy", "execute"}
        assert len(slices) - len(span_slices) == n == \
            info["drops_stamped"]
        for e in slices:
            assert e["dur"] >= 0 and e["ts"] >= 0

    def test_aggregation_above_threshold(self, tmp_path):
        width = 16
        path = tmp_path / "agg.json"
        with Pipeline(num_nodes=2, workers_per_node=2,
                      execution="compiled",
                      telemetry=TelemetryConfig(timeline=True)) as p:
            rep = p.run(fan_lg(width, "telagg"), inputs={"src": 1})
            assert rep.ok
            info = export_chrome_trace(p.session, path,
                                       batch_threshold=1)
        doc = json.loads(path.read_text())
        agg = [e for e in doc["traceEvents"]
               if e.get("ph") == "X" and "drops]" in e["name"]]
        assert agg, "expected aggregated wave slices"
        # aggregation collapses slices below the per-drop count
        assert info["slices"] < info["drops_stamped"]

    def test_export_without_timeline_raises(self, tmp_path):
        with Pipeline(num_nodes=1, execution="compiled") as p:
            rep = p.run(chain_lg("telnotl"), inputs={"src": 1})
            assert rep.ok
            with pytest.raises(ValueError, match="timeline"):
                export_chrome_trace(p.session, tmp_path / "x.json")


# ---------------------------------------------------------------------------
# pipeline spans
# ---------------------------------------------------------------------------


def test_pipeline_spans_recorded_and_optional():
    with Pipeline(num_nodes=1, execution="compiled") as p:
        rep = p.run(chain_lg("telspan"), inputs={"src": 1})
        assert rep.ok
        names = [s.name for s in p.spans]
        assert names == ["translate", "map", "deploy", "execute"]
        assert all(s.duration >= 0 for s in p.spans)
    with Pipeline(num_nodes=1, execution="compiled",
                  telemetry=TelemetryConfig(spans=False)) as p:
        rep = p.run(chain_lg("telspan2"), inputs={"src": 1})
        assert rep.ok
        assert p.spans == []
