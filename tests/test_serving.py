"""Multi-tenant serving: the PR-7 EngineManager contract.

* **templates** — :func:`structural_hash` keys on graph shape + translate
  params + cluster layout; :class:`TemplateCache` serves repeat shapes
  without re-translate/re-map.
* **isolation** — N concurrent :class:`CompiledSession`\\ s of *one*
  template share its ``CompiledPGT`` arrays read-only but never share
  state / payloads / errors; a failing session's report is failed while
  its concurrent neighbour (same template, same node pools) stays clean.
* **admission** — at most ``max_concurrent + max_pending`` in flight;
  beyond that non-blocking :meth:`EngineManager.submit` raises
  :class:`AdmissionError`.
* **lifecycle** — ``close_session`` frees the dense payload table and
  unregisters the session everywhere; finished sessions beyond
  ``keep_finished`` are evicted automatically; ``Pipeline(manager=...)``
  rides the resident cluster and its ``shutdown`` leaves the shared node
  pools alive (only ``EngineManager.close`` kills them).
"""
import threading
import time

import pytest

from repro.core import (AdmissionError, EngineManager, PayloadError,
                        Pipeline, ResilienceConfig, TemplateCache,
                        register_app, structural_hash)
from repro.dsl import GraphBuilder

# ---------------------------------------------------------------------------
# apps + graph shapes
# ---------------------------------------------------------------------------

# rendezvous point for proving two sessions are *temporally* concurrent:
# each test installs a fresh Barrier; a broken/timed-out barrier raises in
# the app, which surfaces as a failed session report (so a scheduling bug
# fails the test instead of hanging it)
_BARRIER = {"b": None}
# gate for holding one session open while admission is probed
_GATE = {"evt": None}


@register_app("srv_passthrough")
def _passthrough(inputs, outputs, app):
    v = inputs[0].read() if inputs else None
    b = _BARRIER["b"]
    if b is not None:
        b.wait(timeout=10.0)
    if v == "boom":
        raise RuntimeError("boom requested")
    for o in outputs:
        o.write(v)


@register_app("srv_gated")
def _gated(inputs, outputs, app):
    evt = _GATE["evt"]
    if evt is not None and not evt.wait(timeout=10.0):
        raise RuntimeError("gate never opened")
    for o in outputs:
        o.write(inputs[0].read() if inputs else None)


@register_app("srv_double")
def _double(inputs, outputs, app):
    v = sum(i.read() for i in inputs) if inputs else 1
    for o in outputs:
        o.write(v * 2)


@register_app("srv_sum")
def _sum(inputs, outputs, app):
    v = sum(i.read() for i in inputs)
    for o in outputs:
        o.write(v)


def simple_lg(name="srv", app="srv_passthrough"):
    g = GraphBuilder(name)
    g.data("in")
    g.component("w", app=app)
    g.data("out")
    g.chain("in", "w", "out")
    return g.graph()


def fan_lg(width=4, name="srvfan"):
    g = GraphBuilder(name)
    g.data("in")
    with g.scatter("sc", width):
        g.component("w", app="srv_double", time=0.0)
        g.data("mid")
    with g.gather("ga", width):
        g.component("r", app="srv_sum", time=0.0)
    g.data("out")
    g.chain("in", "w", "mid", "r", "out")
    return g.graph()


@pytest.fixture
def mgr():
    with EngineManager(num_nodes=2, workers_per_node=2,
                       max_concurrent=2) as m:
        yield m


# ---------------------------------------------------------------------------
# structural hashing + template cache
# ---------------------------------------------------------------------------


def test_structural_hash_keys_on_shape_and_params(mgr):
    base = structural_hash(simple_lg(), dop=8, nodes=mgr.nodes)
    assert structural_hash(simple_lg(), dop=8, nodes=mgr.nodes) == base
    # anything that changes the translated+mapped PGT changes the key
    assert structural_hash(simple_lg(app="srv_gated"), dop=8,
                           nodes=mgr.nodes) != base
    assert structural_hash(simple_lg(), dop=4, nodes=mgr.nodes) != base
    assert structural_hash(simple_lg(), algorithm="none", dop=8,
                           nodes=mgr.nodes) != base
    assert structural_hash(simple_lg(), dop=8, nodes=()) != base
    assert structural_hash(fan_lg(4), dop=8, nodes=mgr.nodes) != \
        structural_hash(fan_lg(5), dop=8, nodes=mgr.nodes)


def test_template_cache_hit_returns_same_object(mgr):
    t1 = mgr.get_template(simple_lg())
    t2 = mgr.get_template(simple_lg())
    assert t1 is t2
    stats = mgr.templates.stats()
    assert stats == {"templates": 1, "hits": 1, "misses": 1,
                     "evictions": 0}
    assert t1.hits == 1


def test_template_cache_lru_eviction():
    with EngineManager(num_nodes=2, workers_per_node=2,
                       max_templates=1) as m:
        m.get_template(simple_lg("shape-a"))
        m.get_template(simple_lg("shape-b"))     # evicts shape-a
        m.get_template(simple_lg("shape-a"))     # cold again
        stats = m.templates.stats()
        assert stats["templates"] == 1
        assert stats["misses"] == 3
        assert stats["evictions"] == 2


def test_template_cache_validates_capacity():
    with pytest.raises(ValueError, match="max_templates"):
        TemplateCache(0)


def test_materialize_without_master_copies_slices(mgr):
    tpl = mgr.get_template(fan_lg())
    s = tpl.materialize("standalone")
    # slices shared by value, not by dict: a session-local mutation must
    # not corrupt the template every other session reads from
    assert s.node_slices == tpl.node_slices
    assert s.node_slices is not tpl.node_slices
    assert s.cross_node_edges == tpl.cross_node_edges
    assert tpl.materializations == 1


# ---------------------------------------------------------------------------
# manager execution ≡ one-shot Pipeline
# ---------------------------------------------------------------------------


def test_manager_run_matches_standalone_pipeline(mgr):
    rep_m = mgr.run(fan_lg(), inputs={"in": 3})
    assert rep_m.ok
    out_m = mgr.get_session(rep_m.session_id).read("out")
    with Pipeline(num_nodes=2, execution="compiled") as p:
        rep_p = p.run(fan_lg(), inputs={"in": 3})
        out_p = p.session.read("out")
    assert rep_p.ok
    assert rep_m.status_counts == rep_p.status_counts
    assert out_m == out_p


# ---------------------------------------------------------------------------
# concurrent-session isolation (the tentpole safety property)
# ---------------------------------------------------------------------------


def test_concurrent_sessions_share_pgt_not_state(mgr):
    lg = simple_lg()
    _BARRIER["b"] = threading.Barrier(2)
    try:
        ta = mgr.submit(lg, inputs={"in": "ok"})
        tb = mgr.submit(lg, inputs={"in": "boom"})
        rep_a, rep_b = ta.result(30), tb.result(30)
    finally:
        _BARRIER["b"] = None
    sa, sb = ta.session, tb.session
    # the barrier proved both executed at the same time on the shared
    # node pools; one template instance backs both
    assert sa.pgt is sb.pgt
    assert tb.template_key == ta.template_key
    # ...yet nothing mutable is shared
    assert sa.drop_state is not sb.drop_state
    assert sa.payloads is not sb.payloads
    assert sa.error_info is not sb.error_info
    # clean session: completed end-to-end, readable output, no errors
    assert rep_a.ok
    assert sa.read("out") == "ok"
    assert not sa.error_info
    # failing session: failed report, error recorded, output never wrote
    assert not rep_b.ok
    assert any(e.startswith("w:") for e in rep_b.errors)
    assert any("boom" in msg for msg in sb.error_info.values())
    with pytest.raises(PayloadError):
        sb.read("out")
    # latency is a client-side quantile input: always stamped post-result
    assert ta.latency is not None and tb.latency is not None


def test_many_sessions_keep_their_own_payloads():
    lg = simple_lg()
    n = 8
    with EngineManager(num_nodes=2, workers_per_node=2, max_concurrent=4,
                       max_pending=n) as m:
        tickets = [m.submit(lg, inputs={"in": f"v{i}"}, block=True)
                   for i in range(n)]
        for i, t in enumerate(tickets):
            assert t.result(30).ok
            assert t.session.read("out") == f"v{i}"
        stats = m.stats()
        assert stats["completed"] == n
        assert stats["failed"] == 0
        assert stats["templates"]["misses"] == 1
        assert stats["templates"]["hits"] == n - 1


def test_scheduler_crash_isolated_to_one_session(mgr, monkeypatch):
    # a dispatch-layer exception (not an app error) must fail only the
    # session it hit, not unwind the manager
    import repro.core.exec_compiled as ec
    real = ec.execute_frontier
    calls = {"n": 0}

    def flaky(session, **kw):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("dispatch blew up")
        return real(session, **kw)

    monkeypatch.setattr(ec, "execute_frontier", flaky)
    rep_bad = mgr.run(simple_lg(), inputs={"in": "x"})
    assert not rep_bad.ok and rep_bad.state == "FAILED"
    assert any("dispatch blew up" in e for e in rep_bad.errors)
    rep_ok = mgr.run(simple_lg(), inputs={"in": "y"})
    assert rep_ok.ok
    assert mgr.stats()["failed"] == 1


# ---------------------------------------------------------------------------
# admission control
# ---------------------------------------------------------------------------


def test_admission_queue_bounds_rejections():
    lg = simple_lg(app="srv_gated")
    _GATE["evt"] = threading.Event()
    try:
        with EngineManager(num_nodes=2, workers_per_node=2,
                           max_concurrent=1, max_pending=0) as m:
            t1 = m.submit(lg, inputs={"in": 1})
            with pytest.raises(AdmissionError, match="admission queue"):
                m.submit(lg, inputs={"in": 2})
            assert m.stats()["rejected"] == 1
            _GATE["evt"].set()
            assert t1.result(30).ok
            # slot release rides the done-callback, which can lag the
            # waiter wake-up by a beat — poll briefly for readmission
            deadline = time.monotonic() + 5.0
            while True:
                try:
                    t3 = m.submit(lg, inputs={"in": 3})
                    break
                except AdmissionError:
                    assert time.monotonic() < deadline, \
                        "slot never released after session finished"
                    time.sleep(0.01)
            assert t3.result(30).ok
    finally:
        _GATE["evt"] = None


def test_submit_after_close_raises():
    m = EngineManager(num_nodes=2, workers_per_node=2)
    m.close()
    with pytest.raises(RuntimeError, match="closed"):
        m.submit(simple_lg())


def test_manager_validates_limits():
    with pytest.raises(ValueError, match="max_concurrent"):
        EngineManager(max_concurrent=0)
    with pytest.raises(ValueError, match="max_pending"):
        EngineManager(max_pending=-1)


def test_latency_counts_the_managers_own_work(monkeypatch):
    """``queue_delay`` and ``latency`` start at the call to ``submit``, so
    a slow ``materialize`` inside it shows in both."""
    from repro.core import GraphTemplate
    slow = 0.2
    materialize = GraphTemplate.materialize

    def slow_materialize(self, *args, **kwargs):
        time.sleep(slow)
        return materialize(self, *args, **kwargs)

    monkeypatch.setattr(GraphTemplate, "materialize", slow_materialize)
    with EngineManager(num_nodes=2, workers_per_node=2) as m:
        t = m.submit(simple_lg("srvslow"), inputs={"in": 1})
        assert t.result(30).ok
        assert t.queue_delay >= slow
        assert t.latency >= t.queue_delay


# ---------------------------------------------------------------------------
# session lifecycle: close + eviction
# ---------------------------------------------------------------------------


def test_close_session_frees_payloads_and_unregisters(mgr):
    rep = mgr.run(simple_lg(), inputs={"in": "keep"})
    sid = rep.session_id
    session = mgr.get_session(sid)
    assert session.read("out") == "keep"
    assert session.payloads.size > 0
    assert mgr.close_session(sid)
    assert session.closed
    assert session.payloads.size == 0          # dense table actually freed
    with pytest.raises(PayloadError, match="closed"):
        session.read("out")
    assert mgr.get_session(sid) is None
    for nm in mgr.master.node_managers().values():
        assert sid not in nm.compiled_sessions
    assert sid not in mgr.master._sessions
    assert mgr.stats()["closed_sessions"] == 1
    assert not mgr.close_session(sid)          # idempotent


def test_finished_sessions_evicted_beyond_keep():
    lg = simple_lg()
    with EngineManager(num_nodes=2, workers_per_node=2,
                       keep_finished=1) as m:
        reps = [m.run(lg, inputs={"in": i}) for i in range(3)]
        assert all(r.ok for r in reps)
        # eviction rides the done-callback; give it a beat
        deadline = time.monotonic() + 5.0
        while m.stats()["closed_sessions"] < 2:
            assert time.monotonic() < deadline, m.stats()
            time.sleep(0.01)
        # oldest two closed, newest still open and readable
        assert m.get_session(reps[0].session_id) is None
        assert m.get_session(reps[1].session_id) is None
        newest = m.get_session(reps[2].session_id)
        assert newest is not None and newest.read("out") == 2


# ---------------------------------------------------------------------------
# Pipeline riding a resident manager
# ---------------------------------------------------------------------------


def test_pipeline_through_manager_hits_cache_and_keeps_pools(mgr):
    with Pipeline(manager=mgr, execution="compiled") as p:
        rep = p.run(simple_lg(), inputs={"in": "a"})
        assert rep.ok and p.session.read("out") == "a"
        assert p.map_time == 0.0               # mapped once, in the template
    with Pipeline(manager=mgr, execution="compiled") as p:
        rep = p.run(simple_lg(), inputs={"in": "b"})
        assert rep.ok and p.session.read("out") == "b"
    assert mgr.templates.stats()["hits"] >= 1
    # Pipeline.shutdown must NOT kill the manager's shared node pools
    for nm in mgr.master.node_managers().values():
        assert not nm.executor._shutdown
    mgr.close()
    for nm in mgr.master.node_managers().values():
        assert nm.executor._shutdown


def test_pipeline_manager_rejects_objects_and_resilience(mgr):
    with pytest.raises(ValueError, match="compiled"):
        Pipeline(manager=mgr, execution="objects")
    with pytest.raises(ValueError, match="resilience"):
        Pipeline(manager=mgr, execution="compiled",
                 resilience=ResilienceConfig())
