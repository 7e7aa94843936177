"""Process-backed node execution + shared-memory payload plane (PR-10).

``EngineConfig(workers="process")`` gives every node a crash-isolated
spawn worker (``ProcExecutor``) and every island a ``PayloadPlane`` of
``multiprocessing.shared_memory`` segments; the thread-backed compiled
engine and the object engine are the semantic oracles.  Covered here:

* plane wire encoding (raw below threshold, shm descriptor above,
  passthrough cache hits, zero-copy attach, unlink-on-close),
* a full worker mailbox round trip with zero-copy arrays in and out,
* engine equivalence: process mode ≡ objects oracle on an array graph,
* error isolation: a non-picklable app poisons only its own drop,
* clean pool shutdown with no leaked worker processes,
* satellite regressions: ``MemoryPayload.nbytes`` must not pickle
  buffer values; ``NodeDropManager.shutdown`` drains with bounded grace
  and marks sessions FAILED instead of silently abandoning app calls;
  a wedged stream-consumer survives lane shutdown only as a warned,
  *fenced* thread whose stale writes raise ``StreamAbort``.

Apps used by worker processes are module-level: spawn workers resolve
functions by reference (module re-import), so test-local closures are
exactly the "not picklable" failure mode exercised below.
"""
import os
import pickle
import threading
import time

import numpy as np
import pytest

from repro.core import (CompiledSession, EngineConfig, MemoryPayload,
                        PayloadPlane, Pipeline, ProcExecutor,
                        ProcNodeDropManager, SessionState, StreamAbort,
                        StreamConfig, WorkerLost, register_app, unroll)
from repro.core import drop as drop_mod
from repro.core.drop import DropState, buffer_nbytes
from repro.core.managers import NodeDropManager
from repro.core.mapping import NodeInfo
from repro.core.procpool import DEFAULT_SHM_MIN_BYTES
from repro.dsl import GraphBuilder

# 256 KiB of float64 — comfortably above DEFAULT_SHM_MIN_BYTES
ARR_N = 32 * 1024


# ---------------------------------------------------------------------------
# module-level apps (importable by spawn workers)
# ---------------------------------------------------------------------------


@register_app("pp/make")
def pp_make(inputs, outputs, app):
    seed = inputs[0].read() if inputs else 1
    for o in outputs:
        o.write(np.full(ARR_N, float(seed)))


@register_app("pp/scale")
def pp_scale(inputs, outputs, app):
    v = inputs[0].read()
    for o in outputs:
        o.write(v * 2.0)


@register_app("pp/reduce")
def pp_reduce(inputs, outputs, app):
    total = sum(float(np.asarray(i.read()).sum()) for i in inputs)
    for o in outputs:
        o.write(total)


@register_app("pp/double")
def pp_double(inputs, outputs, app):
    v = sum(i.read() for i in inputs) if inputs else 1
    for o in outputs:
        o.write(v * 2)


@register_app("pp/boom")
def pp_boom(inputs, outputs, app):
    raise RuntimeError("scripted worker-side failure")


def array_lg(width=3):
    """Scatter of array producers/scalers, gathered into one scalar."""
    g = GraphBuilder("pp_arrays")
    g.data("src")
    with g.scatter("sc", width):
        g.component("mk", app="pp/make", time=1.0)
        g.data("arr", volume=10)
        g.component("up", app="pp/scale", time=1.0)
        g.data("arr2", volume=10)
    with g.gather("ga", width):
        g.component("r", app="pp/reduce", time=1.0)
    g.data("out")
    g.chain("src", "mk", "arr", "up", "arr2", "r", "out")
    return g.graph()


def chain_lg():
    g = GraphBuilder("pp_chain")
    g.data("src")
    g.component("a1", app="pp/double", time=1.0)
    g.data("d1", volume=10)
    g.component("a2", app="pp/double", time=1.0)
    g.data("out")
    g.chain("src", "a1", "d1", "a2", "out")
    return g.graph()


def _pid_gone(pid, wait=3.0):
    deadline = time.monotonic() + wait
    while time.monotonic() < deadline:
        try:
            os.kill(pid, 0)
        except ProcessLookupError:
            return True
        time.sleep(0.02)
    return False


# ---------------------------------------------------------------------------
# PayloadPlane wire encoding (parent-side, no processes)
# ---------------------------------------------------------------------------


class TestPayloadPlane:
    def test_small_and_opaque_values_ship_raw(self):
        plane = PayloadPlane()
        try:
            assert plane.encode(7) == ("raw", 7)
            assert plane.encode({"k": [1, 2]})[0] == "raw"
            # sub-threshold arrays are cheaper to copy than to segment
            small = np.arange(8)
            assert small.nbytes < DEFAULT_SHM_MIN_BYTES
            assert plane.encode(small)[0] == "raw"
            assert plane.stats["raw_values"] == 3
            assert plane.stats["shm_exports"] == 0
        finally:
            plane.close()

    def test_large_array_exports_once_then_passthrough(self):
        plane = PayloadPlane(shm_min_bytes=1024)
        try:
            arr = np.arange(1024, dtype=np.float64)
            tag, desc = plane.encode(arr)
            assert tag == "shm"
            assert plane.stats["shm_exports"] == 1
            # same object again: descriptor cache hit, no second copy
            tag2, desc2 = plane.encode(arr)
            assert (tag2, desc2) == (tag, desc)
            assert plane.stats["shm_passthrough"] == 1
            assert plane.stats["shm_exports"] == 1
            # decode maps the segment zero-copy: two attaches of the same
            # descriptor share one buffer
            a1 = plane.decode((tag, desc))
            a2 = plane.decode((tag, desc))
            np.testing.assert_array_equal(a1, arr)
            assert np.shares_memory(a1, a2)
        finally:
            plane.close()

    def test_close_unlinks_segments(self):
        from multiprocessing.shared_memory import SharedMemory

        plane = PayloadPlane(shm_min_bytes=1024)
        _, (name, _, _) = plane.encode(np.zeros(1024))
        plane.close()
        with pytest.raises(FileNotFoundError):
            SharedMemory(name=name)


# ---------------------------------------------------------------------------
# worker mailbox round trip (one real spawn process)
# ---------------------------------------------------------------------------


class TestProcExecutorRoundTrip:
    def _spec(self, func, idx, uid, inputs, outputs):
        return {"idx": idx, "uid": uid, "func": func, "meta": {},
                "inputs": inputs, "outputs": outputs}

    def test_zero_copy_arrays_in_and_out(self):
        plane = PayloadPlane(shm_min_bytes=1024)
        ex = ProcExecutor("nodeT", plane)
        try:
            arr = np.arange(2048, dtype=np.float64)
            spec = self._spec(pp_scale, 0, "up",
                              [("arr", {}, arr, None)], [(1, "arr2", {})])
            (res,) = ex.run_batch([spec], budget=30.0)
            assert res["status"] == "ok", res.get("tb")
            [(j, out)] = res["writes"]
            assert j == 1
            np.testing.assert_array_equal(out, arr * 2.0)
            # input rode the plane out, the result rode it back
            assert plane.stats["shm_exports"] == 1
            assert plane.stats["shm_results"] == 1
            assert plane.stats["raw_values"] == 0
        finally:
            ex.shutdown()
            plane.close()

    def test_worker_error_reports_traceback(self):
        plane = PayloadPlane()
        ex = ProcExecutor("nodeT", plane)
        try:
            spec = self._spec(pp_boom, 0, "b", [], [(1, "out", {})])
            (res,) = ex.run_batch([spec], budget=30.0)
            assert res["status"] == "err"
            assert "scripted worker-side failure" in res["tb"]
        finally:
            ex.shutdown()
            plane.close()

    def test_killed_worker_raises_worker_lost_and_stays_dead(self):
        plane = PayloadPlane()
        ex = ProcExecutor("nodeT", plane)
        try:
            spec = self._spec(pp_double, 0, "a",
                              [("src", {}, 3, None)], [(1, "out", {})])
            ex.run_batch([spec], budget=30.0)
            ex.kill()
            with pytest.raises(WorkerLost) as ei:
                ex.run_batch([spec], budget=30.0)
            assert ei.value.nodes == ["nodeT"]
            assert ex.dead
            # dead executors fail fast; workers are never respawned
            with pytest.raises(WorkerLost):
                ex.run_batch([spec], budget=30.0)
        finally:
            ex.shutdown()
            plane.close()

    def test_shutdown_leaves_no_process(self):
        plane = PayloadPlane()
        ex = ProcExecutor("nodeT", plane)
        try:
            spec = self._spec(pp_double, 0, "a", [], [(1, "out", {})])
            ex.run_batch([spec], budget=30.0)
            pid = ex.pid
            assert pid is not None
        finally:
            ex.shutdown()
            plane.close()
        assert _pid_gone(pid), f"worker {pid} leaked past shutdown"


# ---------------------------------------------------------------------------
# engine equivalence: workers="process" ≡ objects oracle
# ---------------------------------------------------------------------------


class TestProcessEngineEquivalence:
    def test_array_graph_matches_objects_oracle(self):
        with Pipeline(num_nodes=2, algorithm="none") as p:
            rep = p.run(array_lg(), inputs={"src": 3})
            assert rep.ok, rep.errors
            oracle = {u: d.read() for u, d in p.session.drops.items()
                      if d.state is DropState.COMPLETED
                      and getattr(d, "payload", None) is not None
                      and d.payload.exists()}
        with Pipeline(num_nodes=2, algorithm="none", execution="compiled",
                      workers="process") as p:
            rep = p.run(array_lg(), inputs={"src": 3})
            assert rep.ok, rep.errors
            nms = p.master.node_managers()
            assert all(isinstance(nm, ProcNodeDropManager)
                       for nm in nms.values())
            s = p.session
            for u, want in oracle.items():
                got = s.read(u)
                if isinstance(want, np.ndarray):
                    np.testing.assert_array_equal(got, want)
                else:
                    assert got == want, u
            # array edges actually used the plane (not pickle): every
            # node of the island shares one plane and it saw shm traffic
            planes = {id(nm.plane): nm.plane for nm in nms.values()}
            assert len(planes) == 1
            st = next(iter(planes.values())).stats
            assert st["shm_exports"] + st["shm_results"] > 0
            pids = [nm.executor.pid for nm in nms.values()
                    if nm.executor.pid is not None]
            assert pids, "no worker process was ever spawned"
        # context exit shut the cluster down: nothing may leak
        for pid in pids:
            assert _pid_gone(pid), f"worker {pid} leaked past shutdown"

    def test_worker_app_error_isolated_to_drop(self):
        g = GraphBuilder("pp_err")
        g.data("src")
        g.component("good", app="pp/double", time=1.0)
        g.data("gout")
        g.chain("src", "good", "gout")
        g.component("bad", app="pp/boom", time=1.0)
        g.data("bout")
        g.chain("src", "bad", "bout")
        for workers in ("thread", "process"):
            with Pipeline(num_nodes=2, algorithm="none",
                          execution="compiled", workers=workers) as p:
                rep = p.run(g.graph(), inputs={"src": 2})
                assert not rep.ok
                s = p.session
                assert s.state_of("bad") is DropState.ERROR
                assert s.state_of("good") is DropState.COMPLETED
                assert s.read("gout") == 4

    def test_unpicklable_app_poisons_only_its_drop(self):
        # a test-local closure pickles by reference and the reference
        # cannot resolve — the canonical "app not shippable" failure
        @register_app("pp/local-closure")
        def _local(inputs, outputs, app):      # pragma: no cover - parent
            for o in outputs:                  # rejects it before dispatch
                o.write("never")

        g = GraphBuilder("pp_unpick")
        g.data("src")
        g.component("good", app="pp/double", time=1.0)
        g.data("gout")
        g.chain("src", "good", "gout")
        g.component("bad", app="pp/local-closure", time=1.0)
        g.data("bout")
        g.chain("src", "bad", "bout")
        with Pipeline(num_nodes=2, algorithm="none", execution="compiled",
                      workers="process") as p:
            rep = p.run(g.graph(), inputs={"src": 2})
            assert not rep.ok
            s = p.session
            assert s.state_of("bad") is DropState.ERROR
            assert "not picklable" in s.error_info.get(
                s.index_of("bad"), "")
            assert s.state_of("good") is DropState.COMPLETED
            assert s.read("gout") == 4

    def test_device_app_fails_its_drop_on_process_workers(self):
        g = GraphBuilder("pp_device")
        g.data("src")
        g.component("good", app="pp/double", time=1.0)
        g.data("gout")
        g.chain("src", "good", "gout")
        g.component("dev", app="pp/device", time=1.0)
        g.data("dout")
        g.chain("src", "dev", "dout")
        with Pipeline(num_nodes=2, algorithm="none", execution="compiled",
                      workers="process") as p:
            rep = p.run(g.graph(), inputs={"src": 2})
            assert not rep.ok
            s = p.session
            assert s.state_of("dev") is DropState.ERROR
            assert "device apps run on thread workers" in s.error_info.get(
                s.index_of("dev"), "")
            assert s.state_of("good") is DropState.COMPLETED
            assert s.read("gout") == 4


# ---------------------------------------------------------------------------
# one process per chip: workers never load the TPU library
# ---------------------------------------------------------------------------


@register_app("pp/device", device=True)
def pp_device(inputs, outputs, app):   # pragma: no cover - refused
    raise AssertionError("a device app ran in a process worker")


@register_app("pp/jax-platforms")
def pp_jax_platforms(inputs, outputs, app):
    import jax
    for o in outputs:
        o.write((os.environ.get("JAX_PLATFORMS"), jax.config.jax_platforms))


class TestWorkerStaysOffTheChip:
    def test_worker_jax_is_pinned_to_cpu(self, monkeypatch):
        # the worker inherits the parent's environment at spawn; whatever
        # platform the parent asked for, the worker's JAX stays on the CPU
        monkeypatch.setenv("JAX_PLATFORMS", "tpu")
        plane = PayloadPlane()
        ex = ProcExecutor("nodeT", plane)
        try:
            spec = {"idx": 0, "uid": "j", "func": pp_jax_platforms,
                    "meta": {}, "inputs": [], "outputs": [(1, "out", {})]}
            (res,) = ex.run_batch([spec], budget=60.0)
            assert res["status"] == "ok", res.get("tb")
            assert res["writes"] == [(1, ("cpu", "cpu"))]
        finally:
            ex.shutdown()
            plane.close()

    def test_device_app_refused_before_dispatch(self):
        plane = PayloadPlane()
        ex = ProcExecutor("nodeT", plane)
        try:
            spec = {"idx": 0, "uid": "dev", "func": pp_device, "meta": {},
                    "inputs": [], "outputs": [(1, "out", {})]}
            (res,) = ex.run_batch([spec], budget=30.0)
            assert res["status"] == "err"
            assert "'dev' is a device app" in res["tb"]
            assert "device apps run on thread workers" in res["tb"]
        finally:
            ex.shutdown()
            plane.close()


# ---------------------------------------------------------------------------
# satellite: MemoryPayload.nbytes must not serialise buffer values
# ---------------------------------------------------------------------------


class _NoPickle:
    HIGHEST_PROTOCOL = pickle.HIGHEST_PROTOCOL

    @staticmethod
    def dumps(*a, **k):
        raise AssertionError("nbytes serialised a buffer-protocol value")

    loads = staticmethod(pickle.loads)


class TestMemoryPayloadNbytes:
    def test_100mb_buffer_sized_without_pickle(self, monkeypatch):
        monkeypatch.setattr(drop_mod, "pickle", _NoPickle)
        pl = MemoryPayload()
        pl.write(bytearray(100 * 2**20))
        assert pl.nbytes() == 100 * 2**20

    def test_ndarray_and_bytes_sized_without_pickle(self, monkeypatch):
        monkeypatch.setattr(drop_mod, "pickle", _NoPickle)
        pl = MemoryPayload()
        pl.write(np.zeros((256, 256)))
        assert pl.nbytes() == 256 * 256 * 8
        pl.write(b"x" * 4096)
        assert pl.nbytes() == 4096

    def test_opaque_values_still_fall_back_to_pickle(self):
        pl = MemoryPayload()
        val = {"k": list(range(100))}
        pl.write(val)
        assert pl.nbytes() == len(
            pickle.dumps(val, protocol=pickle.HIGHEST_PROTOCOL))
        assert buffer_nbytes(val) is None


# ---------------------------------------------------------------------------
# satellite: NodeDropManager.shutdown drains, then fails open sessions
# ---------------------------------------------------------------------------


class _SessionStub:
    session_id = "s-stub"

    def __init__(self):
        self.reasons = []

    def fail(self, reason):
        self.reasons.append(reason)


class TestShutdownDrain:
    def test_fast_inflight_work_drains_cleanly(self):
        nm = NodeDropManager(NodeInfo(name="nodeX", island="island0"))
        stub = _SessionStub()
        nm._session_refs[stub.session_id] = stub
        fut = nm.executor.submit(time.sleep, 0.05)
        nm.shutdown()
        assert fut.done()
        assert stub.reasons == []

    def test_wedged_work_bounded_and_session_failed(self, monkeypatch):
        monkeypatch.setattr(NodeDropManager, "SHUTDOWN_GRACE_S", 0.2)
        nm = NodeDropManager(NodeInfo(name="nodeX", island="island0"))
        stub = _SessionStub()
        nm._session_refs[stub.session_id] = stub
        release = threading.Event()
        nm.executor.submit(release.wait)
        t0 = time.monotonic()
        nm.shutdown()
        elapsed = time.monotonic() - t0
        release.set()
        assert elapsed < 3.0, "shutdown must not block unboundedly"
        assert len(stub.reasons) == 1
        assert "in-flight" in stub.reasons[0]
        assert "nodeX" in stub.reasons[0]

    def test_compiled_session_fail_is_terminal_and_sticky(self):
        pgt = unroll(chain_lg())
        s = CompiledSession("s-fail", pgt)
        s.fail("boom")
        assert s.state is SessionState.FAILED
        assert s.error_reason == "boom"
        assert s.wait(0.5)                  # fail() releases waiters
        s.fail("later")                     # terminal: no-op
        assert s.error_reason == "boom"

    def test_pipeline_shutdown_marks_real_session_failed(self, monkeypatch):
        monkeypatch.setattr(NodeDropManager, "SHUTDOWN_GRACE_S", 0.2)
        started, release = threading.Event(), threading.Event()

        @register_app("pp/block")
        def _block(inputs, outputs, app):
            started.set()
            release.wait(20)
            for o in outputs:
                o.write(1)

        # two blocking apps spread over two nodes: single-batch waves run
        # inline on the wave-loop thread, so only multi-node waves
        # exercise the executor drain being tested here
        g = GraphBuilder("pp_block")
        g.data("src")
        for i in range(2):
            g.component(f"b{i}", app="pp/block", time=1.0)
            g.data(f"out{i}")
            g.chain("src", f"b{i}", f"out{i}")
        p = Pipeline(num_nodes=2, algorithm="none", execution="compiled")
        p.translate(g.graph())
        p.deploy()

        def _run():
            try:
                p.execute(timeout=20, inputs={"src": 1})
            except Exception:
                pass  # executor torn down under the wave loop

        t = threading.Thread(target=_run, daemon=True)
        t.start()
        try:
            assert started.wait(5), "app never started"
            p.shutdown()
            assert p.session.state is SessionState.FAILED
            assert "in-flight" in (p.session.error_reason or "")
        finally:
            release.set()
            t.join(5)


# ---------------------------------------------------------------------------
# satellite: wedged stream consumers are warned about and fenced
# ---------------------------------------------------------------------------


class TestStreamLaneFence:
    def test_wedged_consumer_warned_and_stale_write_fenced(self):
        wedged, release = threading.Event(), threading.Event()
        aborted = []

        def _fin(inputs, outputs, app):
            for o in outputs:
                o.write("done")

        @register_app("pp/wedge", streaming=True, finish=_fin)
        def _wedge(value, app):
            wedged.set()
            release.wait(20)
            try:
                app.outputs[0].write(("stale", value))
            except StreamAbort as exc:
                aborted.append(str(exc))
                raise

        @register_app("pp/emit")
        def _emit(inputs, outputs, app):
            for i in range(3):
                for o in outputs:
                    o.write((i, i))

        g = GraphBuilder("pp_fence")
        g.data("src")
        g.component("P", app="pp/emit")
        g.data("d")
        g.component("C", app="pp/wedge")
        g.data("out")
        g.chain("src", "P", "d")
        g.connect("d", "C", streaming=True)
        g.chain("C", "out")

        cfg = EngineConfig(execution="compiled", num_nodes=1,
                           stream=StreamConfig(shutdown_grace_s=0.3))
        with Pipeline(cfg) as p:
            with pytest.warns(RuntimeWarning, match="still alive"):
                rep = p.run(g.graph(), timeout=1.0, inputs={"src": 1})
            assert not rep.ok                      # run timed out wedged
            assert wedged.is_set()
            tbl = p.session.stream
            assert tbl is not None and tbl.generation >= 1
            gen_after_fence = tbl.generation
            release.set()
            deadline = time.monotonic() + 5.0
            while not aborted and time.monotonic() < deadline:
                time.sleep(0.01)
            assert aborted, "stale-lane write was not fenced"
            assert "fenced" in aborted[0]
            # the stale write never landed and never bumped the table
            s = p.session
            assert not s.payload_present[s.pgt.index_of("out")]
            assert tbl.generation == gen_after_fence
