"""The engine's own spans on the profiler's clock (``harness/engine_spans``).

A tiny mamba deployment served through the engine under a CPU profiler
trace: every registry app's ``Timeline`` interval, mapped through the
``engine.anchor`` fit, lands on the ``bench.*`` span the app opened; the
scheduler's waits are read from the stamps; the idle split needs a device
plane, so the CPU leaves it out.  The split itself is checked on a
hand-made trace, and the recorded v5e trace still reduces to the numbers
the accepted readers gave it.
"""
import json

import numpy as np
import pytest

from bench_testlib import BENCH, DATA
from harness import cell, engine_spans as E, spec
from harness import trace as T
from repro.core.telemetry import fit_clock

DEV = "/device:TPU:0"
TOL_NS = 0.5e6


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Two sessions of the tiny mamba cell's graph, traced after a
    warm-up session, with their summaries and the trace."""
    import jax
    from repro.core import EngineManager, TelemetryConfig
    from repro.models import model as M
    from repro.models.common import ArchConfig
    from deployments.lm_serve import Deployment

    arch = json.loads((DATA / "tiny-mamba.json").read_text())["arch"]
    cfg = ArchConfig(**arch)
    params = M.init_params(cfg, jax.random.PRNGKey(3))
    dep = Deployment(cfg, params, microbatch=2, decode_tokens=8)
    graph = dep.graph(4)
    rng = np.random.default_rng(5)

    def prompts():
        return rng.integers(0, cfg.vocab_size, (4, 16)).astype(np.int32)

    tmp = tmp_path_factory.mktemp("engine-trace")
    with EngineManager(num_nodes=2, workers_per_node=2, max_concurrent=2,
                       telemetry=TelemetryConfig(timeline=True)) as mgr:
        assert mgr.run(graph, inputs={"reqs": prompts()}, timeout=600).ok
        jax.profiler.start_trace(str(tmp))
        try:
            tickets = []
            for _ in range(2):      # one at a time: no other app thread
                tickets.append(mgr.submit(graph, inputs={"reqs": prompts()},
                                          timeout=600))
                assert tickets[-1].result(600).ok
        finally:
            jax.profiler.stop_trace()
        spans = [E.summary(t.session) for t in tickets]
    xplane = next(tmp.rglob("*.xplane.pb"))
    return spans, T.load_xplane(xplane), E.anchors(xplane)


@pytest.mark.parametrize("group", ["prefill", "decode", "assemble"])
def test_mapped_app_intervals_land_on_their_spans(traced, group):
    spans, tr, pairs = traced
    assert len(pairs) == 2 * (2 + 5)    # enter, exit and 5 apps each
    fit = fit_clock(pairs)
    bench = [(s, e) for v in tr.spans.values() for name, s, e, _ in v
             if name == f"bench.{group}"]
    apps = [row[1:3] for s in spans for g, row in zip(s.groups, s.stamps)
            if g == group]
    assert len(apps) == len(bench) == {"assemble": 2}.get(group, 4)
    for t0, t1 in apps:
        m0, m1 = fit.to_trace_ns(np.array([t0, t1]))
        # the app's interval holds its span, a few host lines wider
        assert any(abs(m0 - s) <= TOL_NS and abs(m1 - e) <= TOL_NS
                   for s, e in bench), (group, m0, m1)


def test_scheduler_waits_read_and_idle_split_left_out(traced):
    spans, tr, pairs = traced
    wait, delay = E.app_wait_ms(spans), E.activation_delay_ms(spans)
    assert wait is not None and wait >= 0
    assert delay is not None and delay > 0   # decode#0 waits for prefill#1
    for s in spans:
        assert len(s.exec_spans) == 1
        assert sorted(set(s.groups)) == ["assemble", "decode", "prefill"]
    # the CPU has no TPU plane: no idle split, never 0
    assert not tr.ops
    assert E.idle_shares(tr, pairs, spans) is None


def test_idle_split_on_a_hand_made_trace():
    # the trace clock runs 1000 ns ahead of the monotonic clock
    pairs = [(0, 1000.0), (10_000, 11_000.0)]
    spans = [E.SessionSpans(
        exec_spans=[(100e-9, 900e-9)], groups=["a", "b"],
        stamps=np.array([[100e-9, 200e-9, 400e-9, 100e-9],
                         [400e-9, 600e-9, 700e-9, 400e-9]]))]
    # window 1000-2000; device busy 1250-1350 and 1650-1750
    tr = T.Trace(ops={DEV: [("x", 1250, 1350), ("y", 1650, 1750)]},
                 marks={"open": 1000, "close": 2000})
    # executing 1100-1900, apps 1200-1400 and 1600-1700; idle 1000-1250,
    # 1350-1650, 1750-2000: outside any execute 1000-1100 and 1900-2000,
    # inside it with no app running 1100-1200, 1400-1600 and 1750-1900
    got = E.idle_shares(tr, pairs, spans)
    assert got == pytest.approx({"sched": 45.0, "no_session": 20.0})
    win = T.window_ns(tr)
    idle = 100 * (1 - T.union_ns(tr.ops[DEV], win) / (win[1] - win[0]))
    assert got["sched"] + got["no_session"] <= idle
    # one anchor gives no fit, a program without stamps no summary
    assert E.idle_shares(tr, pairs[:1], spans) is None
    assert E.app_wait_ms([None]) is None


def test_recorded_trace_reads_as_before():
    """The accepted readers and breakdown on the recorded v5e trace give
    the values they gave before the engine's spans were added; the new
    split is silent there, since that trace holds no anchor."""
    tr = T.Trace.from_json((DATA / "trace_v5e_granite.json").read_text())
    arch = json.loads((BENCH / "configs" /
                       "granite-moe-3b-a800m.json").read_text())["arch"]
    win = T.window_ns(tr)
    run = cell.RunData(arch=arch, mix=None,
                       peaks={"bf16_flops_per_s": 197e12}, records=[],
                       trace=tr, busy_s=T.busy_s(tr),
                       window_s=(win[1] - win[0]) / 1e9)
    want = {"decode_step_ms": 10.277362870967742,
            "mfu.decode": 0.35937766629779166,
            "idle_share.closed": 0.8341257567844318,
            "sched_gap_ms.closed": None}
    for name, value in want.items():
        got = spec.load_module(BENCH / "metrics" / f"{name}.py",
                               f"pin_{name}").read(run)
        assert got == (None if value is None
                       else pytest.approx(value, rel=1e-12)), name
    assert dict(T.top_ops(tr, 2)) == pytest.approx(
        {"decode_one:%while.2 tuple while": 0.31182949299999996,
         "prefill_step:%while.3 tuple while": 0.061759043}, rel=1e-12)
    assert dict(T.idle_gaps(tr)) == pytest.approx(
        {"bench.decode": 0.0023403159999999994,
         "bench.prefill": 0.0008645100000000003}, rel=1e-12)
    assert E.idle_shares(tr, [], []) is None
