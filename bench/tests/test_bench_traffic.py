"""The traffic generator, the percentile and the open loop's clock."""
import time
from concurrent.futures import Future
from types import SimpleNamespace

import numpy as np
import pytest

from harness import traffic as T
from harness.drive import Driver

MIX = T.Mix.from_file({
    "loop": "open", "rate_per_s": 4.0, "requests_per_session": 4,
    "microbatch": 4, "decode_tokens": 8,
    "prompt_len": {"256": 0.4, "512": 0.3, "1024": 0.2, "2048": 0.1},
    "engine": {"num_nodes": 1, "workers_per_node": 1, "max_concurrent": 1}})


def test_nearest_rank():
    v = list(range(1, 21))
    assert T.nearest_rank(v, 95) == 19
    assert T.nearest_rank(v, 50) == 10
    assert T.nearest_rank(v, 100) == 20
    assert T.nearest_rank([5.0], 95) == 5.0
    assert T.nearest_rank([], 95) == float("inf")


def test_nearest_rank_counts_misses():
    # one miss in 20 sits above the 95th percentile, two reach it
    assert T.nearest_rank(list(range(1, 20)) + [float("inf")], 95) == 19
    assert T.nearest_rank(list(range(1, 19)) + [float("inf")] * 2,
                          95) == float("inf")


def test_apportion_holds_each_share():
    got = T.apportion(MIX.prompt_len, 10)
    assert got == [256] * 4 + [512] * 3 + [1024] * 2 + [2048]
    assert len(T.apportion(MIX.prompt_len, 7)) == 7


def test_open_schedule_from_seed():
    a = T.open_schedule(MIX, 2**31 + 5, 10.0, vocab=1000)
    b = T.open_schedule(MIX, 2**31 + 5, 10.0, vocab=1000)
    c = T.open_schedule(MIX, 77, 10.0, vocab=1000)
    assert len(a) == len(c) == 40
    assert [s.at for s in a] == [s.at for s in b]
    assert all(np.array_equal(x.prompts, y.prompts) for x, y in zip(a, b))
    # another seed: the same gaps and lengths, in another order
    assert [s.at for s in a] != [s.at for s in c]
    gaps = lambda s: sorted(np.diff([x.at for x in s] + [10.0]))
    assert np.allclose(gaps(a), gaps(c))
    assert sorted(s.prompt_len for s in a) == sorted(s.prompt_len for s in c)
    assert a[0].at == 0.0 and all(0 <= s.at < 10.0 for s in a)
    assert np.isclose(np.mean(np.diff([s.at for s in a] + [10.0])), 0.25)
    assert all(s.prompts.shape == (4, s.prompt_len) for s in a)
    assert all(s.prompts.max() < 1000 for s in a)


class StallingManager:
    """Answers every session at once, but its submit stalls."""

    def __init__(self, stall):
        self.stall = stall

    def submit(self, graph, inputs, timeout, block):
        time.sleep(self.stall)
        fut = Future()
        report = SimpleNamespace(ok=True, wall_time=0.0, errors=[])
        n = inputs["reqs"].shape[0]
        session = SimpleNamespace(read=lambda uid: np.zeros((n, 8), np.int32),
                                  timeline=None)
        ticket = SimpleNamespace(future=fut, session=session,
                                 session_id="s", queue_delay=0.0)
        fut.set_result(report)
        return ticket

    def close_session(self, sid):
        return True


def test_open_loop_times_from_scheduled_arrival():
    sched = [T.Session(0, np.zeros((4, 8), np.int32), 0.0),
             T.Session(1, np.zeros((4, 8), np.int32), 0.05)]
    drv = Driver(StallingManager(stall=0.3), graph=None, mix=MIX)
    t0 = time.monotonic()
    late = drv.open(sched, t0, late_s=5.0)
    first, second = sorted(drv.records, key=lambda r: r.session.index)
    assert first.due == pytest.approx(t0) and second.due == pytest.approx(t0 + 0.05)
    # the second waited behind the first's stalled submit: its latency
    # counts that wait from when it was due, not from when it was sent
    assert second.latency >= 0.3 + 0.3 - 0.05 - 0.01
    assert late >= 0.2
    assert all(r.ok for r in drv.records)


def test_unanswered_session_is_a_miss():
    rec = T.Session(0, np.zeros((4, 8), np.int32))
    from harness.drive import Record
    r = Record(rec, due=1.0)
    assert r.latency == float("inf")
    r.done, r.ok = 2.5, False
    assert r.latency == float("inf")
    r.ok = True
    assert r.latency == pytest.approx(1.5)


def test_closed_rate_counts_whole_sessions_to_the_last_answer():
    """Each answered session's tokens count in the share of its time
    inside the window; the window's length is the denominator."""
    from harness.cell import end_to_end
    from harness.drive import Record
    mix = T.Mix.from_file({
        "loop": "closed", "clients": 2, "requests_per_session": 2,
        "microbatch": 2, "decode_tokens": 8, "prompt_len": {"16": 1.0},
        "engine": {"num_nodes": 1, "workers_per_node": 1,
                   "max_concurrent": 1}})

    def rec(sent, answered, ok=True):
        r = Record(T.Session(0, np.zeros((2, 16), np.int32)), due=sent)
        r.done, r.ok = answered, ok
        r.tokens = np.zeros((2, 8), np.int32)
        return r
    # 16 tokens each: a session wholly inside counts whole, one running
    # over the close (108-112, the window closing at 110) counts half, a
    # failed one nothing
    records = [rec(100.0, 104.0), rec(108.0, 112.0), rec(101.0, 103.0, False)]
    got = end_to_end(mix, records, t0=100.0, seconds=10.0)
    assert got == {"gen_tokens_per_s": pytest.approx((16 + 8) / 10.0)}
