"""The trace reduction: device busy union, per-program device time by the
jitted step's stable name, dispatch matching, and idle gaps attributed to
the benchmark's own spans; on a hand-made trace and on a small trace
recorded on a TPU v5e."""
import json

import pytest

from bench_testlib import DATA
from harness import flops
from harness import trace as T

DEV = "/device:TPU:0"


def hand_trace():
    # device: two decode executions and one prefill, ops inside them
    modules = [("jit_prefill_step(7)", 100, 300),
               ("jit_decode_one(3)", 400, 450),
               ("jit_decode_one(3)", 500, 560)]
    ops = [("fusion.1", 100, 200), ("fusion.2", 150, 300),   # overlap
           ("fusion.3", 400, 450), ("fusion.3", 500, 560)]
    spans = {"0:worker-a": [("bench.prefill", 90, 320,
                             {"mb": 4, "prompt_len": 2048})],
             "1:worker-b": [("bench.decode", 380, 600,
                             {"mb": 8, "prompt_len": 512})]}
    dispatches = {"0:worker-a": [("prefill_step", 95, 99),
                                 ("prefill_step", 96, 98)],   # nested
                  "1:worker-b": [("decode_one", 390, 395),
                                 ("decode_one", 460, 470),
                                 ("decode_one", 580, 590)]}   # not run yet
    return T.Trace(ops={DEV: ops}, modules={DEV: modules}, spans=spans,
                   dispatches=dispatches, marks={"open": 0, "close": 700})


def test_busy_is_the_union_of_op_intervals():
    tr = hand_trace()
    assert T.union_ns(tr.ops[DEV]) == 200 + 50 + 60
    assert T.busy_s(tr) == pytest.approx(310e-9)


def test_busy_counts_only_the_marked_window():
    tr = hand_trace()
    tr.marks = {"open": 150, "close": 520}
    # 150-300 of the overlapping pair, 400-450, 500-520
    assert T.busy_s(tr) == pytest.approx(220e-9)
    assert T.window_ns(tr) == (150, 520)
    # without marks the window is what the events cover
    tr.marks = {}
    assert T.window_ns(tr) == (90, 600)


def test_self_time_leaves_out_nested_ops():
    ops = [("while", 0, 100), ("body.a", 10, 40), ("body.b", 50, 90),
           ("after", 100, 130)]
    assert T.self_times(ops) == pytest.approx(
        {"while": 30e-9, "body.a": 30e-9, "body.b": 40e-9, "after": 30e-9})


def test_op_labels_name_program_result_and_opcode():
    hlo = ("%fusion.7 = bf16[8,1536]{1,0:T(8,128)(2,1)} fusion(bf16[8,1536]"
           "{1,0:T(8,128)(2,1)} %p), kind=kLoop, calls=%fused_computation.3")
    assert T.op_label(hlo) == "%fusion.7 bf16[8,1536] fusion"
    assert T.op_label("%while.2 = (s32[], bf16[8,1,1536]{2,0,1}) while((s32[]"
                      ", bf16[8,1,1536]{2,0,1}) %t), condition=%c") == \
        "%while.2 tuple while"
    ops = T._attribute([("%a = f32[] add(f32[] %x, f32[] %y)", 410, 420),
                        ("%b = f32[] copy(f32[] %a)", 470, 480)],
                       [("jit_decode_one(3)", 400, 450)])
    assert [o[0] for o in ops] == ["decode_one:%a f32[] add", "?:%b f32[] copy"]


def test_program_names_are_stable():
    assert T.program_name("jit_decode_one(3)") == "decode_one"
    assert T.program_name("jit_prefill_step") == "prefill_step"
    assert [e[0] for e in T.executions(hand_trace(), "decode_one")] == \
        ["jit_decode_one(3)"] * 2


def test_dispatches_pair_with_executions_in_order():
    tr = hand_trace()
    pre = T.matched(tr, "prefill_step")
    assert len(pre) == 1 and pre[0][1]["prompt_len"] == 2048
    dec = T.matched(tr, "decode_one")
    assert [d["step"] for _, d in dec] == [0, 1]
    assert [ex[1] for ex, _ in dec] == [400, 500]


def test_idle_gaps_attributed_to_open_spans():
    tr = hand_trace()
    gaps = dict(T.idle_gaps(tr))
    # gaps 0-100 (middle 50: no span), 300-400 (middle 350: the prefill
    # span ended at 320, the decode span opens at 380), 450-500 (decode),
    # 560-700 (middle 630: the decode span ended at 600)
    assert gaps == pytest.approx({"host.idle": 340e-9, "bench.decode": 50e-9})


def test_json_round_trip():
    tr = hand_trace()
    again = T.Trace.from_json(tr.to_json())
    assert T.busy_s(again) == T.busy_s(tr)
    assert T.matched(again, "decode_one") == T.matched(tr, "decode_one")


def test_recorded_tpu_trace():
    """A slice of a traced granite-moe-3b-a800m run on a v5e (what the
    meta file says it is), reduced by the same functions as a run."""
    tr = T.Trace.from_json((DATA / "trace_v5e_granite.json").read_text())
    meta = json.loads((DATA / "trace_v5e_granite.meta.json").read_text())
    ex = T.executions(tr, "decode_one")
    pairs = T.matched(tr, "decode_one")
    assert len(ex) == meta["decode_executions"]
    assert len(pairs) == meta["decode_matched"]
    assert len(T.matched(tr, "prefill_step")) == meta["prefill_matched"]
    assert T.busy_s(tr) == pytest.approx(meta["busy_s"], rel=1e-9)
    # each decode execution follows its dispatch, and served the session's
    # rows at the steps of one decode loop
    assert all(d["start"] < s for (_, s, _), d in pairs)
    assert all(d["mb"] == meta["mb"] and d["prompt_len"] == meta["prompt_len"]
               for _, d in pairs)
    assert [d["step"] for _, d in pairs] == list(range(len(pairs)))
    a = json.loads((DATA.parent.parent / "configs" /
                    "granite-moe-3b-a800m.json").read_text())["arch"]
    work = sum(flops.decode_flops(a, d["mb"], d["prompt_len"] + d["step"])
               for _, d in pairs)
    secs = sum(e - s for (_, s, e), _ in pairs) / 1e9
    mfu = 100 * work / (secs * 197e12)
    assert mfu == pytest.approx(meta["mfu_decode_pct"], rel=1e-9)
    assert 0 < mfu < 100
    # the breakdown names ops by program and leaves no gap unattributed
    assert T.top_ops(tr, 1)[0][0].startswith("decode_one:")
    gaps = T.idle_gaps(tr)
    assert sum(v for _, v in gaps) == pytest.approx(
        (T.window_ns(tr)[1] - T.window_ns(tr)[0]) / 1e9 - T.busy_s(tr))
