"""granite-3.0-3b-a800m's reference and its grouped-matmul roofline.

A tiny configuration of the published model (the four multipliers, the
dropless MoE) runs through the harness on the CPU with the real
reference: in float32 the program serves exactly the reference's best
token, the fp8 control fails the limit, and the two roofline readers
count what ``harness/moe_gmm.py`` says and stay silent where no grouped
matmul ran.
"""
import json
import shutil
from types import SimpleNamespace

import pytest

from bench_testlib import BENCH, DATA
from harness import moe_gmm
from harness import trace as T
from harness.spec import load_module

SEEDS = [2**31 + 11, 1000003, 3000009]
CONFIG = "tiny-granite3"
REFERENCE = "granite-3.0-3b-a800m"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture
def granite3_tree(tiny_tree):
    """The tiny tree with the tiny granite-3.0 cells added beside the
    others, borrowing the real reference."""
    root, bench = tiny_tree
    shutil.copy(DATA / f"{CONFIG}.json", bench / "configs")
    shutil.copy(BENCH / "configs" / f"{REFERENCE}.py",
                bench / "configs" / f"{CONFIG}.py")
    bj = json.loads((root / "BENCHMARK.json").read_text())
    bj["configs"].append({"name": CONFIG, "source": "tiny",
                          "file": f"bench/configs/{CONFIG}.json",
                          "reduced": [], "why": "tiny"})
    for traffic in ("tiny-closed", "tiny-open"):
        bj["workloads"].append({"name": f"{CONFIG}.{traffic[5:]}",
                                "config": CONFIG, "traffic": traffic,
                                "chips": 1, "why": "tiny"})
    (root / "BENCHMARK.json").write_text(json.dumps(bj))
    return root, bench


def test_tiny_cell_runs_correct(granite3_tree, run_tiny):
    res, err = run_tiny(f"{CONFIG}.closed", seed=SEEDS[0], seconds=1.0)
    assert res["correct"] is True, err
    assert res["failed"] == 0 and res["attempted"] > 0


def test_reference_matches_float32_program(granite3_tree, run_tiny):
    """Run in float32, the program serves the reference's best token at
    every position: the reference follows the published semantics the
    program serves (multipliers, dropless routing, caches)."""
    _, bench = granite3_tree
    p = bench / "configs" / f"{CONFIG}.json"
    d = json.loads(p.read_text())
    d["arch"]["dtype"] = "float32"
    p.write_text(json.dumps(d))
    for seed in SEEDS:
        res, _ = run_tiny(f"{CONFIG}.open", seed=seed, seconds=1.0)
        assert res["check"]["max_logit_gap"]["value"] == 0.0
        assert res["check"]["mean_logit_gap"]["value"] == 0.0


def test_reference_needs_the_multipliers(granite3_tree):
    """A reference with neutral multipliers computes other logits: the
    comparison sees each multiplier."""
    import numpy as np
    _, bench = granite3_tree
    ref = load_module(bench / "configs" / f"{CONFIG}.py", "ref_granite3")
    a = json.loads((bench / "configs" / f"{CONFIG}.json").read_text())["arch"]
    seq = [np.arange(9, dtype=np.int32) * 7]
    base, _ = ref.reference_logits(a, 5, seq, [4])
    for name, neutral in [("embedding_multiplier", 1.0),
                          ("attention_multiplier", 32 ** -0.5),
                          ("residual_multiplier", 1.0),
                          ("logits_scaling", 1.0)]:
        other, _ = ref.reference_logits(dict(a, **{name: neutral}), 5, seq,
                                        [4])
        assert not np.allclose(base[0], other[0], atol=1e-3), name


@pytest.mark.parametrize("seed", SEEDS)
def test_control_fails_the_limit(granite3_tree, run_tiny, seed):
    res, _ = run_tiny(f"{CONFIG}.open", seed=seed, seconds=1.0, control=1)
    assert res["correct"] is False
    gaps = ("max_logit_gap", "mean_logit_gap")
    assert any(res["check"][k]["value"] > res["check"][k]["limit"]
               for k in gaps)
    assert all(res["served"][k] <= res["check"][k]["limit"] for k in gaps)


# --- the roofline of the grouped matmuls -----------------------------------

ARCH = json.loads((BENCH / "configs" / f"{REFERENCE}.json").read_text())["arch"]


def test_counts_by_hand():
    d, f = 1536, 512
    # a decode step of 8 rows: 64 routed rows reach all 40 experts
    assert moe_gmm.gmm_flops(ARCH, 64) == 3 * 2 * 64 * d * f
    assert moe_gmm.gmm_bytes(ARCH, 64) == (3 * 2 * 64 * (d + f)
                                           + 3 * 2 * 40 * d * f)
    # 16 rows can reach at most 16 experts
    assert moe_gmm.gmm_bytes(ARCH, 16) == (3 * 2 * 16 * (d + f)
                                           + 3 * 2 * 16 * d * f)
    # decode is bound by the expert weights: 188,743,680 B + 786,432 B of
    # rows per layer at 819 GB/s, over 32 layers
    want = 32 * (3 * 2 * (64 * 2048 + 40 * 1536 * 512)) / 819e9
    assert moe_gmm.gmm_bound_s(ARCH, 64, PEAKS) == pytest.approx(want)
    # a prefill of 8 x 512 tokens (32,768 rows) is bound by its FLOPs
    rows = moe_gmm.rows_served(ARCH, "prefill_step",
                               {"mb": 8, "prompt_len": 512})
    assert rows == 32768
    assert moe_gmm.gmm_bound_s(ARCH, rows, PEAKS) == pytest.approx(
        32 * 6 * 32768 * d * f / 197e12)
    assert moe_gmm.rows_served(ARCH, "decode_one",
                               {"mb": 8, "prompt_len": 512}) == 64


def test_op_names():
    def gmm(label, fn="decode_one"):
        return moe_gmm.is_gmm_op(label, fn, ARCH)
    assert gmm("decode_one:%ragged-dot-none.2 bf16[64,1536] custom-call")
    assert moe_gmm.is_gmm_kernel("decode_one:%ragged-dot-none bf16[64,512] "
                                 "custom-call", "decode_one")
    # the ops that stage a layer's expert weights count with the kernels
    assert gmm("decode_one:%dynamic-slice_bitcast_fusion.18 "
               "bf16[40,1536,512] fusion")
    assert gmm("decode_one:%dynamic-slice_bitcast_fusion.19 "
               "bf16[40,512,1536] fusion")
    assert not moe_gmm.is_gmm_kernel("decode_one:%dynamic-slice_bitcast_"
                                     "fusion.18 bf16[40,1536,512] fusion",
                                     "decode_one")
    assert not gmm("decode_one:%ragged-dot-metadata tuple custom-call")
    assert not gmm("prefill_step:%ragged-dot-none bf16[8,5] custom-call")
    assert not gmm("decode_one:%fusion.178 bf16[1,40,8,1536] fusion")


def _run(tr):
    return SimpleNamespace(trace=tr, arch=ARCH, peaks=PEAKS)


def _gmm_trace():
    """Two decode executions of 8 rows; each stages one expert stack in
    0.5 ms and runs 3 grouped matmuls of 1 ms and a metadata call; one
    more execution has no span."""
    tr = T.Trace()
    dev, line = "/device:TPU:0", "0:python"
    mods, ops, disp, spans = [], [], [], []
    for i in range(3):
        t0 = i * 10_000_000
        disp.append(("decode_one", t0, t0 + 100))
        mods.append(("jit_decode_one(1)", t0 + 1000, t0 + 5_000_000))
        ops.append(("decode_one:%ragged-dot-metadata tuple custom-call",
                    t0 + 1000, t0 + 2000))
        ops.append(("decode_one:%dynamic-slice_bitcast_fusion.1 "
                    "bf16[40,512,1536] fusion", t0 + 3_500_000,
                    t0 + 4_000_000))
        for j in range(3):
            s = t0 + 2000 + j * 1_000_000
            ops.append((f"decode_one:%ragged-dot-none.{j} bf16[64,512] "
                        "custom-call", s, s + 1_000_000))
    spans.append(("bench.decode", 0, 15_000_000,
                  {"mb": 8, "prompt_len": 512}))
    tr.modules[dev], tr.ops[dev] = mods, ops
    tr.dispatches[line], tr.spans[line] = disp, spans
    return tr


def test_readers_on_a_hand_made_trace():
    dec = load_module(BENCH / "metrics" / "moe_gmm_roofline.decode.py", "d")
    pre = load_module(BENCH / "metrics" / "moe_gmm_roofline.prefill.py", "p")
    tr = _gmm_trace()
    assert len(T.matched(tr, "decode_one")) == 2
    want = 100 * 2 * moe_gmm.gmm_bound_s(ARCH, 64, PEAKS) / (2 * 3.5e-3)
    assert dec.read(_run(tr)) == pytest.approx(want)
    assert pre.read(_run(tr)) is None


def test_kernel_time_on_a_hand_made_trace():
    """The kernels alone: three 1 ms grouped matmuls per execution, the
    staging op and the metadata call left out."""
    ker = load_module(BENCH / "metrics" / "moe_gmm_kernel_ms.decode.py", "k")
    tr = _gmm_trace()
    assert ker.read(_run(tr)) == pytest.approx(3.0)
    assert moe_gmm.kernel_ms(_run(tr), "prefill_step") is None


def test_readers_silent_without_grouped_matmuls():
    """The recorded v5e trace of the capacity-dispatch program has no
    ``%ragged-dot`` op, and a run without a trace has nothing to read:
    each reader of the grouped matmuls leaves its metric out."""
    tr = T.Trace.from_json((DATA / "trace_v5e_granite.json").read_text())
    assert T.matched(tr, "decode_one") and T.matched(tr, "prefill_step")
    for name in ("roofline.decode", "roofline.prefill", "kernel_ms.decode"):
        reader = load_module(BENCH / "metrics" / f"moe_gmm_{name}.py", name)
        assert reader.read(_run(tr)) is None
        assert reader.read(_run(None)) is None
        assert reader.read(_run(T.Trace())) is None
