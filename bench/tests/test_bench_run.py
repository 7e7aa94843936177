"""Whole runs of tiny cells on the CPU, with the look for a chip skipped.

The harness drives the engine, reads what it served and compares a sample
with the plain reference.  A sound program comes out correct; the fp8
control fails the limit; and each fault a served cell can have, planted in
the timed path, makes ``correct`` false.
"""
import json

import pytest

import deployments.lm_serve as lm_serve

SEEDS = [2**31 + 11, 1000003, 3000009]


@pytest.mark.parametrize("cell", ["tiny-granite.closed", "tiny-mamba.closed",
                                  "tiny-granite.open", "tiny-mamba.open"])
def test_tiny_cell_runs_correct(run_tiny, cell):
    res, err = run_tiny(cell, seed=SEEDS[0], seconds=1.0)
    assert res["correct"] is True, err
    assert res["failed"] == 0 and res["attempted"] > 0
    assert "setup_s" in res["metrics"]
    assert set(res["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    # the numbers compared come last, on stderr too
    assert list(res)[-1] == "check"
    assert err.strip().splitlines()[-1].startswith("[bench] check ")


def test_traced_run_reads_host_metrics(run_tiny):
    res, _ = run_tiny("tiny-granite.closed", seed=SEEDS[1], seconds=1.0,
                      trace=1)
    assert res["correct"] is True
    m = res["metrics"]
    assert m["sched_gap_ms.closed"]["value"] >= 0
    # the CPU has no TPU plane: device metrics are left out, never 0
    assert not {"idle_share.closed", "mfu.decode", "decode_step_ms"} & set(m)
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert "breakdown" in res


@pytest.mark.parametrize("cell", ["tiny-granite.open", "tiny-mamba.open"])
def test_reference_matches_float32_program(tiny_tree, run_tiny, cell):
    """The second witness: run in float32, the program serves exactly the
    reference's best token at every position, so the references follow
    the program's semantics (routing, capacity drops, SSD, caches)."""
    _, bench = tiny_tree
    for p in (bench / "configs").glob("tiny-*.json"):
        d = json.loads(p.read_text())
        d["arch"]["dtype"] = "float32"
        p.write_text(json.dumps(d))
    for seed in SEEDS:
        res, _ = run_tiny(cell, seed=seed, seconds=1.0)
        assert res["check"]["max_logit_gap"]["value"] == 0.0
        assert res["check"]["mean_logit_gap"]["value"] == 0.0


@pytest.mark.parametrize("seed", SEEDS)
def test_control_fails_the_limit(run_tiny, seed):
    """The fp8 control in the program's place comes out not correct, while
    the tokens the program served in the same run pass the same limits."""
    res, _ = run_tiny("tiny-mamba.open", seed=seed, seconds=1.0, control=1)
    assert res["correct"] is False
    gaps = ("max_logit_gap", "mean_logit_gap")
    assert any(res["check"][k]["value"] > res["check"][k]["limit"]
               for k in gaps)
    assert all(res["served"][k] <= res["check"][k]["limit"] for k in gaps)
    assert list(res)[-1] == "check"


def _alter_token(self, inputs, outputs, app):
    class Tap:
        def write(self, out):
            out = out.copy()
            out[:, 3] = (out[:, 3] + 1) % 2048
            for o in outputs:
                o.write(out)
    return REAL_DECODE(self, inputs, [Tap()], app)


def _half_batch(self, inputs, outputs, app):
    class Tap:
        def write(self, out):
            out = out.copy()
            half = len(out) // 2
            out[half:] = out[:half]
            for o in outputs:
                o.write(out)
    return REAL_DECODE(self, inputs, [Tap()], app)


def _state_unchanged(cfg):
    prefill, decode_one = REAL_STEPS(cfg)

    def stuck(params, cache, tok, pos):
        tok, _ = decode_one(params, cache, tok, pos)
        return tok, cache
    return prefill, stuck


REAL_DECODE = lm_serve.Deployment._decode
REAL_STEPS = lm_serve.serving_steps


@pytest.mark.parametrize("cell", ["tiny-granite.open", "tiny-mamba.open"])
@pytest.mark.parametrize("fault", ["token_altered", "half_batch",
                                   "state_unchanged"])
def test_planted_fault_is_not_correct(run_tiny, monkeypatch, cell, fault):
    if fault == "token_altered":
        monkeypatch.setattr(lm_serve.Deployment, "_decode", _alter_token)
    elif fault == "half_batch":
        monkeypatch.setattr(lm_serve.Deployment, "_decode", _half_batch)
    else:
        monkeypatch.setattr(lm_serve, "serving_steps", _state_unchanged)
    res, _ = run_tiny(cell, seed=SEEDS[2], seconds=1.0)
    assert res["correct"] is False
    assert any(c["value"] > c["limit"]
               for k, c in res["check"].items() if k.endswith("_logit_gap"))
