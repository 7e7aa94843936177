"""Fixtures of the benchmark's CPU tests (helpers in ``bench_testlib``)."""
import json
from types import SimpleNamespace

import pytest

from bench_testlib import make_tree


@pytest.fixture
def tiny_tree(tmp_path):
    return tmp_path, make_tree(tmp_path)


@pytest.fixture
def run_tiny(tiny_tree, monkeypatch, capsys):
    """``run_tiny(cell, seed, seconds, trace=0, control=0)`` runs a tiny
    cell on the CPU and returns its parsed result line and its stderr."""
    import jax
    from harness import cell as cell_mod
    import repro.launch.compile_cache as cc

    root, bench = tiny_tree
    monkeypatch.setattr(cc, "setup_compile_cache", lambda: None)
    before = jax.config.jax_persistent_cache_min_compile_time_secs

    def run(cell, seed=7, seconds=1.0, trace=0, control=0):
        args = SimpleNamespace(workload=cell, seed=seed, seconds=seconds,
                               trace=trace, control=control)
        rc = cell_mod.run(root, args, 0.0, bench_dir=bench,
                          devices=jax.devices()[:1],
                          peaks={"bf16_flops_per_s": 1e12})
        out, err = capsys.readouterr()
        assert rc == 0
        return json.loads(out.strip().splitlines()[-1]), err

    yield run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", before)
