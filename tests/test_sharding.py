"""Sharding-rule tests + a miniature multi-device dry-run.

Multi-device cases run in a SUBPROCESS with
``--xla_force_host_platform_device_count=8`` so the main pytest process
keeps its single-device view (jax locks device count at first init).
"""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

SRC = str(Path(__file__).resolve().parents[1] / "src")


def run_child(code: str) -> dict:
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys, json
        sys.path.insert(0, %r)
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
    """) % SRC + textwrap.dedent(code)
    res = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=560)
    assert res.returncode == 0, res.stderr[-3000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


class TestParamRules:
    def test_tp_and_fallbacks(self):
        out = run_child("""
        from repro.configs import get_smoke_config, abstract_params
        from repro.sharding import param_pspecs
        import dataclasses
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        cfg = dataclasses.replace(get_smoke_config("nemotron_4_15b"),
                                  num_heads=8, num_kv_heads=2, d_ff=64,
                                  sharding_strategy="fsdp")
        specs, decisions = param_pspecs(cfg, abstract_params(cfg), mesh)
        flat = {jax.tree_util.keystr(p): s for p, s
                in jax.tree_util.tree_flatten_with_path(
                    specs, is_leaf=lambda x: isinstance(x, P))[0]}
        report = {
          "wq": str(flat["['layers']['attn']['wq']"]),
          "wk": str(flat["['layers']['attn']['wk']"]),
          "w1": str(flat["['layers']['mlp']['w1']"]),
          "embed": str(flat["['embed']"]),
          "decisions": decisions,
        }
        print(json.dumps(report))
        """)
        # heads 8 % 4 == 0 -> sharded; kv 2 % 4 != 0 -> replicated + logged
        assert "'model'" in out["wq"]
        assert "'model'" not in out["wk"]
        assert any("kvheads" in d for d in out["decisions"])
        assert "'model'" in out["w1"]       # ffn TP
        assert "'data'" in out["wq"] or "'data'" in out["embed"]  # fsdp

    def test_mini_dryrun_compiles_and_has_collectives(self):
        """Lower + compile a real train step on an 8-device mesh."""
        out = run_child("""
        import dataclasses
        from repro.configs import get_smoke_config, abstract_params
        from repro.sharding import batch_pspecs, param_pspecs
        from repro.sharding.rules import opt_pspecs
        from repro.train.steps import TrainState, make_train_step, \\
            train_state_init
        from repro.roofline import collective_bytes_from_hlo
        from jax.sharding import NamedSharding
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        cfg = dataclasses.replace(get_smoke_config("codeqwen15_7b"),
                                  num_heads=4, num_kv_heads=4, d_ff=64,
                                  vocab_size=256)
        step = make_train_step(cfg, num_microbatches=2, remat=True)
        state = jax.eval_shape(
            lambda: train_state_init(cfg, jax.random.PRNGKey(0)))
        pspecs, _ = param_pspecs(cfg, abstract_params(cfg), mesh)
        sspecs = TrainState(pspecs, opt_pspecs(pspecs, state.opt), None)
        batch = {"tokens": jax.ShapeDtypeStruct((8, 32), jnp.int32),
                 "labels": jax.ShapeDtypeStruct((8, 32), jnp.int32)}
        bspecs = batch_pspecs(cfg, batch, mesh)
        tos = lambda t: jax.tree.map(
            lambda s: NamedSharding(mesh, s), t,
            is_leaf=lambda x: isinstance(x, P))
        with jax.set_mesh(mesh):
            lowered = jax.jit(step, in_shardings=(tos(sspecs), tos(bspecs)),
                              donate_argnums=(0,)).lower(state, batch)
        compiled = lowered.compile()
        coll = collective_bytes_from_hlo(compiled.as_text())
        mem = compiled.memory_analysis()
        print(json.dumps({
            "total_collective_bytes": coll["total"],
            "all_reduce": coll["all-reduce"],
            "arg_bytes": int(mem.argument_size_in_bytes),
        }))
        """)
        # gradient DP sync must produce all-reduce traffic
        assert out["all_reduce"] > 0
        assert out["arg_bytes"] > 0

    def test_decode_cache_specs(self):
        out = run_child("""
        import dataclasses
        from repro.configs import get_smoke_config
        from repro.models import model as M
        from repro.sharding import cache_pspecs
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        cfg = dataclasses.replace(get_smoke_config("command_r_plus_104b"),
                                  num_heads=8, num_kv_heads=2)
        cache = jax.eval_shape(lambda: M.init_cache(cfg, 8, 64))
        specs = cache_pspecs(cfg, cache, mesh)
        print(json.dumps({"k": str(specs["kv"]["k"])}))
        """)
        # kv heads (2) don't divide model (4) -> flash-decoding seq sharding
        assert out["k"].count("'model'") == 1
        assert "None, 'model'" in out["k"] or "'model'," in out["k"]


class TestMeshFactory:
    def test_mesh_shapes(self):
        out = run_child("""
        # 8 host devices cannot back the 256/512-chip production meshes, but
        # the factory's SHAPE logic is what we check here.
        from repro.launch.mesh import make_production_mesh
        try:
            make_production_mesh()
            ok = True
        except Exception as e:
            ok = "requires" in str(e) or "devices" in str(e).lower()
        print(json.dumps({"graceful": bool(ok)}))
        """)
        assert out["graceful"]
