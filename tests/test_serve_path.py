"""The serving path that ``chip_smoke.py`` drives on the chip, at smoke size.

* served tokens equal the plain reference (the same jitted prefill and
  decode steps run straight, with no engine) on both engines and through a
  resident EngineManager — the comparison ``chip_smoke.py`` makes;
* ``chip_smoke.py`` refuses a machine without a TPU and claims no device;
* the compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says and
  nowhere else, or to the fixed in-repo directory when it is unset;
* ``launch.serve`` serves a registry config picked with ``--arch``.
"""
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.launch.compile_cache import DEFAULT_CACHE_DIR
from repro.launch.serve import make_prompts, reference_tokens, run_serving
from repro.models import model as M

ROOT = Path(__file__).resolve().parents[1]
ARCH = "granite_moe_3b_a800m"
SHAPE = dict(num_requests=4, microbatch=2, prompt_len=8, decode_steps=4)


def _run(args, env_extra, cwd=ROOT, timeout=300):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=str(ROOT / "src"), **env_extra)
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


@pytest.mark.parametrize("execution,sessions", [
    ("compiled", 1), ("objects", 1), ("compiled", 2)],
    ids=["compiled", "objects", "manager-2-sessions"])
def test_served_tokens_equal_the_reference(execution, sessions):
    cfg = get_smoke_config(ARCH)
    res = run_serving(cfg, execution=execution, sessions=sessions,
                      num_nodes=2, **SHAPE)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    want = reference_tokens(
        cfg, params, make_prompts(cfg, SHAPE["num_requests"],
                                  SHAPE["prompt_len"]),
        microbatch=SHAPE["microbatch"], decode_steps=SHAPE["decode_steps"])
    assert want.shape == (SHAPE["num_requests"], SHAPE["decode_steps"])
    np.testing.assert_array_equal(res["responses"], want)
    if sessions > 1:
        assert res["template_hits"] == sessions - 1


def test_chip_smoke_refuses_the_cpu(tmp_path):
    out = _run([str(ROOT / "chip_smoke.py")], {"JAX_PLATFORMS": "cpu"},
               cwd=tmp_path)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert out.stdout == ""      # no device line, no result line


def test_compile_cache_goes_where_the_environment_says(tmp_path):
    cache = tmp_path / "cache"
    before = (sorted(DEFAULT_CACHE_DIR.rglob("*"))
              if DEFAULT_CACHE_DIR.is_dir() else [])
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch.compile_cache import setup_compile_cache\n"
        "print(setup_compile_cache())\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
        "jax.jit(lambda x: x * 2 + 1)(jnp.arange(8.0)).block_until_ready()\n")
    out = _run(["-c", code], {
        "JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": str(cache),
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
        "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "0"})
    assert out.returncode == 0, out.stderr
    assert out.stdout.splitlines() == [str(cache), str(cache)]
    assert any(p.is_file() for p in cache.rglob("*"))
    after = (sorted(DEFAULT_CACHE_DIR.rglob("*"))
             if DEFAULT_CACHE_DIR.is_dir() else [])
    assert after == before


def test_compile_cache_defaults_to_the_fixed_repo_directory():
    assert DEFAULT_CACHE_DIR == ROOT / ".jax_cache"
    code = (
        "import jax\n"
        "from repro.launch.compile_cache import setup_compile_cache\n"
        "print(setup_compile_cache())\n"
        "print(jax.config.jax_compilation_cache_dir)\n")
    out = _run(["-c", code], {"JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr
    assert out.stdout.splitlines() == [str(DEFAULT_CACHE_DIR)] * 2


def test_serve_cli_picks_the_arch(tmp_path):
    out = _run(["-m", "repro.launch.serve", "--arch", ARCH,
                "--execution", "compiled", "--requests", "4",
                "--microbatch", "2", "--prompt", "8", "--decode", "4"],
               {"JAX_PLATFORMS": "cpu",
                "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")})
    assert out.returncode == 0, out.stderr
    assert "granite-smoke: 4 requests x 4 tokens" in out.stdout
    assert "responses (4, 4)" in out.stdout
