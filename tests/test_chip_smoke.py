"""CPU rehearsal of ``chip_smoke.py``: its phases at the reduced encoder
config (same documents, seeds and checks; Pallas in interpret mode), its
refusal to run without a TPU, and where the compile cache goes."""

import importlib.util
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.configs.base import get_config

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(args, tmp_path, **env):
    full = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    full.update({"PYTHONPATH": str(ROOT / "src"), "JAX_PLATFORMS": "cpu"},
                **env)
    return subprocess.run([sys.executable, *args], capture_output=True,
                          text=True, env=full, cwd=tmp_path, timeout=300)


def test_cobi_phase_rehearsal(smoke):
    """Encoder stage -> admission -> packed farm drain -> host reduce, with
    readout validation, every chip-run check and the CPU quality floors."""
    cfg = get_config("sbert-paper").reduced()
    responses = smoke.run_cobi(cfg, 0, 2048)
    assert len(responses) == len(smoke.COBI_SIZES)
    decomposed = responses[smoke.COBI_SIZES.index(70)]
    assert decomposed.solver_invocations > smoke.COBI_CFG.iterations


def test_mcmc_phase_rehearsal(smoke):
    cfg = get_config("sbert-paper").reduced()
    responses = smoke.run_mcmc(cfg, 0, 2048)
    assert len(responses) == len(smoke.MCMC_SIZES)


def test_smoke_refuses_cpu(tmp_path):
    r = _run([str(ROOT / "chip_smoke.py")], tmp_path,
             JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "needs a TPU" in r.stderr


def test_compile_cache_placement(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, entries land there and nowhere
    else is configured; without it, the fixed in-checkout directory."""
    prog = textwrap.dedent("""
        import sys, jax, jax.numpy as jnp
        from repro.launch.compile_cache import CACHE_DIR, enable_compile_cache
        path = enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path, path
        if sys.argv[1] == "env":
            jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(8)).block_until_ready()
        else:
            assert path == str(CACHE_DIR), path
        print(path)
    """)
    cache = tmp_path / "cache"
    r = _run(["-c", prog, "env"], tmp_path, JAX_COMPILATION_CACHE_DIR=str(cache))
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.split()[-1] == str(cache)
    assert any(cache.iterdir())
    r = _run(["-c", prog, "fixed"], tmp_path)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.split()[-1] == str(ROOT / ".jax_compile_cache")
