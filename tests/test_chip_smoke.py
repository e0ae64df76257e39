"""The chip smoke script refuses to run without a TPU, its checks pass at
a small size on the CPU, and the entry points' compile cache goes where
it is told."""

import importlib.util
import os
import shutil
import subprocess
import sys

import jax

from repro.configs import get
from repro.launch import cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _smoke_module():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run_smoke(script, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    return subprocess.run([sys.executable, script], capture_output=True,
                          text=True, env=env, cwd=tmp_path, timeout=300)


def test_chip_smoke_fails_without_a_tpu(tmp_path):
    out = _run_smoke(SCRIPT, tmp_path)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "needs a TPU" in out.stderr


def test_chip_smoke_fails_outside_the_repository(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, alone)
    out = _run_smoke(str(alone), tmp_path)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout


def test_kernel_phase_passes_in_the_interpreter():
    smoke = _smoke_module()
    r = smoke.phase_kernels(gemm_shape=(256, 512, 256),
                            attn_shape=(1, 2, 256, 64), interpret=True)
    assert r["ok"], r
    assert [k["kernel"] for k in r["kernels"]] == [
        "matmul_grouped_W4", "matmul_grouped_W1", "matmul_dispersed",
        "flash_attention_causal"]


def test_serving_phase_matches_the_f32_reference_at_reduced_size():
    """The serving phase's decode-vs-reference check, on the reduced
    phi3-mini: bf16 decode through the cache against the f32 full-sequence
    forward of the same weights."""
    smoke = _smoke_module()
    r = smoke.phase_serving(cfg=get("phi3-mini-3.8b").reduced(),
                            serve_kw=dict(max_len=64))
    assert r["ok"], r["failures"]
    assert r["statuses"] == ["done"]
    assert r["checked_steps"] == smoke.CHECK_STEPS
    assert r["logit_rel_err_max"] < smoke.LOGIT_REL_TOL


def test_compile_cache_defers_to_the_environment(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(cache.ENV_VAR, str(tmp_path))
    assert cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_the_repo(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv(cache.ENV_VAR, raising=False)
    try:
        path = cache.use_compile_cache()
        assert path == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
