"""``bench/run.py`` refuses to measure without a TPU."""

import os
import shutil
import subprocess
import sys

from conftest import ROOT

ARGS = ["--workload", "table2.capacity-sweep", "--seed", "3",
        "--seconds", "1", "--trace", "0"]


def _run(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, str(script)] + ARGS, cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_exits_nonzero_with_no_result_on_the_cpu():
    p = _run(ROOT, ROOT / "bench" / "run.py")
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert not p.stdout.strip()


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    p = _run(tmp_path, tmp_path / "bench" / "run.py")
    assert p.returncode != 0
    assert not p.stdout.strip()
