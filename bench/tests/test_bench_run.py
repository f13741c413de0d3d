"""``bench/run.py`` refuses to measure without its chip or its program."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def _run(cwd, root=ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, str(root / "bench" / "run.py"), "--workload",
         "qwen1.5-4b.chat", "--seed", "2147483659", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=120)


def test_no_tpu_exits_nonzero_without_a_result(tmp_path):
    out = _run(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "TPU" in out.stderr


def test_bare_benchmark_directory_exits_nonzero(tmp_path):
    """A directory with only BENCHMARK.json and the files under paths:
    the program is missing, so no run and no result."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in spec["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(tmp_path, root=tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
