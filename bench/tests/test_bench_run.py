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


def test_kernel_leaves_named_by_the_served_programs_instructions():
    """Each served program's kernels, by the instruction names a v5e
    compile gave them (the serving fixture's scope maps): the leaf of
    every ``tsmm_<leaf>.<n>`` custom call; other ops are not kernels."""
    sys.path.insert(0, str(ROOT))
    from bench import run as R
    scopes = json.loads((ROOT / "bench" / "tests" / "data" /
                         "serve_fixture_scopes.json").read_text())
    for program, ops in scopes.items():
        lines = [f"HloModule jit_{program}, is_scheduled=true"]
        for op, path in ops.items():
            call = ('custom-call(%p), custom_call_target="tpu_custom_call"'
                    if op.startswith("tsmm_") else "fusion(%p), kind=kLoop")
            lines.append(f'  %{op} = bf16[4,512]{{1,0}} {call}, '
                         f'metadata={{op_name="{path}"}}')
        n, leaves = R.kernel_leaves("\n".join(lines))
        kernels = [op for op in ops if op.startswith("tsmm_")]
        assert n == len(kernels)
        assert leaves == {op[len("tsmm_"):].split(".")[0] for op in kernels}
    # the largest prompt bucket runs K and V outside the kernels
    assert R.kernel_leaves("\n".join(
        f'  %{op} = bf16[4,512]{{1,0}} custom-call(%p), '
        f'custom_call_target="tpu_custom_call"'
        for op in scopes["prefill_row_b4_t64"] if op.startswith("tsmm_"))
    )[1] == {"wq", "wo", "w_gate", "w_up", "w_down", "head"}
