"""The command line: no TPU, or no program beside the benchmark, means a
nonzero exit and no result line."""
import os
import shutil
import subprocess
import sys

import tinycells

RUN = os.path.join(tinycells.BENCH, "run.py")
ARGS = ["--workload", "sift1080p.b1", "--seed", str(2 ** 31 + 5),
        "--seconds", "1", "--trace", "0"]


def _run(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, script, *ARGS], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    p = _run(tinycells.ROOT, RUN)
    assert p.returncode == 3, p.stderr[-2000:]
    assert "no chip" in p.stderr
    assert '"correct"' not in p.stdout


def test_benchmark_alone_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(tinycells.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(tinycells.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, str(tmp_path / "bench" / "run.py"))
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
