"""The benchmark's command refuses to run without a TPU, and in a
directory that holds only the benchmark's files, printing no result."""
import json
import os
import shutil
import subprocess
import sys

import benchkit


def _run(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "dcgan32.q8.b64",
         "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    for line in proc.stdout.splitlines():
        try:
            parsed = json.loads(line)
        except ValueError:
            continue
        assert "metrics" not in parsed and "correct" not in parsed
    return proc


def test_no_tpu_no_result():
    proc = _run(benchkit.REPO)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr


def test_benchmark_files_alone_no_result(tmp_path):
    shutil.copy(os.path.join(benchkit.REPO, "BENCHMARK.json"), tmp_path)
    for p in json.load(open(os.path.join(benchkit.REPO,
                                         "BENCHMARK.json")))["paths"]:
        shutil.copytree(os.path.join(benchkit.REPO, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    assert _run(tmp_path).returncode != 0
