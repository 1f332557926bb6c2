"""bench/run.py measures on a TPU or not at all, and needs the program."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", "--workload", "mixtral-chat-c64",
                           "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0"],
                          cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_exits_non_zero_on_a_cpu():
    p = _run(REPO)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_exits_non_zero_with_only_the_benchmark_files(tmp_path):
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    for p in bench["paths"]:
        shutil.copytree(REPO / p, tmp_path / p, ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""
