"""The measurement path refuses to run off a TPU: exit non-zero, no result."""
import json
import os
import subprocess
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))


def test_run_exits_nonzero_on_cpu_and_prints_no_result():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = json.load(f)["workloads"][0]["name"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", cell, "--seed",
         "2147483700", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
    assert "TPU" in p.stderr
