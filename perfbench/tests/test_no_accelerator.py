"""On a host with no accelerator the harness exits non-zero and prints
no result: it never falls back to the CPU."""
import os
import subprocess
import sys

from perfbench import spec


def test_cpu_only_run_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload",
         "deepseek-7b.q8_0.chat", "--seed", str(2 ** 33 + 1), "--seconds",
         "1", "--trace", "0"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode == 2, p.stderr[-2000:]
    assert "{" not in p.stdout and "metrics" not in p.stdout
    assert "no accelerator" in p.stderr
