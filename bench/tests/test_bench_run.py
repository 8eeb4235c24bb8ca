"""``run.py`` refuses to measure without a TPU: non-zero exit, no result."""
import os
import subprocess
import sys

from bench import spec


def test_exits_nonzero_on_cpu_without_a_result_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(spec.BENCH_DIR / "run.py"), "--workload",
         "qwen3-1.7b.chat", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=spec.REPO_DIR,
        timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "no TPU" in p.stderr
