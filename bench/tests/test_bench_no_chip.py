"""Without a TPU the benchmark fails, names the platform and prints no
result."""

import os
import subprocess
import sys

import harness


def test_run_without_tpu_exits_nonzero():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "smollm-360m.train-s4096-b8", "--seed", str(2 ** 33 + 1),
         "--seconds", "1", "--trace", "0"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert "needs a TPU, found platform 'cpu'" in p.stderr
    assert "{" not in p.stdout
