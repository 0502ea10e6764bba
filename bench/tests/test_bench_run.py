"""``bench/run.py`` refuses, with no result line, where JAX finds no TPU."""
import os
import subprocess
import sys

from bench import harness


def test_run_exits_nonzero_without_a_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, str(harness.BENCH / "run.py"), "--workload",
         "lung2.fwd.m1", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr
