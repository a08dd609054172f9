"""The command as the benchmark is run: on a machine with no TPU it exits
non-zero and prints no result."""
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "kron-s22.pr",
         "--seed", str((1 << 33) + 1), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "no TPU" in p.stderr
    assert "platform=cpu" in p.stderr
