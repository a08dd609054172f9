"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
                         --trace <0|1>

from the root of a checkout.  ``BENCHMARK.json`` names the cells; the last
line of standard output is the result, and a run that finds no TPU, or
fewer chips than the cell asks for, exits non-zero without one.  JAX's
persistent compilation cache is kept in ``bench/.jax_cache`` of the
checkout, so only a checkout's first run of a cell compiles, and
nothing else is written outside the checkout but the profiler's trace of a
``--trace 1`` run, under ``TMPDIR`` and deleted when read.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# the checkout's root (for the ``bench`` package) and the program's sources,
# in place of this script's own directory
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / "bench" / ".jax_cache")
os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
os.environ.setdefault("TPU_LOG_DIR", "disabled")  # libtpu logs to /tmp

if __name__ == "__main__":
    from bench.harness import main

    sys.exit(main(t_start=T_START))
