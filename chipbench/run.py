#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Exits 2 and prints no result when JAX finds no TPU, or fewer chips than
the cell asks for.  The last line of standard output is the run's JSON
result; the numbers compared, each with its limit, are the last lines
of standard error.  JAX's compilation cache is kept in ``.jax_cache/`` at
the root of the checkout.
"""
import time

T0 = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
# every program, however quick to compile, comes from the cache after the
# first run, so set-up repeats
os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"

from chipbench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t0=T0))
