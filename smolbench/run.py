#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 smolbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The last line of standard output is one
JSON object (``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
with ``--trace 1`` also ``breakdown``; ``limits`` last); the numbers held
against their limits are also the last lines of standard error.  With
``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics.  Off a TPU the run is a dry run at a
tiny size that prints no result and exits 1.  JAX's compilation cache is
kept in ``.jax_cache/`` at the checkout's root.
"""

import time

T_START = time.perf_counter()  # set-up is timed from here

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"  # the checkout's own cache, never evicted

from smolbench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_start=T_START))
