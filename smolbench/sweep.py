#!/usr/bin/env python3
"""Knee sweep of an open-loop cell, in one process.

    python3 smolbench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates <r> [<r> ...]

Builds the cell's runtime once, then offers each Poisson rate in turn for
``--seconds`` (after the cell's warm traffic at that rate) and prints one
JSON line per rate: requests due, the share released by the window's
close, the served rate, and the 95th percentile from due time to release.
The highest rate whose backlog does not grow is the knee; a cell's
``rate_per_s`` is fixed from it once, and this is not part of a benchmark
run.
"""

import time

T_START = time.perf_counter()

import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"  # the checkout's own cache, never evicted

import numpy as np  # noqa: E402

from smolbench import harness, traffic  # noqa: E402


def main(argv=None) -> int:
    import argparse

    from repro.runtime import ClassificationQuery

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    if cell.traffic["arrivals"]["kind"] != "poisson":
        raise SystemExit(f"{cell.name} is not an open-loop cell")
    jax = harness.configure_jax()
    run = harness.Run(cell, args.seed, args.seconds, False, T_START)
    run.build()
    print(json.dumps({"workload": cell.name, "device": run.device, "setup": run.setup_parts}), flush=True)
    for rate in args.rates:
        client = traffic.Client(run.rt, run.items, ClassificationQuery, jax.profiler.TraceAnnotation)
        t0, t1 = traffic.run(client, {"kind": "poisson", "rate_per_s": rate}, len(run.items), args.seed,
                             run.traffic["warm"], run.seconds, lambda opening: None)
        due = [r for r in client.records.values() if t0 <= r[1] < t1]
        lat = [r[3] - r[1] if r[3] is not None else float("inf") for r in due]
        by_close = sum(1 for r in due if r[3] is not None and r[3] <= t1)
        served = sum(1 for r in client.records.values() if r[3] is not None and t0 <= r[3] <= t1)
        print(json.dumps({
            "rate_per_s": rate, "due": len(due), "completed_share": by_close / max(1, len(due)),
            "served_per_s": served / (t1 - t0),
            "p50_ms": float(np.percentile(lat, 50)) * 1e3, "p95_ms": float(np.percentile(lat, 95)) * 1e3,
            "failed": sum(1 for r in due if r[4] is not None),
        }), flush=True)
    run.rt.stop_serving()
    return 0


if __name__ == "__main__":
    sys.exit(main())
