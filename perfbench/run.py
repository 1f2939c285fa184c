#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout. The cells, metrics and bounds are in
``BENCHMARK.json``; how a run works is in ``harness/bench.py``. The last
line of standard output is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``compared``: each number the output check
compared, beside its limit); the last lines of standard error repeat the
compared numbers. Without a TPU listed in ``harness/peaks.py``, or with
fewer chips than the cell asks for, it prints no result and exits 2.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
CHECKOUT = BENCH.parent


def prepare() -> None:
    """Before JAX is imported: keep the compile cache at one fixed place
    inside the checkout, so only a cell's first run there compiles, with
    every program in it and nothing evicted; put the harness and the
    program on the path."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(
        CHECKOUT / ".cache" / "jax-compile")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    sys.path[:0] = [str(BENCH), str(CHECKOUT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    prepare()
    from harness import bench, peaks

    try:
        result = bench.run_cell(args.workload, args.seed, args.seconds,
                                bool(args.trace), t_start=T_START)
    except peaks.DeviceRefused as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 2
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
