#!/usr/bin/env python3
"""Readings that the output check's limit is set from, for one cell.

    python3 perfbench/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 1,2,3] [--out <file.json>]

For each seed, in one process: the weights from the seed, a few whole
batches of the cell's own shape through ``repro.launch.serve.generate``
(as many as the run's sample needs), the same sample policy and
comparison as a benchmark run (``harness/check.py``), giving the widest
gap of the served tokens. For each control seed the float32 reference is
also run in the program's place with one stated precision one step down,
once for each control (``refmath.CONTROLS``: int8 weights, int4 K/V),
giving each control's widest gap. Every reading is judged by the same
limit test as a run (``check.judge``): the program's has to come out
correct, each control's not. The limit in ``cells/<cell>.json`` lies
between the largest served gap and the smallest control gap. Benchmark
runs never run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import run  # noqa: E402  (perfbench/run.py, beside this file)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    run.prepare()
    import jax

    from harness import bench, check, manifest, peaks, traffic

    cell = manifest.resolve(args.workload)
    peaks.check_devices(jax.devices(), cell.chips)
    mix = traffic.Mix.parse(cell.traffic)
    model = bench.build(cell)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    rows = []
    for seed in sorted(set(seeds) | controls):
        t = time.perf_counter()
        got = bench.readings(cell, model, mix, seed, control=seed in controls)
        got["correct"] = check.judge(got, cell.limits)[0]
        for c in got.get("controls", {}).values():
            c["correct"] = check.judge(c, cell.limits)[0]
        got.update(seed=seed, seconds=time.perf_counter() - t)
        print(json.dumps(got), flush=True)
        rows.append(got)
    served = [r["max_logit_gap"] for r in rows if r["seed"] in seeds]
    ctrl = {}
    for r in rows:
        for name, c in r.get("controls", {}).items():
            ctrl.setdefault(name, []).append(c)
    summary = {"workload": args.workload, "rows": rows,
               "lower": max(served) if served else None,
               "program_all_correct": all(r["correct"] for r in rows),
               "controls": {name: {"upper": min(c["max_logit_gap"] for c in cs),
                                   "any_correct": any(c["correct"]
                                                      for c in cs)}
                            for name, cs in ctrl.items()},
               "limit": cell.limits["max_logit_gap"],
               "seconds": time.perf_counter() - T_START}
    print(json.dumps({k: v for k, v in summary.items() if k != "rows"}))
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(summary, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
