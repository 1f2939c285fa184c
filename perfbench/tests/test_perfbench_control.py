"""The output check's control at a size a test run can hold: the float32
reference put in the program's place with its K/V rows one precision step
down (int4 for the served int8) must come out as not correct against the
cell's limit, by the run's own limit test, on every seed, while the
program's own served tokens come out correct on the same requests. (On
the chip the same comparison runs at each cell's own size, for every
control: ``perfbench/calibrate.py``.)"""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH / "tests"), str(BENCH.parent / "src")]

from harness import bench, check, manifest, traffic  # noqa: E402
import tiny  # noqa: E402

SEEDS = (1, 3, 2**40 + 5)


@pytest.mark.parametrize("workload", sorted(tiny.CELLS))
def test_control_fails_where_the_program_passes(tmp_path, workload):
    cell = manifest.resolve(workload, tiny.make(tmp_path))
    mix = traffic.Mix.parse(cell.traffic)
    model = bench.build(cell)
    for seed in SEEDS:
        got = bench.readings(cell, model, mix, seed, control=True)
        assert check.judge(got, cell.limits)[0], (seed, got)
        assert not check.judge(got["controls"]["int4_kv"], cell.limits)[0], \
            (seed, got)
