"""Each request's inputs besides its prompt tokens, drawn by the harness
from the seed, reach the served entry as keywords and the reference as
the served request's own row, on the CPU.

A tiny configuration whose module is the tiny qwen one with a
``request_inputs`` that gives every request one float row stands in for
a model with audio; the served ``generate`` is wrapped to record what it
is given and to serve the prompts alone, and the reference's ``logits``
to record its ``extra``. A configuration with no such inputs (qwen3)
calls ``generate`` exactly as before."""
from __future__ import annotations

import contextlib
import json
import sys
import time
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH / "tests"), str(BENCH.parent / "src")]

from harness import bench, manifest, peaks, traffic  # noqa: E402
import tiny  # noqa: E402

SEED = 2**33 + 17
CELL = "tiny-inputs.tiny"
REQUEST_INPUTS = '''

def request_inputs(c: dict, rng, clients: int) -> dict:
    """One float row per request."""
    return {"audio": rng.random((clients, 3), dtype=np.float32)}
'''


@contextlib.contextmanager
def _recording(strip_inputs: bool):
    """Run with the CPU standing in for the chip, recording each call of
    the served ``generate`` (prompts, keyword inputs, bound keywords) and
    each reference call's (extra, prompt)."""
    from repro import compile_cache

    calls, refs = [], []
    serve_fn, resolve = bench.serve_fn, manifest.resolve

    def recording_serve_fn(model, params, mix):
        generate = serve_fn(model, params, mix)

        def served(prompts, **inputs):
            calls.append((np.asarray(prompts),
                          {k: np.asarray(v) for k, v in inputs.items()},
                          sorted(generate.keywords)))
            return generate(prompts, **({} if strip_inputs else inputs))

        return served

    def recording_resolve(workload, root=manifest.CHECKOUT):
        cell = resolve(workload, root)
        logits = cell.reference.logits

        def recorded(w, c, extra, prompt, served, *, lower=None):
            if lower is None:
                refs.append((extra, np.asarray(prompt)))
            return logits(w, c, extra, prompt, served, lower=lower)

        cell.reference.logits = recorded
        return cell

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(compile_cache, "enable", lambda: None)
        mp.setattr(peaks, "check_devices",
                   lambda devices, chips: peaks.PEAKS["TPU v5 lite"])
        mp.setattr(bench, "_decode_impls", lambda: {"pallas"})
        mp.setattr(bench, "serve_fn", recording_serve_fn)
        mp.setattr(manifest, "resolve", recording_resolve)
        yield calls, refs


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The tiny tree, plus (new files only) a configuration with inputs."""
    root = tiny.make(tmp_path_factory.mktemp("perfbench"))
    base = root / "perfbench"
    (base / "configs" / "tiny-inputs.json").write_text(json.dumps(tiny.QWEN))
    (base / "configs" / "tiny-inputs.py").write_text(
        (base / "configs" / "qwen3-1.7b.py").read_text() + REQUEST_INPUTS)
    (base / "cells" / f"{CELL}.json").write_text(
        json.dumps({"max_logit_gap": 0.06}))
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["configs"].append(dict(man["configs"][0], name="tiny-inputs",
                               file="perfbench/configs/tiny-inputs.json"))
    man["workloads"].append({"name": CELL, "config": "tiny-inputs",
                             "traffic": "tiny", "chips": 1,
                             "why": "CPU test"})
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    return root


@pytest.fixture(scope="module")
def served(root):
    """One run of the cell with inputs: what generate and the reference
    were given, the cell and its mix."""
    with _recording(strip_inputs=True) as (calls, refs):
        result = bench.run_cell(CELL, SEED, 2.0, False,
                                t_start=time.perf_counter(), root=root)
    cell = manifest.resolve(CELL, root)
    return calls, refs, result, cell, traffic.Mix.parse(cell.traffic)


def test_each_timed_batch_gets_its_own_inputs(served):
    calls, _, result, cell, mix = served
    timed = calls[bench.WARMUP_BATCHES:]
    assert len(timed) * mix.clients == result["attempted"] >= 2 * mix.clients
    for i, (_, inputs, _) in enumerate(timed):
        assert set(inputs) == {"audio"}
        assert inputs["audio"].shape == (mix.clients, 3)
        want = bench.batch_inputs(cell, mix, SEED, i)["audio"]
        assert np.array_equal(inputs["audio"], want)
    rows = [inputs["audio"].tobytes() for _, inputs, _ in timed]
    assert len(set(rows)) == len(rows)
    # the requests of one batch differ from each other too
    assert not np.array_equal(*timed[0][1]["audio"][:2])


def test_the_same_seed_gives_the_same_inputs(served):
    _, _, _, cell, mix = served
    for i in (traffic.WARMUP, 0, 3):
        a = bench.batch_inputs(cell, mix, SEED, i)["audio"]
        assert np.array_equal(a, bench.batch_inputs(cell, mix, SEED, i)
                              ["audio"])
        assert not np.array_equal(
            a, bench.batch_inputs(cell, mix, SEED + 1, i)["audio"])


def test_warm_up_inputs_differ_from_the_first_batch(served):
    calls, _, _, cell, mix = served
    warm = [inputs["audio"] for _, inputs, _ in
            calls[:bench.WARMUP_BATCHES]]
    want = bench.batch_inputs(cell, mix, SEED, traffic.WARMUP)["audio"]
    assert all(np.array_equal(w, want) for w in warm)
    assert not np.array_equal(want, calls[bench.WARMUP_BATCHES][1]["audio"])


def _served_rows(calls, refs):
    """For each reference call, its extra and the row of the batch whose
    prompts hold its prompt, at that slot."""
    pairs = []
    for extra, prompt in refs:
        where = [(inputs, slot) for prompts, inputs, _ in calls
                 for slot, row in enumerate(prompts)
                 if np.array_equal(row, prompt)]
        assert len({inputs["audio"][slot].tobytes()
                    for inputs, slot in where}) == 1, "prompt not unique"
        inputs, slot = where[0]
        pairs.append((extra, {k: v[slot] for k, v in inputs.items()}))
    return pairs


def test_reference_gets_the_served_slots_row(served):
    calls, refs, result, _, _ = served
    assert result["correct"] and refs
    for extra, row in _served_rows(calls[bench.WARMUP_BATCHES:], refs):
        assert set(extra) == {"audio"}
        assert extra["audio"].tobytes() == row["audio"].tobytes()


def test_readings_pass_the_same_inputs(root):
    with _recording(strip_inputs=True) as (calls, refs):
        cell = manifest.resolve(CELL, root)
        mix = traffic.Mix.parse(cell.traffic)
        got = bench.readings(cell, bench.build(cell), mix, SEED + 2)
    assert got["tokens"] > 0 and refs
    for i, (_, inputs, _) in enumerate(calls):
        assert np.array_equal(
            inputs["audio"], bench.batch_inputs(cell, mix, SEED + 2, i)
            ["audio"])
    for extra, row in _served_rows(calls, refs):
        assert extra["audio"].tobytes() == row["audio"].tobytes()


def test_inputs_without_a_row_per_client_are_refused(root):
    cell = manifest.resolve(CELL, root)
    mix = traffic.Mix.parse(cell.traffic)
    cell.reference.request_inputs = lambda c, rng, clients: {
        "audio": np.zeros((clients + 1, 3), np.float32)}
    with pytest.raises(ValueError, match="rows"):
        bench.batch_inputs(cell, mix, SEED, 0)


def test_qwen3_gives_no_inputs_and_generate_no_new_keyword(root):
    cell = manifest.resolve("qwen3-1.7b.chat")
    assert cell.reference.request_inputs(
        cell.config, traffic.inputs_rng(SEED, 0), 32) == {}
    with _recording(strip_inputs=False) as (calls, refs):
        result = bench.run_cell("tiny-qwen.tiny", SEED, 0.5, False,
                                t_start=time.perf_counter(), root=root)
    assert result["correct"] and calls and refs
    for _, inputs, bound in calls:
        assert inputs == {}
        assert bound == ["cache_len", "gen_len"]
    assert all(extra is None for extra, _ in refs)
