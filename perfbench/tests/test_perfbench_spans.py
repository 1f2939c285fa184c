"""The serve-loop readers of the program's own spans, on synthetic traces:
the host gap of each decode step split by the span it falls in (its
launch, which is the dispatch and the wait read as one, its screen, its
sample and the rest of the step), and the
device time a prefill spends after its forward. Each reads nothing, and
the line leaves it out, where the trace has no chip or the program has no
such spans."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from harness import bench, manifest, peaks, tracing, traffic  # noqa: E402

SPAN_METRICS = ("serve.host_ms_per_step.dispatch",
                "serve.host_ms_per_step.screen",
                "serve.host_ms_per_step.sample",
                "serve.host_ms_per_step.other",
                "serve.prefill_cache_ms")
DEV = "/device:TPU:0"


def _ms(name, t0, t1, where=DEV):
    return tracing.Event(name, t0 / 1e3, t1 / 1e3, where)


def _host(name, t0, t1):
    return _ms(name, t0, t1, "python")


def _batch_trace():
    """One batch of 2 decode steps, in milliseconds. Device idle inside
    each decode part, step 1 + step 2: dispatch 5 + 2, wait 1 + 1,
    screen 3 + 2, sample 2.5 + 4, the rest of the step 1 + 2. Busy
    inside the prefill 28, inside its forward 15; the argmax still
    running when the prefill span closes counts to 40 ms only."""
    host = [_host(bench.ANNOTATION, 0, 100), _host("serve.generate", 1, 99),
            _host("serve.prefill", 2, 40),
            _host("serve.prefill.forward", 3, 20),
            _host("serve.prefill.cache", 21, 30),
            _host("serve.prefill.sample", 31, 39),
            _host("serve.decode_step", 41, 70),
            _host("serve.decode.dispatch", 42, 48),
            _host("serve.decode.wait", 48, 61),
            _host("serve.decode.screen", 61, 65),
            _host("serve.decode.sample", 65, 69),
            _host("serve.decode_step", 71, 95),
            _host("serve.decode.dispatch", 71, 74),
            _host("serve.decode.wait", 74, 86),
            _host("serve.decode.screen", 86, 88),
            _host("serve.decode.sample", 88, 93),
            _host("PjitFunction(decode)", 71.5, 73.5)]
    ops = [_ms("fusion.1", 4, 19), _ms("pad.2", 25, 34),
           _ms("reduce.3", 35, 37), _ms("argmax.4", 38, 42),
           _ms("while.5", 47, 60), _ms("reduce.6", 62, 63),
           _ms("argmax.7", 66, 67), _ms("select.8", 68, 68.5),
           _ms("while.5", 73, 85), _ms("argmax.7", 89, 90)]
    return tracing.Trace(ops, host)


def _read(trace):
    cell = manifest.resolve("qwen3-1.7b.chat")
    readers = {m.name: m.reader for m in cell.per_layer}
    mix = traffic.Mix(clients=1, prompt_tokens=4, output_tokens=3)
    run = bench.Run(cell, peaks.PEAKS["TPU v5 lite"], mix, {},
                    [bench.Batch(0, 0.0, 0.1, None, False, True)], {},
                    trace, (0.0, 0.1))
    return {name: readers[name].read(run)
            for name in SPAN_METRICS + ("serve.host_ms_per_step",)}


def test_span_readers_split_the_host_gap_on_a_synthetic_trace():
    got = _read(_batch_trace())
    # dispatch and wait together: (5 + 2) + (1 + 1) ms over 2 steps
    assert got["serve.host_ms_per_step.dispatch"] == pytest.approx(9 / 2)
    assert got["serve.host_ms_per_step.screen"] == pytest.approx(5 / 2)
    assert got["serve.host_ms_per_step.sample"] == pytest.approx(6.5 / 2)
    assert got["serve.host_ms_per_step.other"] == pytest.approx(3 / 2)
    assert got["serve.prefill_cache_ms"] == pytest.approx(28 - 15)
    # the whole batch's idle, 100 - 58.5 ms over 2 steps, holds the four
    # parts and the gaps outside the decode steps
    assert got["serve.host_ms_per_step"] == pytest.approx(41.5 / 2)


def test_span_readers_read_nothing_without_a_chip():
    tr = _batch_trace()
    got = _read(tracing.Trace([], tr.host))
    assert got == dict.fromkeys(got)  # None, as idle_share gives; not 0


def test_span_readers_read_nothing_without_the_programs_spans():
    tr = _batch_trace()
    parent = [e for e in tr.host if not e.name.startswith("serve.")]
    got = _read(tracing.Trace(tr.device_ops, parent))
    assert all(got[name] is None for name in SPAN_METRICS)
    assert got["serve.host_ms_per_step"] == pytest.approx(41.5 / 2)
