"""CPU tests of the benchmark harness: the manifest and its files, names
and units, the work counts against hand-worked numbers, the trace
reduction on a synthetic trace, and the refusal of a CPU."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH / "tests"), str(BENCH.parent / "src")]

from harness import bench, counts, manifest, peaks, tracing, traffic  # noqa: E402
import tiny  # noqa: E402

REAL = manifest.load()
CELLS = [w["name"] for w in REAL["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
def test_every_cell_resolves_by_name(workload):
    cell = manifest.resolve(workload)
    assert cell.config["source"].startswith("https://")
    traffic.Mix.parse(cell.traffic)
    assert cell.limits["max_logit_gap"] > 0
    names = {m.name for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer and all(callable(m.reader.read)
                                  for m in cell.per_layer)
    for fn in ("program_fields", "request_inputs", "logits", "work"):
        assert callable(getattr(cell.reference, fn))


def test_new_entries_resolve_from_new_files_only(tmp_path):
    root = tiny.make(tmp_path)
    for workload, mix in tiny.CELLS.items():
        cell = manifest.resolve(workload, root)
        assert cell.traffic_name == mix
        assert {m.name for m in cell.per_layer} == {
            m["name"] for m in REAL["per_layer"]}
    # the benchmark's own files are the real ones, unedited
    for rel in ("harness/bench.py", "configs/qwen3-1.7b.py"):
        assert (root / "perfbench" / rel).read_bytes() == \
            (BENCH / rel).read_bytes()


def test_missing_file_is_refused(tmp_path):
    root = tiny.make(tmp_path)
    (root / "perfbench" / "traffic" / "tiny.json").unlink()
    with pytest.raises(manifest.ManifestError):
        manifest.resolve("tiny-qwen.tiny", root)


def test_names_and_units_use_allowed_characters():
    for m in REAL["end_to_end"] + REAL["per_layer"]:
        assert manifest.NAME_RE.match(m["name"]), m["name"]
        assert manifest.UNIT_RE.match(m["unit"]), m["unit"]
    for w in REAL["workloads"]:
        for k in ("name", "config", "traffic"):
            assert manifest.NAME_RE.match(w[k])
    for bad in ("tokens per s", "a/b", "µs", ".x" * 40):
        assert not manifest.NAME_RE.match(bad)
    assert not manifest.UNIT_RE.match("tokens per second")


def test_bad_name_is_refused(tmp_path):
    root = tiny.make(tmp_path)
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["end_to_end"][0]["name"] = "tokens per s"
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    with pytest.raises(manifest.ManifestError):
        manifest.load(root)


def _work(workload):
    cell = manifest.resolve(workload)
    mix = traffic.Mix.parse(cell.traffic)
    return cell.reference.work(cell.config, mix.clients, mix.prompt_tokens,
                               mix.output_tokens)


def test_qwen3_decode_step_counts_by_hand():
    f, b = _work("qwen3-1.7b.chat")["decode_steps"][0]
    # one token, one layer: projections 2*2048*128*(2*16+2*8), the read of
    # 513 live positions 4*513*16*128, SwiGLU 3*2*2048*6144
    layer = 2 * 2048 * 128 * 48 + 4 * 513 * 16 * 128 + 3 * 2 * 2048 * 6144
    assert layer == 104_865_792
    assert f == 32 * (28 * layer + 2 * 2048 * 151936) == 113_874_305_024
    # bf16 weights (1,720,574,976 params, the tied head read once), the
    # 32 new tokens' embedding rows, live int8 K/V rows (128 codes + a
    # 4-byte scale) of 513 positions, and the new rows written
    params = 28 * (2 * 2048 * 24 * 128 + 3 * 2048 * 6144 + 2 * 2048
                   + 2 * 128) + 151936 * 2048 + 2048
    assert params == 1_720_574_976
    kv_row = 28 * 32 * 8 * 132 * 2
    assert b == 2 * params + 32 * 2048 * 2 + kv_row * 513 + kv_row
    assert b == 4_413_949_952


def test_qwen3_prefill_counts_by_hand():
    f = _work("qwen3-1.7b.rag-4k")["prefill_flops"]
    # per layer and request: the projections of 4096 rows, the causal
    # query-key pairs 4096*4097/2 at 4*16*128 operations each, SwiGLU;
    # then the head on the last position only
    layer = (2 * 4096 * 2048 * 128 * 48 + 4 * (4096 * 4097 // 2) * 16 * 128
             + 3 * 2 * 4096 * 2048 * 6144)
    assert f == 4 * (28 * layer + 2 * 2048 * 151936)
    assert f == 53_880_438_128_640


def test_decode_counts_grow_with_the_live_cache_only():
    steps = _work("qwen3-1.7b.rag-4k")["decode_steps"]
    df = [b[0] - a[0] for a, b in zip(steps, steps[1:])]
    db = [b[1] - a[1] for a, b in zip(steps, steps[1:])]
    # one more live row per step: 4*16*128 operations and 8 K + 8 V rows
    # of 132 bytes per layer and request, nothing for padded capacity
    assert set(df) == {4 * 28 * 16 * 128 * 4}
    assert set(db) == {28 * 4 * 8 * 132 * 2}


def test_least_seconds_takes_the_larger_bound():
    p = peaks.PEAKS["TPU v5 lite"]
    assert counts.least_seconds(197e12, 0, p) == pytest.approx(1.0)
    assert counts.least_seconds(0, 819e9, p) == pytest.approx(1.0)
    assert counts.least_seconds(197e12, 2 * 819e9, p) == pytest.approx(2.0)


def _synthetic_trace():
    E = tracing.Event
    dev = "/device:TPU:0"
    ops = [E("fusion.1", 0.0, 1.0, dev),
           E("while.3", 0.0, 2.0, dev),  # a loop spans its body's ops
           E("fusion.2", 0.5, 2.0, dev),
           E("decode_attention_pallas.7", 3.0, 4.0, dev),
           E("fusion.1", 6.0, 7.0, dev)]
    host = [E(bench.ANNOTATION, 0.0, 5.0, "python"),
            E(bench.ANNOTATION, 5.5, 8.0, "python"),
            E("PjitFunction(decode)", 2.2, 2.8, "python")]
    return tracing.Trace(ops, host)


def test_trace_reduction_busy_idle_kernels_and_gaps():
    tr = _synthetic_trace()
    assert tr.window(bench.ANNOTATION) == (0.0, 8.0)
    assert tr.busy_s(0.0, 8.0) == pytest.approx(4.0)
    assert tr.busy_s(0.5, 3.5) == pytest.approx(2.0)
    kernel = ("decode_attention_pallas",)
    assert tr.kernel_s(kernel, 0.0, 8.0) == pytest.approx(1.0)
    assert tr.kernel_s(kernel, 3.5, 8.0) == pytest.approx(0.5)
    assert tr.kernel_s(("decode_attention",), 0.0, 8.0) == 0
    assert tr.idle_within(tr.spans(bench.ANNOTATION)) == pytest.approx(3.5)
    assert dict(tr.idle_gaps(0.0, 8.0)) == pytest.approx(
        {"host": 2.0, bench.ANNOTATION: 1.0, "PjitFunction(decode)": 1.0})
    assert tr.top_ops(0.0, 8.0)[0] == ("fusion", pytest.approx(3.5))
    assert tracing.op_name(
        "%fusion.131 = bf16[32,6144]{1,0} fusion(bf16[28,2048,6144] "
        "%decode_attention_pallas.5)") == "fusion.131"
    # busy time is averaged over the devices
    two = tracing.Trace(tr.device_ops + [
        tracing.Event("fusion.9", 0.0, 8.0, "/device:TPU:1")], tr.host)
    assert two.busy_s(0.0, 8.0) == pytest.approx(6.0)


def test_layer_readers_on_a_synthetic_trace():
    cell = manifest.resolve("qwen3-1.7b.rag-4k")
    mix = traffic.Mix.parse(cell.traffic)
    work = {"prefill_flops": 0, "decode_steps": [(0, 0)] * 15,
            "kernels": {"attention_decode": (0, 0.5 * 819e9)}}
    batches = [bench.Batch(0, 0.0, 5.0, None, False, True),
               bench.Batch(1, 5.5, 8.0, None, False, True)]
    run = bench.Run(cell, peaks.PEAKS["TPU v5 lite"], mix, work, batches,
                    {"prefill_s": (1.0, 2), "decode_step_s": (3.0, 30)},
                    _synthetic_trace(), (0.0, 8.0))
    got = {m.name: m.reader.read(run) for m in cell.per_layer}
    assert got["idle_share"] == pytest.approx(50.0)
    assert got["serve.host_ms_per_step"] == pytest.approx(3500.0 / 30)
    # least time 0.5 s per batch, two batches, 1 s of kernel time
    assert got["attention_decode_roofline"] == pytest.approx(100.0)
    assert got["serve.prefill_ms"] == pytest.approx(500.0)
    assert got["serve.decode_step_ms"] == pytest.approx(100.0)


def test_latency_reader_takes_the_tail_of_all_requests():
    cell = manifest.resolve("qwen3-1.7b.chat")
    mix = traffic.Mix.parse(cell.traffic)
    reader = {m.name: m.reader for m in cell.per_layer}["serve.latency_p95_s"]
    # ten batches of 32, one of them stalled: the slowest 10% of requests
    # wait 5.7 s, so the 95th percentile is that batch's time
    batches = [bench.Batch(i, 10.0 * i, 10.0 * i + (5.7 if i == 7 else 3.7),
                           None, False, False) for i in range(10)]
    run = bench.Run(cell, peaks.PEAKS["TPU v5 lite"], mix, {}, batches, {})
    assert reader.read(run) == pytest.approx(5.7)
    # among 20 batches the stalled one holds the slowest 5% of 640
    # requests: the 95th percentile lies 5% of the way from 3.7 to 5.7
    batches += [bench.Batch(i, 10.0 * i, 10.0 * i + 3.7, None, False, False)
                for i in range(10, 20)]
    assert reader.read(run) == pytest.approx(3.8)


def test_a_cpu_is_refused():
    import jax

    with pytest.raises(peaks.DeviceRefused):
        peaks.check_devices(jax.devices(), 1)


def _run_py(cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", CELLS[0],
         "--seed", str(2**33 + 1), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_on_a_cpu_prints_no_result_and_fails():
    r = _run_py(BENCH.parent)
    assert r.returncode == 2, r.stderr[-2000:]
    assert r.stdout.strip() == ""
    assert "TPU" in r.stderr


def test_run_without_the_program_fails(tmp_path):
    shutil.copy(BENCH.parent / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run_py(tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
