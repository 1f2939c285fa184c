"""whisper-medium's reference against the program, at a tiny size on the
CPU (``tiny_whisper.py``): each request's own audio served through
``generate(audio=...)``, prefill then decode over an int8 K/V cache, and
the float32 reference's full forward over the same audio, prompt and
served tokens; a whole run of the tiny cell; and the cell's work counts.

The program runs in float32 here, so the int8 cache is its one precision
step below the reference."""
from __future__ import annotations

import sys
import time
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH / "tests"), str(BENCH.parent / "src")]

from harness import bench, counts, manifest, peaks, traffic  # noqa: E402
import tiny_whisper  # noqa: E402

SEEDS = (1, 3, 2**40 + 5)
#: largest logit error over the largest reference logit. The int8 cache
#: rounds each K/V row to 1/254 of its absmax, and the program read at most
#: 0.0087 over 8 requests of 4 seeds (CPU readings); the reference with its
#: K/V rounded to int4 (18 times coarser) read at least 0.101. 0.03 leaves
#: over three times of room on each side.
TOL = 0.03


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    return manifest.resolve(tiny_whisper.CELL, tiny_whisper.make(
        tmp_path_factory.mktemp("perfbench")))


def _served_and_program_logits(cell, model, params, mix, seed):
    """Tokens ``generate`` serves for the seed's first batch, and the
    program's logits along them: its prefill over the batch's audio, then
    its int8-cache decode step, teacher-forced on the served tokens."""
    import jax.numpy as jnp

    from repro.launch import serve

    B, P, G = mix.clients, mix.prompt_tokens, mix.output_tokens
    prompts = jnp.asarray(traffic.prompts(mix, model.cfg.vocab_size, seed, 0))
    inputs = bench.batch_inputs(cell, mix, seed, 0)
    audio = jnp.asarray(inputs["audio"])
    cache_len = serve.resolve_cache_len(model.cfg, P + G, P, G)
    toks, _ = serve.generate(model, params, prompts, audio=audio, gen_len=G,
                             cache_len=cache_len)
    toks = np.asarray(toks)
    logits, cache = serve.prefill_cache(model, params, prompts, audio=audio,
                                        cache_len=cache_len, gen_len=G)
    _, decode = serve._jitted(model)
    got = [np.asarray(logits[:, -1])]
    for i in range(G - 1):
        logits, cache = decode(params, cache, jnp.asarray(toks[:, i:i + 1]),
                               jnp.int32(P + i))
        got.append(np.asarray(logits[:, -1]))
    return np.asarray(prompts), inputs, toks, np.stack(got, 1)


@pytest.mark.parametrize("seed", SEEDS)
def test_served_logits_match_the_reference_and_int4_kv_does_not(cell, seed):
    mix = traffic.Mix.parse(cell.traffic)
    model = bench.build(cell)
    params = bench.make_weights(cell, model, seed)
    prompts, inputs, toks, got = _served_and_program_logits(
        cell, model, params, mix, seed)
    # the teacher-forced logits are the ones generate chose its tokens by
    np.testing.assert_array_equal(got.argmax(-1), toks)
    ref = cell.reference
    for b in range(mix.clients):
        extra = bench.slot_inputs(inputs, b)
        want = ref.logits(params, cell.config, extra, prompts[b], toks[b])
        scale = np.abs(want).max()
        assert np.abs(got[b] - want).max() / scale < TOL, (seed, b)
        low = ref.logits(params, cell.config, extra, prompts[b], toks[b],
                         lower="int4_kv")
        assert np.abs(low - want).max() / scale > TOL, (seed, b)
    # two slots with different audio: the same prompt and tokens read
    # other logits over slot 1's audio than over slot 0's
    mine = ref.logits(params, cell.config, bench.slot_inputs(inputs, 0),
                      prompts[0], toks[0])
    other = ref.logits(params, cell.config, bench.slot_inputs(inputs, 1),
                       prompts[0], toks[0])
    assert np.abs(other - mine).max() / np.abs(mine).max() > 3 * TOL


def test_request_inputs_are_each_requests_30s_of_mels(cell):
    real = manifest.resolve("whisper-medium.transcribe-30s")
    mix = traffic.Mix.parse(real.traffic)
    audio = real.reference.request_inputs(
        real.config, traffic.inputs_rng(2**33 + 1, 0), mix.clients)["audio"]
    assert audio.shape == (16, 3000, 80) and audio.dtype == np.float32
    assert -1 <= audio.min() and audio.max() < 1
    assert not np.array_equal(audio[0], audio[1])


@pytest.fixture
def _on_the_cpu(monkeypatch):
    from repro import compile_cache

    monkeypatch.setattr(compile_cache, "enable", lambda: None)
    monkeypatch.setattr(peaks, "check_devices",
                        lambda devices, chips: peaks.PEAKS["TPU v5 lite"])
    monkeypatch.setattr(bench, "_decode_impls", lambda: {"pallas"})


@pytest.mark.parametrize("trace", [False, True])
def test_a_run_of_the_tiny_cell_is_correct(tmp_path, _on_the_cpu, trace):
    root = tiny_whisper.make(tmp_path)
    r = bench.run_cell(tiny_whisper.CELL, 2**33 + 7, 0.5, trace,
                       t_start=time.perf_counter(), root=root)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0, r
    if not trace:
        assert set(r["metrics"]) == {"tokens_per_s", "setup_s"}
    else:  # no chip in the trace: the device readers read nothing
        assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}


def test_the_cells_work_by_hand():
    cell = manifest.resolve("whisper-medium.transcribe-30s")
    mix = traffic.Mix.parse(cell.traffic)
    w = cell.reference.work(cell.config, mix.clients, mix.prompt_tokens,
                            mix.output_tokens)
    # the frontend over 16 clips: conv1 3000 rows of 3*80 taps into 1024,
    # conv2 1500 rows of 3*1024; bf16 in, taps, bias, out
    (f1, b1), (f2, b2) = w["kernels"]["sliding_conv1d"]
    assert f1 == 2 * 16 * 3000 * 240 * 1024 == 23_592_960_000
    assert f2 == 2 * 16 * 1500 * 3072 * 1024 == 150_994_944_000
    assert b1 == 2 * (16 * 3000 * 80 + 240 * 1024 + 1024 + 16 * 3000 * 1024)
    assert b2 == 2 * (16 * 3000 * 1024 + 3072 * 1024 + 1024
                      + 16 * 1500 * 1024)
    # each decode step reads every encoder position's int8 K and V rows
    # (64 codes and a 4-byte scale) in 24 layers, 16 heads, 16 slots
    cross = 24 * 16 * 16 * 68 * 2 * 1500
    assert cross == 1_253_376_000
    f, b = w["kernels"]["cross_attention_decode"]
    qo = 24 * 16 * 2 * 16 * 64 * 4
    assert b == 127 * (cross + qo)
    assert f == 127 * 16 * 24 * 4 * 1500 * 16 * 64
    # the cross read is more than half of a step's bytes
    step_f, step_b = w["decode_steps"][0]
    assert cross / step_b > 0.5
    assert len(w["decode_steps"]) == 127
    # a step is bound by bytes, the conv2 kernel by operations
    p = peaks.PEAKS["TPU v5 lite"]
    assert counts.least_seconds(step_f, step_b, p) == step_b / 819e9
    assert counts.least_seconds(f2, b2, p) == f2 / 197e12
