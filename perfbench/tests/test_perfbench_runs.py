"""Whole runs of the tiny cells on the CPU, with the device check and the
check that the Pallas kernel served every decode read passed over: a
sound run comes out correct, in both modes, and a run whose served path
is broken underneath comes out not correct, once for each fault a serving
cell can have (a token altered where it is produced; a decode step that
returns its cache unchanged)."""
from __future__ import annotations

import sys
import time
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH / "tests"), str(BENCH.parent / "src")]

from harness import bench, peaks  # noqa: E402
import tiny  # noqa: E402

SEED = 2**33 + 11


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make(tmp_path_factory.mktemp("perfbench"))


@pytest.fixture(autouse=True)
def _on_the_cpu(monkeypatch):
    # keep the tests' compiles out of the checkout's persistent cache, and
    # let the CPU stand in for the chip
    from repro import compile_cache

    monkeypatch.setattr(compile_cache, "enable", lambda: None)
    monkeypatch.setattr(peaks, "check_devices",
                        lambda devices, chips: peaks.PEAKS["TPU v5 lite"])
    monkeypatch.setattr(bench, "_decode_impls", lambda: {"pallas"})


def _run(root, workload, trace=False, tamper=None, monkeypatch=None):
    if tamper is not None:
        serve_fn = bench.serve_fn
        monkeypatch.setattr(
            bench, "serve_fn",
            lambda model, params, mix: tamper(serve_fn(model, params, mix),
                                              model))
    return bench.run_cell(workload, SEED, 0.5, trace,
                          t_start=time.perf_counter(), root=root)


def token_altered(generate, model):
    """Every slot's second token is replaced by its neighbour id."""
    import jax.numpy as jnp

    def served(prompts):
        toks, done = generate(prompts)
        toks = np.asarray(toks).copy()
        toks[:, 1] = (toks[:, 1] + 1) % model.cfg.vocab_size
        return jnp.asarray(toks), done

    return served


def cache_unchanged(generate, model):
    """The decode step returns the cache it was given."""
    from repro.launch import serve

    prefill, decode = serve._jitted(model)
    serve._JITTED[model] = (
        prefill, lambda p, c, t, pos: (decode(p, c, t, pos)[0], c))
    return generate


@pytest.mark.parametrize("workload", sorted(tiny.CELLS))
def test_a_sound_run_is_correct(root, workload):
    r = _run(root, workload)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"tokens_per_s", "latency_p95_s", "setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert list(r)[-1] == "compared"
    gap = r["compared"]["max_logit_gap"]
    assert gap["value"] <= gap["limit"]


def test_a_traced_run_reports_layer_metrics(root):
    r = _run(root, "tiny-qwen.tiny", trace=True)
    assert r["correct"]
    assert {"serve.prefill_ms", "serve.decode_step_ms", "mfu.prefill",
            "mfu.decode", "decode_step_roofline",
            "serve.latency_p95_s"} <= set(r["metrics"])
    assert r["device"]["window_s"] > 0
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("fault", [token_altered, cache_unchanged])
@pytest.mark.parametrize("workload", sorted(tiny.CELLS))
def test_a_broken_served_path_is_not_correct(root, workload, fault,
                                             monkeypatch):
    r = _run(root, workload, tamper=fault, monkeypatch=monkeypatch)
    assert not r["correct"], r["compared"]
