"""Observability layer (`repro.obs`, DESIGN.md §12): deterministic
histogram quantiles, snapshot round-trips, spans on the JAX profiler's
timeline, the near-zero disabled path, and the instrumented
serve/train/dispatch surfaces.

The contracts under test:

  * quantiles are a pure function of the persisted bucket counts — two
    machines aggregating the same snapshot can never disagree;
  * metric and span names come from the frozen ``obs.names``
    vocabularies (the lint enforces literals, the registry everything);
  * with no profiler recording, a span site does one ``is_enabled()``
    check and gets a shared null context manager — nothing recorded;
  * while a profiler records, spans land in its ``/host:CPU`` plane
    under their bare names, nested as the code runs them — the serve
    loop's spans cover every decode step and its four parts;
  * `HEALTH.record` mirrors into the ``health.events`` counter and, while
    a profiler records, a ``health.event`` span on the timeline;
  * serve/train smokes populate the metric names the report CLI and the
    CI obs job assert on.
"""
import contextlib
import glob
import inspect
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.health import HEALTH, DispatchLog
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.metrics import BOUNDS, REGISTRY, hist_quantile


@pytest.fixture(autouse=True)
def _clean_obs():
    """Each test sees a fresh registry and no profiler recording;
    process-global state is restored afterwards."""
    REGISTRY.reset()
    assert not jax.profiler.TraceAnnotation.is_enabled()
    yield
    REGISTRY.reset()
    HEALTH.reset()


@contextlib.contextmanager
def _profiled(log_dir):
    """Record a JAX profile into ``log_dir``, host spans only (no Python
    function tracer), as the benchmark's traced runs do."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _host_spans(log_dir, prefixes=("serve.", "health.", "autotune.")):
    """``(name, start_ns, end_ns, stats)`` of the program's spans in the
    ``/host:CPU`` plane of the profile under ``log_dir``, by start."""
    from jax.profiler import ProfileData

    files = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    assert len(files) == 1, files
    data = ProfileData.from_file(files[0])
    out = []
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefixes):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return sorted(out, key=lambda e: e[1])


# -- histogram quantile determinism -------------------------------------------

def test_hist_quantile_is_deterministic_function_of_counts():
    """Same persisted counts → same quantile, computed by hand: linear
    interpolation from the bucket's lower bound."""
    counts = [0] * (len(BOUNDS) + 1)
    # 10 observations in the (0.002, 0.005] bucket, 10 in (0.01, 0.02]
    i_5ms = BOUNDS.index(5e-3)
    i_20ms = BOUNDS.index(2e-2)
    counts[i_5ms] = 10
    counts[i_20ms] = 10
    # p50 target = 10th obs → exactly fills the first bucket: its hi bound
    assert hist_quantile(BOUNDS, counts, 0.5) == pytest.approx(5e-3)
    # p75 target = 15th obs → halfway through the second bucket
    assert hist_quantile(BOUNDS, counts, 0.75) == pytest.approx(
        1e-2 + (2e-2 - 1e-2) * 0.5
    )


def test_hist_quantile_edges():
    empty = [0] * (len(BOUNDS) + 1)
    assert hist_quantile(BOUNDS, empty, 0.99) == 0.0
    # everything in the +Inf overflow bucket → honestly saturates at the
    # last finite bound instead of inventing a value
    overflow = [0] * (len(BOUNDS) + 1)
    overflow[-1] = 5
    assert hist_quantile(BOUNDS, overflow, 0.5) == BOUNDS[-1]


def test_histogram_observe_quantile_and_sums():
    h = REGISTRY.histogram("serve.decode_step_s")
    for v in (0.0015, 0.0015, 0.003, 0.03, 0.4):
        h.observe(v, arch="a")
    assert h.count(arch="a") == 5
    assert h.sum(arch="a") == pytest.approx(0.436)
    # deterministic given the fixed 1-2-5 grid
    # p50 target 2.5 → (0.002, 0.005] bucket, halfway: 0.0035
    assert h.quantile(0.5, arch="a") == pytest.approx(0.0035)
    # p95 target 4.75 → (0.2, 0.5] bucket, 3/4 in: 0.425
    assert h.quantile(0.95, arch="a") == pytest.approx(0.425)
    # a second label set is an independent series
    assert h.count(arch="b") == 0


# -- name vocabulary enforcement ----------------------------------------------

def test_registry_rejects_unknown_metric_names():
    with pytest.raises(ValueError, match="unknown metric name"):
        REGISTRY.counter("serve.not_a_metric")
    with pytest.raises(ValueError, match="unknown metric name"):
        REGISTRY.histogram("dispatch.bogus")


def test_registry_rejects_kind_collisions():
    REGISTRY.counter("serve.requests")
    with pytest.raises(TypeError, match="already registered as counter"):
        REGISTRY.gauge("serve.requests")


def test_span_rejects_unknown_names_when_armed(tmp_path):
    with _profiled(tmp_path):
        with pytest.raises(ValueError, match="unknown span name"):
            obs.span("serve.not_a_span")
    # off, the name is not looked at: the null span costs one check
    assert obs.span("serve.not_a_span") is obs_trace._NULL


# -- snapshot round-trip ------------------------------------------------------

def test_snapshot_write_load_roundtrip(tmp_path):
    REGISTRY.counter("dispatch.calls").inc(3.0, site="conv1d", rung="pallas")
    REGISTRY.gauge("serve.kv_cache_bytes").set(1024.0, kind="served")
    h = REGISTRY.histogram("serve.prefill_s")
    h.observe(0.12, arch="whisper-medium")
    REGISTRY.facts("serve.run").set("arch", "whisper-medium")

    path = REGISTRY.write(tmp_path)
    snap = obs_metrics.Registry.load(path)
    assert snap["schema"] == obs_metrics.SCHEMA
    assert snap["bounds"] == list(BOUNDS)
    c = snap["counters"]["dispatch.calls"]
    assert c == [{"labels": {"rung": "pallas", "site": "conv1d"},
                  "value": 3.0}]
    g = snap["gauges"]["serve.kv_cache_bytes"][0]
    assert g["labels"] == {"kind": "served"} and g["value"] == 1024.0
    hs = snap["histograms"]["serve.prefill_s"][0]
    assert hs["count"] == 1 and hs["sum"] == pytest.approx(0.12)
    # the quantile recomputed from the LOADED buckets matches the live one
    assert hist_quantile(snap["bounds"], hs["buckets"], 0.5) == pytest.approx(
        h.quantile(0.5, arch="whisper-medium")
    )
    assert snap["facts"]["serve.run"]["arch"] == "whisper-medium"


def test_prometheus_exposition_shape(tmp_path):
    REGISTRY.counter("serve.requests").inc(2.0, arch="a")
    REGISTRY.histogram("serve.prefill_s").observe(0.0015, arch="a")
    text = REGISTRY.to_prometheus()
    assert '# TYPE repro_serve_requests counter' in text
    assert 'repro_serve_requests{arch="a"} 2' in text
    assert '# TYPE repro_serve_prefill_s histogram' in text
    # cumulative buckets end at +Inf == _count
    assert 'repro_serve_prefill_s_bucket{arch="a",le="+Inf"} 1' in text
    assert 'repro_serve_prefill_s_count{arch="a"} 1' in text


# -- tracing ------------------------------------------------------------------

def test_trace_spans_nest_and_export_valid_chrome_json(tmp_path):
    """While a profiler records, spans land in its ``/host:CPU`` plane
    (the profile TensorBoard and Perfetto load) under their bare names,
    attributes as stats, the outer span containing the inner one."""
    with _profiled(tmp_path):
        with obs.span("serve.generate", arch="a"):
            with obs.span("serve.prefill", arch="a", step=3):
                time.sleep(0.002)
    spans = _host_spans(tmp_path)
    assert [name for name, *_ in spans] == ["serve.generate", "serve.prefill"]
    (_, g0, g1, gstats), (_, p0, p1, pstats) = spans
    assert g0 <= p0 and p1 <= g1
    assert p1 - p0 >= 2_000_000  # slept 2 ms; ns units
    assert gstats == {"arch": "a"}
    assert pstats == {"arch": "a", "step": 3}


def test_disabled_span_is_shared_null_and_records_nothing(tmp_path):
    s1 = obs.span("serve.generate")
    s2 = obs.span("serve.decode.sample", site="conv1d")
    assert s1 is s2 is obs_trace._NULL  # no per-call allocation
    with s1:
        pass
    # a null span entered before the profiler starts stays null inside
    # it: only spans opened while it records reach the timeline
    with _profiled(tmp_path):
        with s2:
            pass
        with obs.span("serve.prefill"):
            pass
    assert [name for name, *_ in _host_spans(tmp_path)] == ["serve.prefill"]


def test_disabled_span_overhead_is_flag_check_cheap():
    """The disabled path is one ``is_enabled()`` check; 200k calls must
    land well under any instrumented site's real work (generous bound so
    CI jitter can't flake it)."""
    t0 = time.perf_counter()
    for _ in range(200_000):
        obs.span("serve.decode_step")
    dt = time.perf_counter() - t0
    assert dt < 2.0, f"disabled span() too slow: {dt:.3f}s / 200k calls"


# -- health mirror ------------------------------------------------------------

def test_health_record_mirrors_counter_and_trace_instant(tmp_path):
    HEALTH.reset()
    with _profiled(tmp_path):
        HEALTH.record("conv1d", "pallas_error", "demote:pallas->jax", "boom")
    HEALTH.record("conv1d", "pallas_error", "demote:pallas->jax")
    c = REGISTRY.counter("health.events")
    assert c.value(site="conv1d", reason="pallas_error",
                   action="demote:pallas->jax") == 2.0
    # the recorded event is a span on the timeline; the one after the
    # profiler stopped is counted but not traced
    spans = [e for e in _host_spans(tmp_path) if e[0] == "health.event"]
    assert len(spans) == 1
    assert spans[0][3] == {
        "site": "conv1d", "reason": "pallas_error",
        "action": "demote:pallas->jax",
    }
    # the dedup contract is unchanged: one event, count bumped
    assert len(HEALTH.events) == 1 and HEALTH.events[0].count == 2


# -- DispatchLog --------------------------------------------------------------

def test_unnamed_dispatch_log_stays_pure_mapping():
    log = DispatchLog()
    log["k"] = "pallas"
    log["k"] = "jax"
    assert log["k"] == "jax" and log.count("k") == 2
    snap = REGISTRY.snapshot()
    assert snap["counters"] == {} and snap["facts"] == {}


def test_named_dispatch_log_mirrors_into_registry():
    log = DispatchLog("attn_decode")
    log["attn_dec|B2|S24|KV24|G1|D64|int8"] = "pallas"
    log["attn_dec|B2|S24|KV24|G1|D64|int8"] = "pallas"
    c = REGISTRY.counter("dispatch.log_calls")
    assert c.value(log="attn_decode",
                   key="attn_dec|B2|S24|KV24|G1|D64|int8") == 2.0
    facts = REGISTRY.facts("dispatch.attn_decode")
    assert facts.get("attn_dec|B2|S24|KV24|G1|D64|int8") == "pallas"
    log.clear()
    assert c.series() == []
    assert facts.items() == []


# -- kernel dispatch instrumentation ------------------------------------------

def test_ladder_records_dispatch_metrics_and_spans(rng):
    """Every dispatch counts under its autotune key, rung and site — at
    trace time, so a jitted call counts once, not once per call."""
    from repro.kernels import ops

    x = jnp.asarray(rng.normal(size=(1, 32, 8)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(3, 8, 16)).astype(np.float32))

    y = ops.conv1d(x, w, interpret=True)
    calls = REGISTRY.counter("dispatch.calls").series()
    assert len(calls) == 1
    labels, n = calls[0]
    assert n == 1.0
    assert labels["site"] == "conv1d"
    assert labels["key"].startswith("conv1d|B1|L32|Cin8|Cout16|K3|")
    assert labels["rung"] in ("pallas", "jax", "ref")

    jitted = jax.jit(lambda x, w: ops.conv1d(x, w, interpret=True))
    for _ in range(3):
        np.testing.assert_allclose(jitted(x, w), y, rtol=1e-5, atol=1e-5)
    assert REGISTRY.counter("dispatch.calls").value(**labels) == 2.0


# -- serve smoke --------------------------------------------------------------

def _smoke_qwen(rng):
    """qwen3-1.7b at smoke size, its params, and a (2, 16) prompt batch."""
    from repro.configs import get_config, smoke_config
    from repro.distributed.sharding import Runtime
    from repro.models import build_model

    cfg = smoke_config(get_config("qwen3-1.7b"))
    model = build_model(cfg, Runtime())
    params = model.init(jax.random.key(0))
    prompts = jnp.asarray(
        rng.integers(2, cfg.vocab_size, size=(2, 16)), jnp.int32
    )
    return model, params, prompts


def test_serve_generate_populates_metrics(rng):
    from repro.launch.serve import generate

    model, params, prompts = _smoke_qwen(rng)
    toks, done = generate(model, params, prompts, gen_len=4, cache_len=24)
    assert toks.shape == (2, 4)

    arch = model.cfg.name
    assert REGISTRY.counter("serve.requests").value(arch=arch) == 1.0
    assert REGISTRY.counter("serve.tokens_generated").value(arch=arch) == 8.0
    assert REGISTRY.histogram("serve.prefill_s").count(arch=arch) == 1
    # the first token falls out of prefill; gen_len-1 decode steps follow
    assert REGISTRY.histogram("serve.decode_step_s").count(arch=arch) == 3
    assert REGISTRY.histogram("serve.request_s").count(arch=arch) == 1
    assert REGISTRY.gauge("serve.slots_total").value(arch=arch) == 2.0
    occ = REGISTRY.gauge("serve.slot_occupancy").value(arch=arch)
    assert occ is not None and 0.0 <= occ <= 1.0
    kv = REGISTRY.gauge("serve.kv_cache_bytes").value(kind="served")
    assert kv is not None and kv > 0


PREFILL_PARTS = ("serve.prefill.forward", "serve.prefill.cache",
                 "serve.prefill.sample")
DECODE_PARTS = ("serve.decode.dispatch", "serve.decode.wait",
                "serve.decode.screen", "serve.decode.sample")


def test_serve_spans_cover_every_decode_step(rng, tmp_path):
    """Under a profiler, each batch's serve.generate holds one
    serve.prefill with its three parts in order, then gen_len - 1
    serve.decode_step spans, each holding its four parts in order; with
    no profiler recording, the loop's spans are the shared null span."""
    from repro.launch.serve import generate

    model, params, prompts = _smoke_qwen(rng)
    P, G = prompts.shape[1], 4

    def serve():
        return generate(model, params, prompts, gen_len=G, cache_len=24)

    toks = serve()[0]  # compiles outside the profile
    assert obs.span("serve.decode_step") is obs_trace._NULL
    with _profiled(tmp_path):
        for _ in range(2):
            np.testing.assert_array_equal(serve()[0], toks)
    serve()  # after the profile: recorded nowhere

    spans = _host_spans(tmp_path)

    def inside(outer):
        _, s0, s1, _ = outer
        return [e for e in spans if e is not outer and s0 <= e[1]
                and e[2] <= s1]

    batches = [e for e in spans if e[0] == "serve.generate"]
    assert len(batches) == 2
    for batch in batches:
        held = inside(batch)
        top = [e for e in held
               if not any(e in inside(o) for o in held if o is not e)]
        assert [e[0] for e in top] == (
            ["serve.prefill"] + ["serve.decode_step"] * (G - 1))
        prefill, *steps = top
        assert tuple(e[0] for e in inside(prefill)) == PREFILL_PARTS
        assert [e[3]["step"] for e in steps] == list(range(P, P + G - 1))
        for step in steps:
            assert tuple(e[0] for e in inside(step)) == DECODE_PARTS


def test_generate_uses_monotonic_clock():
    """Step timing, deadlines, and the watchdog must not see wall-clock
    jumps (NTP, suspend): `_generate_once` may only use perf_counter
    (time.time() stays allowed for ABSOLUTE timestamps like heartbeats,
    which live elsewhere)."""
    from repro.launch import serve

    src = inspect.getsource(serve._generate_once)
    assert "time.time()" not in src, "wall clock in the decode loop"
    assert "time.perf_counter()" in src


# -- train smoke --------------------------------------------------------------

def _train_args(tmp_path, **over):
    import argparse

    d = dict(
        arch="qwen3-1.7b", smoke=True, steps=3, batch=2, seq=64,
        lr=3e-4, seed=0, run_dir=str(tmp_path), ckpt_every=2, log_every=100,
        grad_accum=1, conv_backend=None, audio_frontend="stub",
        no_resume=True, fail_at=None, max_restarts=0,
    )
    d.update(over)
    return argparse.Namespace(**d)


def test_train_loop_populates_metrics_and_artifacts(tmp_path):
    from repro.configs import get_config, smoke_config
    from repro.launch.train import train_loop

    out = train_loop(_train_args(tmp_path))
    assert len(out["losses"]) == 3
    arch = smoke_config(get_config("qwen3-1.7b")).name
    assert REGISTRY.counter("train.steps").value(arch=arch) == 3.0
    assert REGISTRY.counter("train.tokens").value(arch=arch) == 3 * 2 * 64.0
    assert REGISTRY.histogram("train.step_s").count(arch=arch) == 3
    # one async save at step 2 + the blocking final save
    assert REGISTRY.histogram("train.ckpt_save_s").count(arch=arch) == 2
    assert REGISTRY.gauge("train.loss").value(arch=arch) == pytest.approx(
        out["losses"][-1]
    )
    tps = REGISTRY.gauge("train.tokens_per_s").value(arch=arch)
    assert tps is not None and tps > 0
    # artifacts persisted under run_dir (no profile: --trace is off)
    snap = json.load(open(tmp_path / "metrics.json"))
    assert "train.step_s" in snap["histograms"]
    assert not (tmp_path / "profile").exists()


# -- report CLI ---------------------------------------------------------------

def test_report_rebuilds_serve_summary_from_artifacts(tmp_path, capsys):
    run = REGISTRY.facts("serve.run")
    run.set("arch", "whisper-medium")
    run.set("shape", (2, 8))
    run.set("elapsed_s", "1.50")
    run.set("tok_per_s", "10.7")
    run.set("recyclable", 0)
    run.set("batch", 2)
    run.set("eos_id", 50257)
    run.set("sample", "[1 2 3]")
    REGISTRY.facts("dispatch.attn_decode").set(
        "attn_dec|B2|S24|KV24|G1|D64|int8", "pallas"
    )
    REGISTRY.counter("dispatch.log_calls").inc(
        8.0, log="attn_decode", key="attn_dec|B2|S24|KV24|G1|D64|int8"
    )
    REGISTRY.gauge("serve.kv_cache_bytes").set(1000.0, kind="served")
    REGISTRY.gauge("serve.kv_cache_bytes").set(2400.0, kind="fp")
    for v in (0.01, 0.02, 0.03):
        REGISTRY.histogram("serve.decode_step_s").observe(
            v, arch="whisper-medium"
        )
    REGISTRY.counter("health.events").inc(
        1.0, site="conv1d", reason="pallas_error", action="demote:pallas->jax"
    )
    obs.write_artifacts(tmp_path)

    from repro.obs import report

    lines = report.render(tmp_path)
    text = "\n".join(lines)
    assert ("[serve] generated (2, 8) in 1.50s (10.7 tok/s); "
            "0/2 slots recyclable (eos=50257)") in text
    assert ("[serve] attn-decode: impl=pallas "
            "key=attn_dec|B2|S24|KV24|G1|D64|int8 calls=8") in text
    assert "[serve] kv-cache bytes: 1000 (fp 2400, ratio 2.40x)" in text
    assert "[serve] sample: [1 2 3]" in text
    assert ("health: site=conv1d reason=pallas_error "
            "action=demote:pallas->jax") in text
    assert "decode-step" in text

    # the __main__ entry point renders the same thing
    import subprocess
    import sys as _sys

    proc = subprocess.run(
        [_sys.executable, "-m", "repro.obs", "report", str(tmp_path)],
        capture_output=True, text=True, env=_cli_env(),
    )
    assert proc.returncode == 0, proc.stderr
    assert "kv-cache bytes: 1000" in proc.stdout


def test_report_notes_the_trace_profile(tmp_path):
    """A ``--trace`` run's profile sits in ``<run_dir>/profile`` beside
    the metrics, which ``write_artifacts`` alone writes; the report notes
    the profile and lists dispatch keys by calls, the most first."""
    from repro.obs import report

    prof = str(tmp_path / "profile")
    with jax.profiler.trace(prof):
        with obs.span("serve.generate", arch="a"):
            pass
    REGISTRY.counter("dispatch.calls").inc(3.0, site="pool1d", key="k1",
                                           rung="pallas")
    REGISTRY.counter("dispatch.calls").inc(7.0, site="conv1d", key="k2",
                                           rung="jax")
    paths = obs.write_artifacts(tmp_path)
    assert paths == [str(tmp_path / "metrics.json"),
                     str(tmp_path / "metrics.prom")]
    assert [name for name, *_ in _host_spans(prof)] == ["serve.generate"]

    lines = report.render(tmp_path)
    assert f"[obs] profile: 1 .xplane.pb under {prof}" in "\n".join(lines)
    assert [ln for ln in lines if ln.startswith("[dispatch]")] == [
        "[dispatch] key=k2 rung=jax calls=7",
        "[dispatch] key=k1 rung=pallas calls=3",
    ]


@pytest.mark.parametrize("launcher", ["serve", "train"])
def test_trace_profile_is_written_when_the_run_fails(
    tmp_path, monkeypatch, launcher
):
    """``--trace`` ends its profile however the run ends: a serve or
    train run that raises still leaves the profiler stopped and its
    spans in ``<run_dir>/profile``."""
    import importlib
    import sys

    mod = importlib.import_module(f"repro.launch.{launcher}")

    def fail(args):
        with obs.span("serve.generate", arch="a"):
            pass
        raise RuntimeError("run failed")

    monkeypatch.setattr(mod, f"_{launcher}", fail)
    monkeypatch.setattr(mod.compile_cache, "enable", lambda: None)
    monkeypatch.setattr(sys, "argv", [
        launcher, "--trace", "--run-dir", str(tmp_path)])
    with pytest.raises(RuntimeError, match="run failed"):
        mod.main()
    assert not jax.profiler.TraceAnnotation.is_enabled()
    names = [name for name, *_ in _host_spans(tmp_path / "profile")]
    assert names == ["serve.generate"]


def _cli_env():
    import os
    import pathlib

    env = dict(os.environ)
    src = pathlib.Path(__file__).resolve().parent.parent / "src"
    env["PYTHONPATH"] = str(src) + os.pathsep + env.get("PYTHONPATH", "")
    return env


# -- leveled logging ----------------------------------------------------------

def test_log_levels_and_format(capsys):
    from repro.obs import logs

    old = logs.level()
    try:
        logs.set_level("info")
        obs.debug("serve", "hidden")
        obs.info("serve", "shown")
        obs.warn("ft", "also shown")
        out = capsys.readouterr().out
        assert "[serve] shown\n" in out
        assert "[ft] also shown\n" in out
        assert "hidden" not in out
        logs.set_level("warn")
        obs.info("serve", "now hidden")
        assert "now hidden" not in capsys.readouterr().out
    finally:
        logs.set_level(old)
