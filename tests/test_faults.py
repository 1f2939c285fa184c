"""Chaos suite: fault injection → graceful degradation (DESIGN.md §10).

Every failure class the robustness layer claims to survive is produced on
demand here via ``repro.faults`` and the observable contract is asserted:
the call still completes, the output matches the healthy path, and a
reason-coded event lands in ``ops.HEALTH``.
"""
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import faults
from repro.health import HEALTH
from repro.kernels import autotune, ops


@pytest.fixture(autouse=True)
def _clean_slate():
    """Each test starts with no armed injections and a healthy registry
    (demotions are process-lifetime by design — tests must not leak)."""
    faults.reset()
    HEALTH.reset()
    yield
    faults.reset()
    HEALTH.reset()


# -- injector -----------------------------------------------------------------

def test_env_spec_parsing():
    injs = faults._parse_env("pallas_compile:conv1d*2, slow_step ,jax_runtime:a.b")
    assert [(i.kind, i.site, i.times) for i in injs] == [
        ("pallas_compile", "conv1d", 2),
        ("slow_step", None, None),
        ("jax_runtime", "a.b", None),
    ]


def test_env_arming_and_reset(monkeypatch):
    monkeypatch.setenv(faults.ENV_VAR, "pallas_compile:conv1d")
    faults.reload_env()
    assert faults.active("pallas_compile", "conv1d.w8a8") is not None
    assert faults.active("pallas_compile", "conv2d") is None
    faults.reset()  # disarms env injections too
    assert faults.active("pallas_compile", "conv1d") is None


def test_times_budget():
    with faults.inject("jax_runtime", times=2):
        assert faults.take("jax_runtime")
        assert faults.take("jax_runtime")
        assert not faults.take("jax_runtime")
    assert not faults.take("jax_runtime")  # context exit disarms


def test_site_prefix_matching():
    with faults.inject("pallas_compile", site="conv1d"):
        assert faults.active("pallas_compile", "conv1d") is not None
        assert faults.active("pallas_compile", "conv1d.w8a8") is not None
        assert faults.active("pallas_compile", "conv1dx") is None
        assert faults.active("pallas_compile", "conv2d") is None
    with faults.inject("pallas_compile"):  # site=None → everything
        assert faults.active("pallas_compile", "anything") is not None


def test_probabilistic_firing_is_deterministic():
    def sequence():
        with faults.inject("slow_step", p=0.5, seed=7) as inj:
            return [inj.take() for _ in range(32)]

    a, b = sequence(), sequence()
    assert a == b
    assert any(a) and not all(a)  # p=0.5 actually mixes


def test_maybe_fail_carries_reason_code():
    with faults.inject("pallas_runtime", site="conv2d"):
        with pytest.raises(faults.FaultError) as ei:
            faults.maybe_fail("pallas_runtime", "conv2d.w8a8")
    assert ei.value.kind == "pallas_runtime"
    assert ei.value.site == "conv2d.w8a8"


def test_sleep_point_sleeps_when_armed():
    assert faults.sleep_point("slow_step", "train") == 0.0
    with faults.inject("slow_step", delay_s=0.01):
        t0 = time.time()
        assert faults.sleep_point("slow_step", "train") == 0.01
        assert time.time() - t0 >= 0.009


# -- ops dispatch ladder (fp paths) -------------------------------------------

def _conv1d_operands(rng):
    x = jnp.asarray(rng.normal(size=(1, 32, 4)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(3, 4, 4)).astype(np.float32))
    return x, w


def test_conv1d_ladder_demotes_and_matches(rng):
    x, w = _conv1d_operands(rng)
    clean = ops.conv1d(x, w)
    with faults.inject("pallas_compile", site="conv1d"):
        out = ops.conv1d(x, w)
    np.testing.assert_allclose(out, clean, rtol=2e-5, atol=2e-5)
    assert HEALTH.is_demoted("conv1d", "pallas")
    (ev,) = HEALTH.events_for("conv1d", reason="pallas_compile")
    assert ev.action == "demote:pallas->jax"
    # demotion is sticky: the next call (injection gone) skips pallas and
    # reproduces the jax rung bit-for-bit
    again = ops.conv1d(x, w)
    np.testing.assert_array_equal(np.asarray(again), np.asarray(out))


def test_conv1d_double_fault_chains_to_ref(rng):
    x, w = _conv1d_operands(rng)
    clean = ops.conv1d(x, w)
    with faults.inject("pallas_compile", site="conv1d"), \
         faults.inject("jax_runtime", site="conv1d"):
        out = ops.conv1d(x, w)
    np.testing.assert_allclose(out, clean, rtol=2e-5, atol=2e-5)
    assert HEALTH.is_demoted("conv1d", "pallas")
    assert HEALTH.is_demoted("conv1d", "jax")
    (ev,) = HEALTH.events_for("conv1d", reason="jax_runtime")
    assert ev.action == "demote:jax->ref"


def test_conv2d_ladder(rng):
    x = jnp.asarray(rng.normal(size=(1, 10, 10, 3)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(3, 3, 3, 4)).astype(np.float32))
    clean = ops.conv2d(x, w)
    with faults.inject("pallas_compile", site="conv2d"):
        out = ops.conv2d(x, w)
    np.testing.assert_allclose(out, clean, rtol=2e-5, atol=2e-5)
    assert HEALTH.is_demoted("conv2d", "pallas")


def test_depthwise_ladder(rng):
    x = jnp.asarray(rng.normal(size=(1, 32, 4)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(3, 4)).astype(np.float32))
    clean = ops.conv1d_depthwise(x, w)
    with faults.inject("pallas_runtime", site="conv1d_depthwise"):
        out = ops.conv1d_depthwise(x, w)
    np.testing.assert_allclose(out, clean, rtol=2e-5, atol=2e-5)
    (ev,) = HEALTH.events_for("conv1d_depthwise", reason="pallas_runtime")
    assert ev.action == "demote:pallas->jax"


def test_pool1d_ladder_and_last_rung_propagates(rng):
    x = jnp.asarray(rng.normal(size=(1, 32, 4)).astype(np.float32))
    clean = ops.pool1d(x, window=4, op="max")
    with faults.inject("pallas_compile", site="pool1d"):
        out = ops.pool1d(x, window=4, op="max")
    np.testing.assert_allclose(out, clean, rtol=2e-5, atol=2e-5)
    # both rungs failing: nothing left to degrade to — the fault surfaces
    HEALTH.reset()
    with faults.inject("pallas_compile", site="pool1d"), \
         faults.inject("jax_runtime", site="pool1d"):
        with pytest.raises(faults.FaultError):
            ops.pool1d(x, window=4, op="sum")


def test_fully_demoted_site_still_serves(rng):
    x = jnp.asarray(rng.normal(size=(1, 16, 4)).astype(np.float32))
    HEALTH.demote("pool1d", "pallas")
    HEALTH.demote("pool1d", "jax")
    out = ops.pool1d(x, window=4, op="sum")  # last rung serves regardless
    assert out.shape == (1, 13, 4)
    assert bool(jnp.isfinite(out).all())


def test_attention_decode_ladder(rng):
    B, S, KV, G, D = 2, 16, 2, 2, 8
    q = jnp.asarray(rng.normal(size=(B, KV * G, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, S, KV, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, S, KV, D)).astype(np.float32))
    lengths = jnp.asarray([5, S], jnp.int32)
    ref = ops.attention_decode(q, k, v, lengths=lengths, impl="ref")
    with faults.inject("pallas_compile", site="attention_decode"):
        out = ops.attention_decode(q, k, v, lengths=lengths, impl="pallas")
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)
    assert HEALTH.is_demoted("attention_decode", "pallas")


# -- ops dispatch ladder (quant paths) + scale guards -------------------------

def test_quant_conv1d_ladder(rng):
    x = jnp.asarray(rng.normal(size=(1, 32, 4)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(3, 4, 4)).astype(np.float32))
    clean = ops.conv1d(x, w, precision="w8a8")
    with faults.inject("pallas_compile", site="conv1d"):
        out = ops.conv1d(x, w, precision="w8a8")
    np.testing.assert_allclose(out, clean, rtol=1e-5, atol=1e-5)
    assert HEALTH.is_demoted("conv1d.w8a8", "pallas")


def test_zero_x_scale_float_weight_falls_back_to_fp(rng):
    x = jnp.asarray(rng.normal(size=(1, 32, 4)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(3, 4, 4)).astype(np.float32))
    out = ops.conv1d(x, w, precision="w8a8", x_scale=jnp.float32(0.0))
    assert bool(jnp.isfinite(out).all())  # not a NaN-token factory
    np.testing.assert_allclose(out, ops.conv1d(x, w), rtol=2e-5, atol=2e-5)
    (ev,) = HEALTH.events_for("conv1d.w8a8", reason="quant_scale_zero")
    assert ev.action == "fallback:fp"


def test_nan_x_scale_int8_weight_uses_dynamic_scale(rng):
    from repro.quant.qconv import quantize_weight

    x = jnp.asarray(rng.normal(size=(1, 32, 4)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(3, 4, 4)).astype(np.float32))
    qw = quantize_weight(w)
    dyn = ops.conv1d(x, qw.q, w_scale=qw.scale, precision="w8a8")
    out = ops.conv1d(x, qw.q, w_scale=qw.scale, precision="w8a8",
                     x_scale=jnp.float32(float("nan")))
    assert bool(jnp.isfinite(out).all())
    np.testing.assert_allclose(out, dyn, rtol=1e-5, atol=1e-5)
    (ev,) = HEALTH.events_for("conv1d.w8a8", reason="quant_scale_nan")
    assert ev.action == "fallback:dynamic_scale"


def test_bad_w_scale_int8_weight_raises(rng):
    from repro.quant.qconv import quantize_weight

    x = jnp.asarray(rng.normal(size=(1, 32, 4)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(3, 4, 4)).astype(np.float32))
    qw = quantize_weight(w)
    with pytest.raises(ValueError, match="w_scale"):
        ops.conv1d(x, qw.q, w_scale=jnp.zeros_like(qw.scale),
                   precision="w8a8")
    (ev,) = HEALTH.events_for("conv1d.w8a8", reason="quant_scale_zero")
    assert ev.action == "error:w_scale"


def test_calibration_scale_fault_screened_at_quantize(rng):
    """End-to-end: a poisoned calibration scale never reaches dispatch —
    ``quantize_params`` screens it and leaves the site float."""
    from repro.quant.apply import quantize_params
    from repro.quant.calibrate import Calibration, collecting, observe
    from repro.quant.qconv import QuantizedWeight

    calib = Calibration(percentile=None)
    with collecting(calib):
        observe("whisper/conv1", rng.normal(size=(2, 16, 8)).astype(np.float32))
        observe("whisper/conv2", rng.normal(size=(2, 16, 8)).astype(np.float32))
    with faults.inject("quant_scale_nan", site="whisper/conv1"):
        spec = calib.spec()
    assert not bool(np.isfinite(spec["whisper/conv1"]["x_scale"]))
    params = {"f": {"conv1_w": jnp.ones((3, 8, 8)),
                    "conv2_w": jnp.ones((3, 8, 8))}}
    qp = quantize_params(params, spec)
    assert not isinstance(qp["f"]["conv1_w"], QuantizedWeight)  # left float
    assert isinstance(qp["f"]["conv2_w"], QuantizedWeight)
    (ev,) = HEALTH.events_for("whisper/conv1", reason="quant_scale_nan")
    assert ev.action == "fallback:fp"


# -- autotune cache quarantine ------------------------------------------------

def test_autotune_corrupt_file_quarantined(tmp_path, monkeypatch):
    p = tmp_path / "autotune.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(p))
    p.write_text("{ this is not json")
    autotune.invalidate()
    assert autotune.lookup("conv1d|whatever") is None
    assert not p.exists()
    assert (tmp_path / "autotune.json.corrupt").exists()  # kept for autopsy
    (ev,) = HEALTH.events_for("autotune", reason="cache_corrupt")
    assert ev.action == "quarantine"


def test_autotune_schema_mismatch_quarantined(tmp_path, monkeypatch):
    import json

    p = tmp_path / "autotune.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(p))
    p.write_text(json.dumps({autotune.SCHEMA_KEY: 99, "k": {"tile_l": 4}}))
    autotune.invalidate()
    assert autotune.lookup("k") is None
    assert (tmp_path / "autotune.json.corrupt").exists()
    assert HEALTH.events_for("autotune", reason="cache_schema_mismatch")


def test_autotune_legacy_and_roundtrip(tmp_path, monkeypatch):
    import json

    p = tmp_path / "autotune.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(p))
    # legacy file without __schema__ is accepted as schema 1
    p.write_text(json.dumps({"k": {"tile_l": 4}}))
    autotune.invalidate()
    assert autotune.lookup("k") == {"tile_l": 4}
    # a flush stamps the schema version; reload round-trips
    autotune.record("k2", {"tile_l": 8})
    on_disk = json.loads(p.read_text())
    assert on_disk[autotune.SCHEMA_KEY] == autotune.SCHEMA_VERSION
    autotune.invalidate()
    assert autotune.lookup("k2") == {"tile_l": 8}
    assert autotune.lookup(autotune.SCHEMA_KEY) is None  # never a cache key


def test_autotune_injected_corruption(tmp_path, monkeypatch):
    import json

    p = tmp_path / "autotune.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(p))
    p.write_text(json.dumps({"k": {"tile_l": 4}}))
    autotune.invalidate()
    with faults.inject("autotune_corrupt", times=1):
        assert autotune.lookup("k") is None  # valid file, forced corrupt
    assert (tmp_path / "autotune.json.corrupt").exists()


# -- checkpoint validation / recovery -----------------------------------------

def _state(rng):
    return {"w": jnp.asarray(rng.normal(size=(8, 8)).astype(np.float32)),
            "b": jnp.zeros((8,))}


def test_ckpt_corrupt_fault_recovers_previous_step(tmp_path, rng):
    from repro.checkpoint import CheckpointManager

    mgr = CheckpointManager(tmp_path, keep=5)
    state = _state(rng)
    mgr.save(1, state)
    with faults.inject("ckpt_corrupt", site="step_5", times=1):
        mgr.save(5, state)  # one leaf truncated after its nbytes landed
    assert mgr.validate(1) is None
    assert mgr.validate(5) is not None
    assert mgr.latest_valid_step() == 1
    assert (Path(tmp_path) / "step_5.corrupt").exists()
    (ev,) = HEALTH.events_for("ckpt", reason="ckpt_invalid")
    assert ev.action == "quarantine"
    # the quarantined step is invisible from now on
    from repro.checkpoint import latest_step
    assert latest_step(tmp_path) == 1


def test_ckpt_write_stall_injection(tmp_path, rng):
    from repro.checkpoint import CheckpointManager

    mgr = CheckpointManager(tmp_path, keep=2)
    with faults.inject("ckpt_write_stall", delay_s=0.01):
        t0 = time.time()
        mgr.save(3, _state(rng))
    assert time.time() - t0 >= 0.02  # ≥2 leaves × 0.01s stall
    assert mgr.latest_valid_step() == 3


# -- heartbeats ---------------------------------------------------------------

def test_torn_heartbeat_counts_stale(tmp_path):
    from repro.distributed.ft import beat, heartbeat_file, stale_hosts

    beat(tmp_path, 0)
    heartbeat_file(tmp_path, 1).write_text("")  # torn write: empty file
    heartbeat_file(tmp_path, 2).write_text("garbage")
    (Path(tmp_path) / "heartbeats" / "host_abc").write_text("1.0")  # junk
    (Path(tmp_path) / "heartbeats" / "README").write_text("hi")
    assert stale_hosts(tmp_path, timeout_s=60) == [1, 2]


def test_heartbeat_stale_fault_suppresses_beat(tmp_path):
    from repro.distributed.ft import beat, heartbeat_file, stale_hosts

    with faults.inject("heartbeat_stale", site="host_1"):
        beat(tmp_path, 0)
        beat(tmp_path, 1)
    assert heartbeat_file(tmp_path, 0).exists()
    assert not heartbeat_file(tmp_path, 1).exists()
    assert stale_hosts(tmp_path, timeout_s=60) == []  # never-written ≠ listed


# -- serve: retry / nan-guard / deadline --------------------------------------

def _serve_model():
    from repro.configs import get_config, smoke_config
    from repro.distributed.sharding import Runtime
    from repro.models import build_model

    cfg = smoke_config(get_config("qwen3-1.7b"))
    model = build_model(cfg, Runtime())
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(rng.integers(2, cfg.vocab_size, size=(2, 8)),
                          jnp.int32)
    return model, params, prompts


def test_serve_retry_recovers_nan_logits():
    from repro.launch.serve import generate

    model, params, prompts = _serve_model()
    clean, _ = generate(model, params, prompts, gen_len=4, cache_len=16)
    with faults.inject("nan_activations", site="serve/logits", times=1):
        toks, _ = generate(model, params, prompts, gen_len=4, cache_len=16)
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(clean))
    (ev,) = HEALTH.events_for("serve/generate", reason="nan_logits")
    assert ev.action == "retry"


def test_serve_retries_exhausted_raises():
    from repro.launch.serve import generate

    model, params, prompts = _serve_model()
    with faults.inject("nan_activations", site="serve/logits"):
        with pytest.raises(FloatingPointError):
            generate(model, params, prompts, gen_len=4, cache_len=16,
                     max_retries=1)
    evs = HEALTH.events_for("serve/generate", reason="nan_logits")
    assert any(e.action == "error:retries_exhausted" for e in evs)


def test_serve_deadline_truncates():
    from repro.launch.serve import generate

    model, params, prompts = _serve_model()
    toks, done = generate(model, params, prompts, gen_len=6, cache_len=16,
                          deadline_s=0.0)
    assert toks.shape == (2, 6)  # static shape holds under truncation
    assert bool(done.all())  # every slot recyclable
    eos = model.cfg.eos_id
    assert bool((toks[:, -1] == eos).all())  # tail is eos padding
    (ev,) = HEALTH.events_for("serve/generate", reason="deadline_exceeded")
    assert ev.action == "truncate"


def test_serve_heartbeat_and_watchdog(tmp_path):
    from repro.distributed.ft import StepWatchdog, heartbeat_file
    from repro.launch.serve import generate

    model, params, prompts = _serve_model()
    wd = StepWatchdog()
    toks, _ = generate(model, params, prompts, gen_len=5, cache_len=16,
                       run_dir=tmp_path, host_id=3, watchdog=wd)
    assert toks.shape == (2, 5)
    assert heartbeat_file(tmp_path, 3).exists()
    assert wd.seen == 4  # one observation per decode step


def test_serve_pallas_fault_token_exact():
    """The CI chaos contract in-process: under an injected Pallas compile
    failure the conv frontend demotes to the compiled-JAX twin and greedy
    decode emits the SAME tokens (whisper smoke, sliding_pallas)."""
    from repro.configs import get_config, smoke_config
    from repro.distributed.sharding import Runtime
    from repro.launch.serve import generate
    from repro.models import build_model

    cfg = smoke_config(get_config("whisper-medium"))
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(rng.integers(2, cfg.vocab_size, size=(1, 6)),
                          jnp.int32)
    mels = jnp.asarray(rng.uniform(-1, 1, (1, 32, 80)), jnp.float32)

    def run(backend):
        model = build_model(cfg.replace(conv_backend=backend), Runtime())
        params = model.init(jax.random.key(0))
        toks, _ = generate(model, params, prompts, gen_len=4, cache_len=16,
                           audio=mels)
        return np.asarray(toks)

    want = run("sliding")  # the jax twin is this exact code path
    with faults.inject("pallas_compile", site="conv1d"):
        got = run("sliding_pallas")
    np.testing.assert_array_equal(got, want)
    assert HEALTH.events_for("conv1d", reason="pallas_compile")


# -- runtime fault domain (DESIGN.md §15) --------------------------------------

def test_guest_trap_not_armed_is_identity(rng):
    x = jnp.asarray(rng.normal(size=(2, 3)).astype(np.float32))
    assert faults.guest_trap("conv1d", "pallas", None, x) is x


def test_runtime_sentinel_trips_on_nonfinite(monkeypatch):
    monkeypatch.setenv(faults.SENTINEL_ENV, "1")
    ok = jnp.ones((2, 2))
    bad = ok.at[0, 0].set(jnp.nan)
    assert bool(jnp.isfinite(faults.guest_trap("conv1d", "pallas",
                                               "k", ok)).all())
    with pytest.raises(faults.FaultError) as ei:
        faults.guest_trap("conv1d", "pallas", "k", bad)
    assert ei.value.kind == "nan_activations"
    trip = faults.consume_trip()
    assert trip == faults.Trip("conv1d", "pallas", "k", "nan_activations")
    assert faults.consume_trip() is None  # mailbox is consume-once


def test_consume_trip_site_filter():
    faults._record_trip(faults.Trip("conv1d", "pallas", "k", "pallas_runtime"))
    assert faults.consume_trip("conv2d") is None  # not ours: left in place
    assert faults.consume_trip("conv1d") is not None
    assert faults.consume_trip() is None


def test_breaker_probation_repromotes(monkeypatch):
    monkeypatch.setenv("REPRO_HEALTH_COOLDOWN_CALLS", "3")
    HEALTH.demote("conv1d", "pallas", reason="pallas_runtime")
    assert HEALTH.is_demoted("conv1d", "pallas")
    HEALTH.tick(3)  # cooldown elapses
    assert not HEALTH.is_demoted("conv1d", "pallas")  # the single probe
    assert HEALTH.is_demoted("conv1d", "pallas")  # probe already out
    HEALTH.note_success("conv1d", "pallas")  # probe passed
    assert not HEALTH.is_demoted("conv1d", "pallas")
    assert HEALTH.breaker("conv1d", "pallas") is None
    assert HEALTH.events_for("conv1d", reason="pallas_runtime")
    acts = {e.action for e in HEALTH.events_for("conv1d")}
    assert "probe:pallas" in acts and "repromote:pallas" in acts


def test_breaker_failed_probe_grows_cooldown(monkeypatch):
    monkeypatch.setenv("REPRO_HEALTH_COOLDOWN_CALLS", "2")
    monkeypatch.setenv("REPRO_HEALTH_COOLDOWN_GROWTH", "2.0")
    HEALTH.demote("pool1d", "pallas")
    HEALTH.tick(2)
    assert not HEALTH.is_demoted("pool1d", "pallas")  # probe granted
    HEALTH.demote("pool1d", "pallas")  # probe failed: re-open, trips=2
    br = HEALTH.breaker("pool1d", "pallas")
    assert br.trips == 2 and br.state == "open"
    HEALTH.tick(2)
    assert HEALTH.is_demoted("pool1d", "pallas")  # 2 < 2*growth: not ready
    HEALTH.tick(2)
    assert not HEALTH.is_demoted("pool1d", "pallas")  # 4 >= 4: next probe
    HEALTH.note_success("pool1d", "pallas")
    # trip history survives repromotion: a fresh demotion resumes at 3
    HEALTH.demote("pool1d", "pallas")
    assert HEALTH.breaker("pool1d", "pallas").trips == 3


def test_eager_ladder_runtime_trap_probe_cycle(rng, monkeypatch):
    """The full circuit through the real dispatch ladder, eagerly: runtime
    trap → demote, cooldown → probe, probe fails → re-demote with grown
    cooldown, second probe passes → repromote."""
    monkeypatch.setenv("REPRO_HEALTH_COOLDOWN_CALLS", "1")
    x = jnp.asarray(rng.normal(size=(1, 32, 4)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(3, 4)).astype(np.float32))
    clean = ops.conv1d_depthwise(x, w)
    with faults.inject("pallas_runtime", site="conv1d_depthwise", times=2):
        out = ops.conv1d_depthwise(x, w)  # trap fires -> demote (trip 1)
        np.testing.assert_allclose(out, clean, rtol=2e-5, atol=2e-5)
        assert HEALTH.breaker("conv1d_depthwise", "pallas").trips == 1
        # jax rung's note_success credited clean=1 >= 1: next call probes;
        # the probe consumes the second injected fault -> re-demote
        out = ops.conv1d_depthwise(x, w)
        np.testing.assert_allclose(out, clean, rtol=2e-5, atol=2e-5)
        br = HEALTH.breaker("conv1d_depthwise", "pallas")
        assert br.trips == 2 and br.state == "open"
        # grown cooldown: after one clean call the breaker is still open
        # (is_demoted is a mutating probation gate — inspect via breaker)
        out = ops.conv1d_depthwise(x, w)
        br = HEALTH.breaker("conv1d_depthwise", "pallas")
        assert br.state == "open" and br.trips == 2
        # second clean call reaches the grown cooldown; the injection
        # budget is exhausted, so the next probe passes -> repromote
        ops.conv1d_depthwise(x, w)
    assert HEALTH.breaker("conv1d_depthwise", "pallas") is None
    acts = {e.action for e in HEALTH.events_for("conv1d_depthwise")}
    assert "repromote:pallas" in acts


def test_serve_runtime_fault_demotes_rejits_token_exact():
    """A kernel dying INSIDE the compiled call (pallas_runtime guest trap)
    maps back to its (site, rung) via the trip, demotes, re-jits, and the
    re-run emits the SAME greedy tokens as the clean sliding baseline."""
    from repro.configs import get_config, smoke_config
    from repro.distributed.sharding import Runtime
    from repro.launch.serve import generate
    from repro.models import build_model

    cfg = smoke_config(get_config("whisper-medium"))
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(rng.integers(2, cfg.vocab_size, size=(1, 6)),
                          jnp.int32)
    mels = jnp.asarray(rng.uniform(-1, 1, (1, 32, 80)), jnp.float32)

    def run(backend):
        model = build_model(cfg.replace(conv_backend=backend), Runtime())
        params = model.init(jax.random.key(0))
        toks, _ = generate(model, params, prompts, gen_len=4, cache_len=16,
                           audio=mels)
        return np.asarray(toks)

    want = run("sliding")
    with faults.inject("pallas_runtime", site="conv1d", times=1):
        got = run("sliding_pallas")
    np.testing.assert_array_equal(got, want)
    evs = HEALTH.events_for("conv1d", reason="pallas_runtime")
    assert any(e.action == "demote:pallas(runtime)" for e in evs)
    assert HEALTH.is_demoted("conv1d", "pallas")


def test_serve_probation_repromotes_across_requests(monkeypatch):
    """Request 1 trips the runtime trap (demote + re-jit); by request 2
    the cooldown has elapsed, the probation poll drops the jit cache, the
    probe passes, and the repromoted pallas rung reproduces the clean
    tokens bit-for-bit."""
    from repro.configs import get_config, smoke_config
    from repro.distributed.sharding import Runtime
    from repro.launch.serve import generate
    from repro.models import build_model

    monkeypatch.setenv("REPRO_HEALTH_COOLDOWN_CALLS", "2")
    cfg = smoke_config(get_config("whisper-medium"))
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(rng.integers(2, cfg.vocab_size, size=(1, 6)),
                          jnp.int32)
    mels = jnp.asarray(rng.uniform(-1, 1, (1, 32, 80)), jnp.float32)

    clean_model = build_model(cfg.replace(conv_backend="sliding"), Runtime())
    clean_params = clean_model.init(jax.random.key(0))
    want, _ = generate(clean_model, clean_params, prompts, gen_len=4,
                       cache_len=16, audio=mels)

    model = build_model(cfg.replace(conv_backend="sliding_pallas"), Runtime())
    params = model.init(jax.random.key(0))
    with faults.inject("pallas_runtime", site="conv1d", times=1):
        got1, _ = generate(model, params, prompts, gen_len=4, cache_len=16,
                           audio=mels)
        np.testing.assert_array_equal(np.asarray(got1), np.asarray(want))
        # non-mutating check: is_demoted would consume the probe grant
        br = HEALTH.breaker("conv1d", "pallas")
        assert br is not None and br.state == "open" and br.trips == 1
        got2, _ = generate(model, params, prompts, gen_len=4, cache_len=16,
                           audio=mels)
    np.testing.assert_array_equal(np.asarray(got2), np.asarray(want))
    acts = {e.action for e in HEALTH.events_for("conv1d")}
    assert "probe:pallas" in acts and "repromote:pallas" in acts
    assert HEALTH.breaker("conv1d", "pallas") is None


def test_serve_slot_quarantine_siblings_token_exact():
    """One poisoned slot (injected nan_activations at serve/slot.1) is
    quarantined — eos-masked, marked recyclable — while slot 0's tokens
    stay bit-identical to the clean run. The batch survives."""
    from repro.launch.serve import generate

    model, params, prompts = _serve_model()
    clean, _ = generate(model, params, prompts, gen_len=4, cache_len=16)
    with faults.inject("nan_activations", site="serve/slot.1", times=1):
        toks, done = generate(model, params, prompts, gen_len=4,
                              cache_len=16)
    np.testing.assert_array_equal(np.asarray(toks[0]), np.asarray(clean[0]))
    assert bool(done[1])  # the poisoned slot is recyclable
    eos = model.cfg.eos_id
    assert bool((toks[1] == eos).all())  # its tokens pinned to eos
    (ev,) = HEALTH.events_for("serve/slot", reason="nan_logits")
    assert ev.action == "quarantine"
    # no retry: the batch was never torn down
    assert not HEALTH.events_for("serve/generate", reason="nan_logits")


def test_serve_load_shedding(monkeypatch):
    """With decode-step history projecting past the deadline budget, a new
    request is rejected at admission with LoadShedError + a reason-coded
    event (and never reaches the journal or the retry loop)."""
    from repro import obs
    from repro.launch.serve import LoadShedError, generate

    model, params, prompts = _serve_model()
    # seed the histogram with slow steps for this arch
    hist = obs.REGISTRY.histogram("serve.decode_step_s")
    for _ in range(10):
        hist.observe(0.5, arch=model.cfg.name)
    with pytest.raises(LoadShedError):
        generate(model, params, prompts, gen_len=8, cache_len=16,
                 deadline_s=0.2)
    (ev,) = HEALTH.events_for("serve/admission", reason="load_shed")
    assert ev.action == "shed"
    # a generous budget still admits
    toks, _ = generate(model, params, prompts, gen_len=4, cache_len=16,
                       deadline_s=60.0)
    assert toks.shape == (2, 4)


def test_serve_journal_replay_roundtrip(tmp_path):
    """A begin record without an end (crashed in flight) replays to
    bit-identical greedy tokens and closes the journal."""
    from repro.launch.serve import RequestJournal, generate, replay_pending

    model, params, prompts = _serve_model()
    want, want_done = generate(model, params, prompts, gen_len=4,
                               cache_len=16)
    j = RequestJournal(tmp_path)
    j.begin("r1", prompts, gen_len=4, cache_len=16, temperature=0.0, seed=0)
    assert [r["id"] for r in j.pending()] == ["r1"]
    ((rid, toks, done),) = replay_pending(model, params, j)
    assert rid == "r1"
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(done), np.asarray(want_done))
    assert j.pending() == []  # replay wrote the end record
    # completed requests journal begin+end and do not replay again
    generate(model, params, prompts, gen_len=4, cache_len=16,
             journal=j, request_id="r2")
    assert j.pending() == []


def test_train_runtime_fault_demotes_and_recovers(tmp_path):
    """The train loop's runtime catch layer: an in-compiled-call trap at
    step 0 demotes the rung, rebuilds the jitted step, and the retried
    step produces the same loss as a clean run (state untouched by the
    poisoned attempt)."""
    import argparse

    from repro.launch.train import train_loop

    def args(run_dir):
        return argparse.Namespace(
            arch="whisper-medium", smoke=True, steps=2, batch=2, seq=16,
            lr=3e-4, seed=0, run_dir=str(run_dir), ckpt_every=0,
            log_every=10, grad_accum=None, conv_backend="sliding_pallas",
            audio_frontend="mels", no_resume=True, fail_at=None,
        )

    clean = train_loop(args(tmp_path / "clean"))
    # the same clean run on the rung the retry falls back to
    HEALTH.demote("conv1d", "pallas", reason="pallas_runtime")
    clean_demoted = train_loop(args(tmp_path / "clean_demoted"))
    HEALTH.reset()
    with faults.inject("pallas_runtime", site="conv1d", times=1):
        chaos = train_loop(args(tmp_path / "chaos"))
    assert np.isfinite(chaos["losses"]).all()
    # the retried step 0 must match a clean run on its rung exactly: the
    # poisoned attempt's output never reached `state`
    np.testing.assert_array_equal(np.asarray(chaos["losses"][0]),
                                  np.asarray(clean_demoted["losses"][0]))
    # later steps run on the demoted rung, whose backward may differ from
    # the pallas rung in the final ulp — allclose, not bitwise
    np.testing.assert_allclose(np.asarray(chaos["losses"]),
                               np.asarray(clean["losses"]), rtol=1e-5)
    evs = HEALTH.events_for("conv1d", reason="pallas_runtime")
    assert any(e.action == "demote:pallas(runtime)" for e in evs)
