"""Autotuner subsystem: search, persistent JSON cache, ops dispatch consult."""
import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import autotune, ops, ref

TOL = dict(rtol=3e-4, atol=3e-4)


@pytest.fixture
def tuning_cache(tmp_path, monkeypatch):
    p = tmp_path / "autotune.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(p))
    autotune.invalidate()
    yield p
    autotune.invalidate()


def test_autotune_conv1d_writes_cache(rng, tuning_cache):
    x = jnp.asarray(rng.normal(size=(1, 96, 8)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(3, 8, 8)).astype(np.float32))
    r = autotune.autotune_conv1d(x, w, tile_candidates=(16, 32))
    assert tuning_cache.exists()
    entry = json.loads(tuning_cache.read_text())[r.key]
    assert {"tile_l", "cin_block", "cout_block", "regime", "us",
            "default_us"} <= set(entry)
    assert r.best_us > 0 and r.default_us > 0
    # lookup round-trips through the file
    autotune.invalidate()
    assert autotune.lookup(r.key) == entry


def test_autotune_conv2d_writes_cache(rng, tuning_cache):
    x = jnp.asarray(rng.normal(size=(1, 24, 24, 4)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(3, 3, 4, 8)).astype(np.float32))
    r = autotune.autotune_conv2d(x, w, tile_candidates=((8, 8), (8, 16)))
    entry = json.loads(tuning_cache.read_text())[r.key]
    assert entry["regime"] == "custom"
    assert {"tile_h", "tile_w"} <= set(entry)


def _spy_conv1d_kernel(monkeypatch):
    """Record the kwargs of each fp conv1d kernel call the dispatch makes."""
    seen = {}
    row = ops._CONV["conv1d"]

    def spy(x, w, bias=None, **kw):
        seen.update(kw)
        return row.kernel(x, w, bias, **kw)

    monkeypatch.setitem(ops._CONV, "conv1d", row._replace(kernel=spy))
    return seen


def test_ops_consults_tuned_config(rng, tuning_cache, monkeypatch):
    """ops.conv1d must pick up a cached non-default tiling for its shape."""
    x = jnp.asarray(rng.normal(size=(1, 100, 8)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(3, 8, 8)).astype(np.float32))
    key = autotune.conv1d_key(1, 100, 8, 8, 3, 1, "float32")
    autotune.record(key, {"tile_l": 13, "cin_block": 0, "cout_block": 0,
                          "regime": "generic"})

    seen = _spy_conv1d_kernel(monkeypatch)
    got = ops.conv1d(x, w, backend="sliding", interpret=True)
    assert seen["tile_l"] == 13 and seen["regime"] == "generic"
    np.testing.assert_allclose(got, ref.conv1d_ref(x, w), **TOL)
    # explicit arguments beat the cache
    seen.clear()
    ops.conv1d(x, w, backend="sliding", tile_l=32, interpret=True)
    assert seen["tile_l"] == 32


def test_auto_channel_blocking_large_channels(rng, tuning_cache, monkeypatch):
    """Above AUTO_BLOCK_THRESHOLD the dispatcher blocks channels even with
    no tuned entry — the acceptance guarantee that Cin=Cout=512 never loads
    a full-channel VMEM tile."""
    seen = _spy_conv1d_kernel(monkeypatch)
    x = jnp.asarray(rng.normal(size=(1, 24, 512)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(3, 512, 512)).astype(np.float32))
    got = ops.conv1d(x, w, backend="sliding", interpret=True)
    assert seen["cin_block"] == autotune.AUTO_BLOCK
    assert seen["cout_block"] == autotune.AUTO_BLOCK
    np.testing.assert_allclose(
        got, ref.conv1d_ref(x, w), rtol=2e-3, atol=2e-3
    )


def test_cache_env_override_isolates(rng, tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "a.json"))
    autotune.invalidate()
    autotune.record("k1", {"tile_l": 1})
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "b.json"))
    assert autotune.lookup("k1") is None  # path change invalidates memory
    autotune.invalidate()


def test_flush_uses_per_process_temp(tmp_path, monkeypatch):
    """Writers use a pid-unique temp name (a shared `.tmp` raced under
    concurrent tuning) and the atomic rename leaves no temp files behind."""
    import os

    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "cache.json"))
    autotune.invalidate()
    autotune.record("k", {"tile_l": 64})
    assert json.loads((tmp_path / "cache.json").read_text())["k"]["tile_l"] == 64
    leftovers = [p for p in os.listdir(tmp_path) if ".tmp" in p]
    assert not leftovers, leftovers
    # the temp path this process would use embeds its pid (uniqueness
    # across concurrently-flushing tuner processes)
    autotune.invalidate()


def test_grad_key_distinct_from_forward():
    k_fwd = autotune.conv1d_key(1, 64, 8, 8, 3, 1, "float32")
    k_bwd = autotune.conv1d_key(1, 64, 8, 8, 3, 1, "float32", grad=True)
    assert k_bwd != k_fwd and k_bwd.endswith("|grad")
    k2 = autotune.conv2d_key(1, 8, 8, 4, 4, 3, 3, 1, 1, "float32", grad=True)
    assert k2.endswith("|grad")


def test_depthwise_quant_key_tuned_and_consulted(rng, tmp_path, monkeypatch):
    """The int8 depthwise kernel tunes under its own conv1ddw|…|w8a8 key
    and ops.conv1d_depthwise(precision=) honors the recorded entry."""
    import jax
    import numpy as np
    import jax.numpy as jnp
    from repro.kernels import ops

    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "t.json"))
    autotune.invalidate()
    x = jnp.asarray(rng.normal(size=(1, 48, 8)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(4, 8)).astype(np.float32))
    r = autotune.autotune_conv1d_depthwise(
        x, w, interpret=True, tile_candidates=(16, 32), precision="w8a8"
    )
    key = autotune.conv1d_dw_key(1, 48, 8, 4, 1, "w8a8")
    assert key.endswith("|w8a8") and autotune.lookup(key) is not None
    got = ops.conv1d_depthwise(x, w, padding="VALID", precision="w8a8")
    from repro.quant import qconv, quantize_depthwise_weight

    want = qconv.conv1d_depthwise_q(
        x, quantize_depthwise_weight(w), None, mode="w8a8",
        x_scale=qconv.act_scale(x), padding="VALID",
    )
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    autotune.invalidate()
