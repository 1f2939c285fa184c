"""Gradients of the Pallas sliding-conv/pool path vs jax.grad of the
pure-jnp oracles (``kernels/ref.py``) — the custom-VJP backward kernels
(``kernels/sliding_conv_bwd.py``) must reproduce reverse-mode AD through
the reference implementations, plus end-to-end training smokes through
``--conv-backend sliding_pallas``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.sliding_conv1d import apply_activation

# f32: tolerances absorb only accumulation-order noise (values O(1)).
TOL = dict(rtol=2e-5, atol=2e-5)
# sum/avg pool: the two-phase prefix scan trades exact associativity for
# O(n) — same tolerance class as the forward pool tests.
PTOL = dict(rtol=2e-4, atol=2e-4)


def _close_scaled(got, want, rtol, atol_frac):
    """allclose with atol proportional to the gradient magnitude — for
    bf16 / large-channel cases where absolute grads reach O(10³)."""
    g = np.asarray(got, np.float32)
    w = np.asarray(want, np.float32)
    scale = max(1.0, float(np.abs(w).max()))
    np.testing.assert_allclose(g, w, rtol=rtol, atol=atol_frac * scale)


def _epi(y, b, act):
    yf = y.astype(jnp.float32)
    if b is not None:
        yf = yf + b.astype(jnp.float32)
    return apply_activation(yf, act).astype(y.dtype)


# -- conv1d -------------------------------------------------------------------

@pytest.mark.parametrize(
    "K,stride,act",
    [(3, 1, "gelu"), (5, 1, "relu"), (7, 2, "silu"), (20, 1, "none"),
     (3, 2, "none"), (9, 3, "gelu"), (3, 1, "gelu_erf"), (3, 2, "gelu_erf")],
)
def test_conv1d_grad_regimes(rng, K, stride, act):
    """custom/generic/compound regimes × stride × fused epilogue: grads of
    (x, w, bias) match jax.grad of the oracle + unfused epilogue."""
    x = jnp.asarray(rng.normal(size=(2, 100, 8)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(K, 8, 16)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(16,)).astype(np.float32))
    out_len = (100 - K) // stride + 1
    ct = jnp.asarray(rng.normal(size=(2, out_len, 16)).astype(np.float32))

    def f(x, w, b):
        y = ops.conv1d(
            x, w, stride=stride, bias=b, activation=act, interpret=True
        )
        return jnp.sum(y * ct)

    def f_ref(x, w, b):
        return jnp.sum(_epi(ref.conv1d_ref(x, w, stride=stride), b, act) * ct)

    got = jax.grad(f, (0, 1, 2))(x, w, b)
    want = jax.grad(f_ref, (0, 1, 2))(x, w, b)
    for g, r, name in zip(got, want, "xwb"):
        np.testing.assert_allclose(g, r, err_msg=f"d{name}", **TOL)


def test_conv1d_grad_same_padding(rng):
    """SAME padding: the pad's VJP (slice) composes with the kernel VJP."""
    x = jnp.asarray(rng.normal(size=(1, 60, 8)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(5, 8, 8)).astype(np.float32))
    f = lambda x, w: jnp.sum(ops.conv1d(x, w, padding="SAME", interpret=True) ** 2)
    f_ref = lambda x, w: jnp.sum(ops.conv1d(x, w, padding="SAME", backend="xla") ** 2)
    got = jax.grad(f, (0, 1))(x, w)
    want = jax.grad(f_ref, (0, 1))(x, w)
    np.testing.assert_allclose(got[0], want[0], **TOL)
    np.testing.assert_allclose(got[1], want[1], **TOL)


def test_conv1d_grad_no_bias(rng):
    x = jnp.asarray(rng.normal(size=(1, 50, 4)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(3, 4, 4)).astype(np.float32))
    f = lambda x, w: jnp.sum(ops.conv1d(x, w, activation="silu", interpret=True) ** 2)
    f_ref = lambda x, w: jnp.sum(_epi(ref.conv1d_ref(x, w), None, "silu") ** 2)
    got = jax.grad(f, (0, 1))(x, w)
    want = jax.grad(f_ref, (0, 1))(x, w)
    np.testing.assert_allclose(got[0], want[0], **TOL)
    np.testing.assert_allclose(got[1], want[1], **TOL)


def test_conv1d_grad_channel_blocked(rng):
    """Explicit non-divisible Cin/Cout blocks through fwd AND bwd kernels."""
    x = jnp.asarray(rng.normal(size=(2, 60, 24)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(5, 24, 40)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(40,)).astype(np.float32))

    def f(x, w, b):
        y = ops.conv1d(
            x, w, bias=b, activation="gelu_erf", tile_l=16, cin_block=10,
            cout_block=16, interpret=True,
        )
        return jnp.sum(y ** 2)

    f_ref = lambda x, w, b: jnp.sum(
        _epi(ref.conv1d_ref(x, w), b, "gelu_erf") ** 2)
    got = jax.grad(f, (0, 1, 2))(x, w, b)
    want = jax.grad(f_ref, (0, 1, 2))(x, w, b)
    for g, r in zip(got, want):
        _close_scaled(g, r, rtol=1e-4, atol_frac=1e-5)


def test_conv1d_grad_512ch_auto_blocked(rng):
    """Acceptance shape: Cin=Cout=512 through the auto-blocked path — the
    backward dw kernel tiles its (K, 128, 128) weight-gradient blocks."""
    x = jnp.asarray(rng.normal(size=(1, 40, 512)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(3, 512, 512)).astype(np.float32))
    f = lambda x, w: jnp.sum(ops.conv1d(x, w, tile_l=32, interpret=True) ** 2)
    f_ref = lambda x, w: jnp.sum(ref.conv1d_ref(x, w) ** 2)
    got = jax.grad(f, (0, 1))(x, w)
    want = jax.grad(f_ref, (0, 1))(x, w)
    for g, r in zip(got, want):
        _close_scaled(g, r, rtol=1e-4, atol_frac=1e-5)


@pytest.mark.parametrize("act", ["gelu", "none", "gelu_erf"])
def test_conv1d_grad_bf16(rng, act):
    x = jnp.asarray(rng.normal(size=(2, 100, 16))).astype(jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(3, 16, 16))).astype(jnp.bfloat16)
    b = jnp.asarray(rng.normal(size=(16,))).astype(jnp.bfloat16)
    ct = jnp.asarray(rng.normal(size=(2, 98, 16))).astype(jnp.bfloat16)

    def f(x, w, b):
        y = ops.conv1d(x, w, bias=b, activation=act, interpret=True)
        return jnp.sum((y * ct).astype(jnp.float32))

    def f_ref(x, w, b):
        return jnp.sum((_epi(ref.conv1d_ref(x, w), b, act) * ct).astype(jnp.float32))

    got = jax.grad(f, (0, 1, 2))(x, w, b)
    want = jax.grad(f_ref, (0, 1, 2))(x, w, b)
    for g, r in zip(got, want):
        assert g.dtype == jnp.bfloat16  # cotangents keep the param dtype
        _close_scaled(g, r, rtol=5e-2, atol_frac=5e-2)


# -- depthwise ---------------------------------------------------------------

@pytest.mark.parametrize("K,stride,act", [(4, 1, "silu"), (3, 2, "none")])
def test_depthwise_grad(rng, K, stride, act):
    """The Mamba conv path: depthwise conv→bias→silu backward."""
    x = jnp.asarray(rng.normal(size=(2, 80, 16)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(K, 16)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(16,)).astype(np.float32))

    def f(x, w, b):
        y = ops.conv1d_depthwise(
            x, w, stride=stride, bias=b, activation=act, interpret=True
        )
        return jnp.sum(y ** 2)

    def f_ref(x, w, b):
        xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))  # CAUSAL
        return jnp.sum(
            _epi(ref.conv1d_depthwise_ref(xp, w, stride=stride), b, act) ** 2
        )

    got = jax.grad(f, (0, 1, 2))(x, w, b)
    want = jax.grad(f_ref, (0, 1, 2))(x, w, b)
    for g, r, name in zip(got, want, "xwb"):
        np.testing.assert_allclose(g, r, err_msg=f"d{name}", **TOL)


def test_depthwise_grad_channel_blocked(rng):
    x = jnp.asarray(rng.normal(size=(2, 60, 20)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(4, 20)).astype(np.float32))
    f = lambda x, w: jnp.sum(
        ops.conv1d_depthwise(x, w, c_block=8, interpret=True) ** 2
    )
    f_ref = lambda x, w: jnp.sum(
        ref.conv1d_depthwise_ref(jnp.pad(x, ((0, 0), (3, 0), (0, 0))), w) ** 2
    )
    got = jax.grad(f, (0, 1))(x, w)
    want = jax.grad(f_ref, (0, 1))(x, w)
    np.testing.assert_allclose(got[0], want[0], **TOL)
    np.testing.assert_allclose(got[1], want[1], **TOL)


# -- conv2d ------------------------------------------------------------------

@pytest.mark.parametrize(
    "kh,kw,stride,act",
    [(3, 3, (1, 1), "relu"), (5, 5, (2, 2), "none"), (19, 19, (1, 1), "none")],
)
def test_conv2d_grad(rng, kh, kw, stride, act):
    """custom/compound 2-D regimes × stride × epilogue backward."""
    H, W = (22, 22) if kh == 19 else (20, 18)
    x = jnp.asarray(rng.normal(size=(2, H, W, 8)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(kh, kw, 8, 16)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(16,)).astype(np.float32))

    def f(x, w, b):
        y = ops.conv2d(
            x, w, stride=stride, bias=b, activation=act, tile_h=8, tile_w=8,
            interpret=True,
        )
        return jnp.sum(y ** 2)

    def f_ref(x, w, b):
        return jnp.sum(_epi(ref.conv2d_ref(x, w, stride=stride), b, act) ** 2)

    got = jax.grad(f, (0, 1, 2))(x, w, b)
    want = jax.grad(f_ref, (0, 1, 2))(x, w, b)
    for g, r, name in zip(got, want, "xwb"):
        _close_scaled(g, r, rtol=1e-4, atol_frac=1e-5)


def test_conv2d_grad_channel_blocked(rng):
    x = jnp.asarray(rng.normal(size=(1, 20, 18, 12)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(3, 3, 12, 20)).astype(np.float32))
    f = lambda x, w: jnp.sum(
        ops.conv2d(x, w, tile_h=8, tile_w=8, cin_block=5, cout_block=8,
                   interpret=True) ** 2
    )
    f_ref = lambda x, w: jnp.sum(ref.conv2d_ref(x, w) ** 2)
    got = jax.grad(f, (0, 1))(x, w)
    want = jax.grad(f_ref, (0, 1))(x, w)
    for g, r in zip(got, want):
        _close_scaled(g, r, rtol=1e-4, atol_frac=1e-5)


def test_conv2d_grad_bf16(rng):
    x = jnp.asarray(rng.normal(size=(1, 16, 16, 8))).astype(jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(3, 3, 8, 8))).astype(jnp.bfloat16)
    f = lambda x, w: jnp.sum(
        ops.conv2d(x, w, activation="relu", tile_h=8, tile_w=8,
                   interpret=True).astype(jnp.float32) ** 2
    )
    f_ref = lambda x, w: jnp.sum(
        _epi(ref.conv2d_ref(x, w), None, "relu").astype(jnp.float32) ** 2
    )
    got = jax.grad(f, (0, 1))(x, w)
    want = jax.grad(f_ref, (0, 1))(x, w)
    for g, r in zip(got, want):
        assert g.dtype == jnp.bfloat16
        _close_scaled(g, r, rtol=5e-2, atol_frac=5e-2)


# -- pooling -----------------------------------------------------------------

@pytest.mark.parametrize("op", ["sum", "avg", "max"])
@pytest.mark.parametrize("window", [2, 9, 64])
def test_pool_grad(rng, op, window):
    x = jnp.asarray(rng.normal(size=(2, 200, 16)).astype(np.float32))
    f = lambda x: jnp.sum(ops.pool1d(x, window=window, op=op, interpret=True) ** 2)
    f_ref = lambda x: jnp.sum(ref.pool_ref(x, window=window, op=op) ** 2)
    np.testing.assert_allclose(jax.grad(f)(x), jax.grad(f_ref)(x), **PTOL)


def test_pool_grad_max_ties_conserve_mass(rng):
    """At tied window maxima the gradient splits evenly across the ties —
    total mass per window stays dy (crediting every tie in full would
    inflate it ×ties; post-relu data makes this the common case)."""
    x = jnp.zeros((1, 6, 1), jnp.float32)
    g = jax.grad(lambda x: jnp.sum(ops.pool1d(x, window=3, op="max",
                                              interpret=True)))(x)
    assert abs(float(g.sum()) - 4.0) < 1e-6  # 4 windows × mass 1
    xr = jnp.asarray(np.maximum(rng.normal(size=(2, 100, 8)), 0).astype(np.float32))
    gm = jax.grad(lambda x: jnp.sum(ops.pool1d(x, window=9, op="max",
                                               interpret=True)))(xr)
    assert abs(float(gm.sum()) - 2 * 92 * 8) < 1e-3


def test_pool_grad_bf16_max(rng):
    # tie-free bf16 data (per-channel integer permutations, exact in bf16):
    # at a tie both "dy to every argmax" (ours) and "split across argmaxes"
    # (the oracle's maximum chain) are valid subgradients but differ.
    cols = np.stack([rng.permutation(100) for _ in range(8)], axis=1)
    x = (jnp.asarray(cols[None], jnp.float32) * 0.25).astype(jnp.bfloat16)
    f = lambda x: jnp.sum(
        ops.pool1d(x, window=9, op="max", interpret=True).astype(jnp.float32) ** 2
    )
    f_ref = lambda x: jnp.sum(
        ref.pool_ref(x, window=9, op="max").astype(jnp.float32) ** 2
    )
    _close_scaled(jax.grad(f)(x), jax.grad(f_ref)(x), rtol=5e-2, atol_frac=5e-2)


# -- model-layer plumbing ----------------------------------------------------

def test_layers_conv_bias_act_trainable(rng):
    """layers.conv1d/2d_bias_act with backend=sliding_pallas are
    transparently trainable — grads match the xla backend."""
    from repro.models.layers import conv1d_bias_act, conv2d_bias_act

    x = jnp.asarray(rng.normal(size=(2, 64, 8)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(3, 8, 16)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(16,)).astype(np.float32))
    for backend in ["sliding_pallas"]:
        f = lambda x, w, b: jnp.sum(
            conv1d_bias_act(x, w, b, activation="gelu_erf", padding="SAME",
                            backend=backend) ** 2
        )
        f_ref = lambda x, w, b: jnp.sum(
            conv1d_bias_act(x, w, b, activation="gelu_erf", padding="SAME",
                            backend="xla") ** 2
        )
        got = jax.grad(f, (0, 1, 2))(x, w, b)
        want = jax.grad(f_ref, (0, 1, 2))(x, w, b)
        for g, r in zip(got, want):
            np.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-4)

    x2 = jnp.asarray(rng.normal(size=(1, 14, 14, 3)).astype(np.float32))
    w2 = jnp.asarray(rng.normal(size=(7, 7, 3, 8)).astype(np.float32))
    b2 = jnp.asarray(rng.normal(size=(8,)).astype(np.float32))
    f2 = lambda x, w, b: jnp.sum(
        conv2d_bias_act(x, w, b, stride=(7, 7), backend="sliding_pallas") ** 2
    )
    f2_ref = lambda x, w, b: jnp.sum(
        conv2d_bias_act(x, w, b, stride=(7, 7), backend="xla") ** 2
    )
    got = jax.grad(f2, (0, 1, 2))(x2, w2, b2)
    want = jax.grad(f2_ref, (0, 1, 2))(x2, w2, b2)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-4)


def test_bwd_tile_override_and_grad_key(rng, tmp_path, monkeypatch):
    """autotune_conv1d_grad records the |grad key; ops consults it for the
    backward dw-kernel tile, and an explicit bwd_tile_l always wins."""
    from repro.kernels import autotune

    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "at.json"))
    autotune.invalidate()
    x = jnp.asarray(rng.normal(size=(1, 128, 8)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(3, 8, 8)).astype(np.float32))
    r = autotune.autotune_conv1d_grad(x, w, interpret=True,
                                      tile_candidates=(32, 64))
    key = autotune.conv1d_key(1, 128, 8, 8, 3, 1, "float32", grad=True)
    entry = autotune.lookup(key)
    assert entry is not None and entry.get("tile_l")
    # grads still correct with the tuned AND an explicit bwd tile
    for kw in ({}, {"bwd_tile_l": 16}):
        f = lambda x, w: jnp.sum(ops.conv1d(x, w, interpret=True, **kw) ** 2)
        g = jax.grad(f, (0, 1))(x, w)
        g_ref = jax.grad(
            lambda x, w: jnp.sum(ref.conv1d_ref(x, w) ** 2), (0, 1)
        )(x, w)
        np.testing.assert_allclose(g[0], g_ref[0], **TOL)
        np.testing.assert_allclose(g[1], g_ref[1], **TOL)
    autotune.invalidate()


# -- end-to-end training smokes ----------------------------------------------

def _train_args(tmp_path, **over):
    import argparse

    d = dict(
        arch="whisper-medium", smoke=True, steps=3, batch=2, seq=64,
        lr=3e-4, seed=0, run_dir=str(tmp_path), ckpt_every=0, log_every=100,
        grad_accum=1, conv_backend=None, audio_frontend="stub",
        no_resume=True, fail_at=None, max_restarts=0,
    )
    d.update(over)
    return argparse.Namespace(**d)


def test_train_smoke_sliding_pallas_whisper(tmp_path):
    """Whisper mel frontend through the Pallas custom-VJP conv kernels:
    loss is finite and decreases over the smoke run."""
    from repro.launch.train import train_loop

    out = train_loop(_train_args(
        tmp_path, conv_backend="sliding_pallas", audio_frontend="mels",
        steps=4,
    ))
    losses = out["losses"]
    assert len(losses) == 4
    assert all(np.isfinite(losses)), losses
    assert min(losses[1:]) < losses[0], losses


def test_train_step_sliding_pallas_mamba(rng):
    """Jamba's depthwise Mamba conv trains through the Pallas VJP: loss
    finite, conv weights receive gradient and move."""
    from repro.configs import get_config, smoke_config
    from repro.launch.steps import make_train_step
    from repro.models import build_model
    from repro.optim import OptConfig, init_opt_state

    cfg = smoke_config(get_config("jamba-1.5-large-398b"))
    cfg = cfg.replace(conv_backend="sliding_pallas", grad_accum=1)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    batch = {
        "tokens": jnp.asarray(rng.integers(2, cfg.vocab_size, (2, 64)), jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 64)), jnp.int32),
    }
    opt_cfg = OptConfig(total_steps=10, warmup_steps=2)
    state = {"params": params, "opt": init_opt_state(params, opt_cfg)}
    step = jax.jit(make_train_step(model, opt_cfg))
    new_state, metrics = step(state, batch)
    assert bool(jnp.isfinite(metrics["loss"]))
    moved = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))),
        state["params"], new_state["params"],
    )
    flat = {
        jax.tree_util.keystr(k): v
        for k, v in jax.tree_util.tree_flatten_with_path(moved)[0]
    }
    conv_moves = [v for k, v in flat.items() if "conv_w" in k]
    assert conv_moves and max(conv_moves) > 0, "conv weights did not train"
