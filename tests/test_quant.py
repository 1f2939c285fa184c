"""int8 PTQ subsystem (repro.quant + kernels.sliding_conv_quant).

Three layers of validation:

  1. **Exact oracle** — the Pallas int8 kernels (interpret mode) must match
     ``repro.quant.qconv`` with int32 accumulation bit-for-bit in the
     integer part (same taps, same int32 sums, same f32 epilogue): tight
     allclose. The "fast" (CPU wall-clock) evaluation must equal the exact
     one too — it reorders integer sums only.
  2. **Calibrated tolerance vs the f32 reference** — symmetric absmax
     quantization admits an analytic per-element error bound
     ``0.5·s_x·Σ|w| + 0.5·s_w·Σ|x| + 0.25·s_x·s_w·N`` over a conv window
     (activations are ≤1.1-Lipschitz), so quantized outputs are asserted
     within that *computed* bound of the f32 oracle — across stride > 1,
     channel-blocked 512ch, and fused-epilogue cases (the acceptance set).
  3. **Model wiring** — calibration context → QuantSpec → quantize_params
     → whisper frontend / mamba / llava / layers entry points.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import quant
from repro.kernels import autotune, ops, ref
from repro.kernels.sliding_conv_quant import (
    conv1d_quant_pallas,
    conv2d_quant_pallas,
)
from repro.quant import qconv

TIGHT = dict(rtol=1e-5, atol=1e-5)


def _quant_bound(x, w, sx, sw, lipschitz=1.1):
    """Analytic per-element |quant - f32| bound for a VALID conv window:
    error per product ≤ |x|·(s_w/2) + |w|·(s_x/2) + (s_x·s_w)/4, summed
    over the window with worst-case |x| and per-cout Σ|w|."""
    n = int(np.prod(w.shape[:-1]))
    l1w = float(jnp.max(jnp.sum(jnp.abs(w), axis=tuple(range(w.ndim - 1)))))
    xmax = float(jnp.max(jnp.abs(x)))
    swm = float(jnp.max(sw))
    sxf = float(sx)
    return lipschitz * (
        0.5 * sxf * l1w + 0.5 * swm * xmax * n + 0.25 * sxf * swm * n
    )


def _qops(x, w):
    qw = qconv.quantize_weight(w)
    sx = qconv.act_scale(x)
    return qw, sx, qconv.quantize_act(x, sx)


# -- 1-D kernels vs oracle + f32 bound ----------------------------------------

@pytest.mark.parametrize("K,regime", [(3, "custom"), (7, "generic")])
@pytest.mark.parametrize("stride", [1, 2])
def test_conv1d_w8a8_kernel(rng, K, regime, stride):
    x = jnp.asarray(rng.normal(size=(2, 130, 8)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(K, 8, 16)).astype(np.float32))
    qw, sx, xq = _qops(x, w)
    got = conv1d_quant_pallas(
        xq, qw.q, qw.scale, None, x_scale=sx, mode="w8a8", stride=stride,
        tile_l=48, regime=regime, interpret=True,
    )
    want = qconv.conv1d_q(x, qw, None, mode="w8a8", x_scale=sx, stride=stride)
    np.testing.assert_allclose(got, want, **TIGHT)
    f32 = ref.conv1d_ref(x, w, stride=stride)
    bound = _quant_bound(x, w, sx, qw.scale)
    assert float(jnp.max(jnp.abs(got - f32))) <= bound


@pytest.mark.parametrize("K", [3, 33])
def test_conv1d_w8a16_kernel(rng, K):
    """Weight-only mode: f32 accumulation over register-dequantized taps."""
    x = jnp.asarray(rng.normal(size=(1, 100, 6)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(K, 6, 10)).astype(np.float32))
    qw = qconv.quantize_weight(w)
    got = conv1d_quant_pallas(
        x, qw.q, qw.scale, None, mode="w8a16", tile_l=32, interpret=True
    )
    want = qconv.conv1d_q(x, qw, None, mode="w8a16")
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    # weight-only error ≤ 0.5·s_w·Σ|x| per window (no activation term)
    f32 = ref.conv1d_ref(x, w)
    bound = float(jnp.max(qw.scale)) * 0.5 * float(
        jnp.max(jnp.abs(x))
    ) * K * 6 + 1e-4
    assert float(jnp.max(jnp.abs(got - f32))) <= bound


def test_conv1d_w8a8_blocked_512ch(rng):
    """Channel-blocked path: Cin = Cout = 512 forces auto-blocking through
    ops dispatch (int32 VMEM scratch revisits)."""
    x = jnp.asarray(rng.normal(size=(1, 40, 512)).astype(np.float32) * 0.5)
    w = jnp.asarray(rng.normal(size=(3, 512, 512)).astype(np.float32) * 0.05)
    qw, sx, _ = _qops(x, w)
    got = ops.conv1d(x, w, precision="w8a8", x_scale=sx, tile_l=16)
    want = qconv.conv1d_q(x, qw, None, mode="w8a8", x_scale=sx)
    np.testing.assert_allclose(got, want, **TIGHT)
    f32 = ref.conv1d_ref(x, w)
    assert float(jnp.max(jnp.abs(got - f32))) <= _quant_bound(
        x, w, sx, qw.scale
    )


@pytest.mark.parametrize("activation", ["relu", "gelu", "silu"])
def test_conv1d_w8a8_fused_epilogue(rng, activation):
    """dequant→bias→activation fused on the final visit, incl. blocked."""
    x = jnp.asarray(rng.normal(size=(2, 64, 8)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(5, 8, 12)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(12,)).astype(np.float32))
    qw, sx, xq = _qops(x, w)
    got = conv1d_quant_pallas(
        xq, qw.q, qw.scale, b, x_scale=sx, mode="w8a8",
        activation=activation, tile_l=32, cin_block=4, interpret=True,
    )
    want = qconv.conv1d_q(
        x, qw, b, mode="w8a8", x_scale=sx, activation=activation
    )
    np.testing.assert_allclose(got, want, **TIGHT)
    f32 = ops.conv1d(x, w, bias=b, activation=activation)
    assert float(jnp.max(jnp.abs(got - f32))) <= _quant_bound(
        x, w, sx, qw.scale
    )


def test_conv1d_requant_chain(rng):
    """out_scale fuses an int8 requant after the activation — chained
    quantized convs never materialize f32 activations."""
    x = jnp.asarray(rng.normal(size=(1, 60, 8)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(3, 8, 8)).astype(np.float32))
    qw, sx, xq = _qops(x, w)
    out_scale = jnp.float32(0.05)
    got = conv1d_quant_pallas(
        xq, qw.q, qw.scale, None, x_scale=sx, mode="w8a8",
        activation="relu", out_scale=out_scale, tile_l=32, interpret=True,
    )
    assert got.dtype == jnp.int8
    want = qconv.conv1d_q(
        x, qw, None, mode="w8a8", x_scale=sx, activation="relu",
        out_scale=out_scale,
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# -- 2-D kernels --------------------------------------------------------------

@pytest.mark.parametrize(
    "kh,kw,stride",
    [(3, 3, (1, 1)), (5, 5, (2, 2)), (5, 5, (2, 3)), (19, 19, (1, 1))],
)
def test_conv2d_w8a8_kernel(rng, kh, kw, stride):
    x = jnp.asarray(rng.normal(size=(2, 37, 31, 4)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(kh, kw, 4, 8)).astype(np.float32))
    qw, sx, xq = _qops(x, w)
    got = conv2d_quant_pallas(
        xq, qw.q, qw.scale, None, x_scale=sx, mode="w8a8", stride=stride,
        tile_h=8, tile_w=8, interpret=True,
    )
    want = qconv.conv2d_q(x, qw, None, mode="w8a8", x_scale=sx, stride=stride)
    np.testing.assert_allclose(got, want, **TIGHT)
    f32 = ref.conv2d_ref(x, w, stride=stride)
    assert float(jnp.max(jnp.abs(got - f32))) <= _quant_bound(
        x, w, sx, qw.scale
    )


def test_conv2d_w8a8_blocked_epilogue(rng):
    """Blocked channels + fused bias/silu through the ops dispatch."""
    x = jnp.asarray(rng.normal(size=(1, 20, 20, 16)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(3, 3, 16, 24)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(24,)).astype(np.float32))
    qw, sx, _ = _qops(x, w)
    got = ops.conv2d(
        x, w, bias=b, activation="silu", precision="w8a8", x_scale=sx,
        tile_h=8, tile_w=8, cin_block=8, cout_block=8,
    )
    want = qconv.conv2d_q(
        x, qw, b, mode="w8a8", x_scale=sx, activation="silu"
    )
    np.testing.assert_allclose(got, want, **TIGHT)


def test_conv2d_w8a16_kernel(rng):
    x = jnp.asarray(rng.normal(size=(1, 24, 24, 4)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(5, 5, 4, 8)).astype(np.float32))
    qw = qconv.quantize_weight(w)
    got = conv2d_quant_pallas(
        x, qw.q, qw.scale, None, mode="w8a16", tile_h=8, tile_w=8,
        interpret=True,
    )
    want = qconv.conv2d_q(x, qw, None, mode="w8a16")
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_fast_path_equals_exact(rng):
    """The CPU wall-clock evaluation reorders integer sums only."""
    x = jnp.asarray(rng.normal(size=(1, 18, 18, 4)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(5, 5, 4, 8)).astype(np.float32))
    qw, sx, _ = _qops(x, w)
    a = qconv.conv2d_q(x, qw, None, mode="w8a8", x_scale=sx)
    b = qconv.conv2d_q(x, qw, None, mode="w8a8", x_scale=sx,
                       accumulate="fast")
    np.testing.assert_allclose(a, b, **TIGHT)
    c = qconv.conv2d_q_im2col(x, qw, x_scale=sx)
    np.testing.assert_allclose(a, c, **TIGHT)


# -- quantizers / calibration -------------------------------------------------

def test_quantize_weight_per_cout(rng):
    w = jnp.asarray(rng.normal(size=(3, 4, 6)).astype(np.float32))
    qw = qconv.quantize_weight(w)
    assert qw.q.dtype == jnp.int8 and qw.scale.shape == (6,)
    err = jnp.abs(qw.dequant() - w)
    assert bool((err <= qw.scale * 0.5 + 1e-6).all())


def test_calibration_spec_and_context(rng):
    calib = quant.Calibration(percentile=None)  # pure absmax
    x = jnp.asarray(rng.normal(size=(2, 16, 4)).astype(np.float32))
    with quant.collecting(calib):
        quant.observe("site/a", x)
        quant.observe("site/a", 2 * x)
    quant.observe("site/a", 100 * x)  # outside context: ignored
    assert calib.seen == ["site/a"]
    spec = calib.spec()
    want = 2 * float(jnp.max(jnp.abs(x))) / 127.0
    np.testing.assert_allclose(float(spec["site/a"]["x_scale"]), want,
                               rtol=1e-5)
    assert calib.channel_absmax("site/a").shape == (4,)


def test_calibration_skips_tracers(rng):
    """Under jit the activation is a tracer — observation must no-op, not
    crash (calibration passes are documented eager-only)."""
    calib = quant.Calibration()

    @jax.jit
    def f(x):
        quant.observe("site/jit", x)
        return x * 2

    with quant.collecting(calib):
        f(jnp.ones((2, 3)))
    assert calib.seen == []


def test_calibration_percentile_clips_outliers(rng):
    calib = quant.Calibration(percentile=99.0)
    x = np.asarray(rng.normal(size=(1, 1000, 4)), np.float32)
    x[0, 0, 0] = 1e6  # a single outlier must not blow up the scale
    with quant.collecting(calib):
        quant.observe("s", jnp.asarray(x))
    assert float(calib.spec()["s"]["x_scale"]) < 100.0


# -- model-level wiring -------------------------------------------------------

def test_whisper_frontend_quantized(rng):
    from repro.configs import get_config, smoke_config
    from repro.models.whisper import Whisper, conv_frontend

    cfg = smoke_config(get_config("whisper-medium")).replace(
        conv_backend="sliding_pallas"
    )
    model = Whisper(cfg)
    params = model.init(jax.random.key(0))
    mels = jnp.asarray(rng.normal(size=(1, 32, 80)).astype(np.float32))

    calib = quant.Calibration()
    with quant.collecting(calib):
        f32 = conv_frontend(params["frontend"], mels, cfg)
    assert set(calib.seen) == {"whisper/conv1", "whisper/conv2"}

    qparams = quant.quantize_params(params, spec=calib.spec())
    assert quant.quantized_site_count(qparams) == 2
    qcfg = cfg.replace(conv_precision="w8a8")
    got = conv_frontend(qparams["frontend"], mels, qcfg)
    assert got.shape == f32.shape
    rel = float(jnp.max(jnp.abs(got - f32))) / (
        float(jnp.max(jnp.abs(f32))) + 1e-9
    )
    assert rel < 0.1, f"w8a8 frontend drifted {rel:.3f} from f32"


def test_quantize_params_scans_and_serves(rng):
    """QuantizedWeight leaves flatten/scan like arrays: the jamba/mamba
    stacked conv_w quantizes weight-only and still evaluates."""
    from repro.models.mamba import mamba_defs, mamba_apply
    from repro.configs import get_config, smoke_config
    from repro.distributed.sharding import Runtime, init_params

    cfg = smoke_config(get_config("jamba-1.5-large-398b"))
    p = init_params(mamba_defs(cfg), jax.random.key(0), "float32")
    x = jnp.asarray(rng.normal(size=(1, 16, cfg.d_model)).astype(np.float32))
    y32, _ = mamba_apply(p, x, cfg, Runtime())
    qp = quant.quantize_params({"mamba": p})["mamba"]
    assert isinstance(qp["conv_w"], quant.QuantizedWeight)
    yq, _ = mamba_apply(qp, x, cfg, Runtime())
    rel = float(jnp.max(jnp.abs(yq - y32))) / (
        float(jnp.max(jnp.abs(y32))) + 1e-9
    )
    assert rel < 0.05  # weight-only int8 on a k=4 depthwise conv


def test_llava_patch_embed_quantized(rng):
    from repro.models.llava import patch_embed

    w = jnp.asarray(rng.normal(size=(14, 14, 3, 32)).astype(np.float32) * 0.1)
    img = jnp.asarray(rng.normal(size=(1, 28, 28, 3)).astype(np.float32))
    f32 = patch_embed(w, img)
    got = patch_embed(qconv.quantize_weight(w), img, precision="w8a8")
    rel = float(jnp.max(jnp.abs(got - f32))) / (
        float(jnp.max(jnp.abs(f32))) + 1e-9
    )
    assert got.shape == f32.shape and rel < 0.1


def test_layers_conv2d_bias_act_quant_backends_agree(rng):
    """The pure-JAX backend's quant path and the Pallas interpret path
    compute the same int8 contract."""
    from repro.models import layers as L

    x = jnp.asarray(rng.normal(size=(1, 16, 16, 4)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(3, 3, 4, 8)).astype(np.float32))
    qw = qconv.quantize_weight(w, qconv.act_scale(x))
    a = L.conv2d_bias_act(x, qw, None, activation="relu", padding="SAME",
                          backend="sliding", precision="w8a8")
    b = L.conv2d_bias_act(x, qw, None, activation="relu", padding="SAME",
                          backend="sliding_pallas", precision="w8a8")
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


# -- autotune key integration -------------------------------------------------

def test_quant_autotune_key_consulted(rng, tmp_path, monkeypatch):
    """The quant dispatch resolves tilings under the precision-named shape
    key — a tuned entry there must be honored (and not collide with the
    float key for the same shape)."""
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "t.json"))
    autotune.invalidate()
    key = autotune.conv1d_key(1, 64, 8, 8, 3, 1, "w8a8")
    assert key.endswith("|w8a8")
    autotune.record(key, {"tile_l": 16, "cin_block": 4, "cout_block": 0,
                          "regime": "generic"})
    x = jnp.asarray(rng.normal(size=(1, 64, 8)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(3, 8, 8)).astype(np.float32))
    qw, sx, _ = _qops(x, w)
    got = ops.conv1d(x, w, precision="w8a8", x_scale=sx)  # uses tuned entry
    want = qconv.conv1d_q(x, qw, None, mode="w8a8", x_scale=sx)
    np.testing.assert_allclose(got, want, **TIGHT)
    autotune.invalidate()


def test_quant_rejects_non_sliding_backends(rng):
    x = jnp.asarray(rng.normal(size=(1, 16, 4)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(3, 4, 4)).astype(np.float32))
    with pytest.raises(ValueError):
        ops.conv1d(x, w, backend="im2col_gemm", precision="w8a8")
    with pytest.raises(ValueError):
        ops.conv1d(x, w, precision="w8a8", dilation=2)


# -- compound regime (K > 17): chunked reduction grid -------------------------

@pytest.mark.parametrize("stride", [1, 2])
def test_conv1d_w8a8_compound_kernel(rng, stride):
    """K=33 resolves to the compound regime (TAP_CHUNK-chunked reduction,
    no unrolled-tap fallback) and matches the int32 oracle bit-for-bit."""
    from repro.kernels.sliding_conv_quant import _quant_regime

    assert _quant_regime(None, 33) == "compound"
    x = jnp.asarray(rng.normal(size=(1, 90, 6)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(33, 6, 8)).astype(np.float32))
    qw, sx, xq = _qops(x, w)
    got = conv1d_quant_pallas(
        xq, qw.q, qw.scale, None, x_scale=sx, mode="w8a8", stride=stride,
        tile_l=16, interpret=True,
    )
    want = qconv.conv1d_q(x, qw, None, mode="w8a8", x_scale=sx, stride=stride)
    np.testing.assert_allclose(got, want, **TIGHT)
    f32 = ref.conv1d_ref(x, w, stride=stride)
    assert float(jnp.max(jnp.abs(got - f32))) <= _quant_bound(
        x, w, sx, qw.scale
    )


def test_conv1d_w8a8_compound_blocked_epilogue(rng):
    """Compound regime composes with channel blocking (reduction sweeps
    Cin blocks × tap chunks) and the fused bias/act/requant epilogue."""
    x = jnp.asarray(rng.normal(size=(1, 80, 8)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(19, 8, 12)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(12,)).astype(np.float32))
    qw, sx, xq = _qops(x, w)
    got = conv1d_quant_pallas(
        xq, qw.q, qw.scale, b, x_scale=sx, mode="w8a8", regime="compound",
        activation="relu", tile_l=16, cin_block=4, interpret=True,
    )
    want = qconv.conv1d_q(
        x, qw, b, mode="w8a8", x_scale=sx, activation="relu"
    )
    np.testing.assert_allclose(got, want, **TIGHT)
    out_scale = jnp.float32(0.04)
    got8 = conv1d_quant_pallas(
        xq, qw.q, qw.scale, b, x_scale=sx, mode="w8a8", regime="compound",
        activation="relu", out_scale=out_scale, tile_l=16, cin_block=4,
        interpret=True,
    )
    want8 = qconv.conv1d_q(
        x, qw, b, mode="w8a8", x_scale=sx, activation="relu",
        out_scale=out_scale,
    )
    assert got8.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(got8), np.asarray(want8))


def test_conv2d_w8a8_compound_kernel(rng):
    """kw=19 → ROW_CHUNK-chunked compound regime, vs the int32 oracle.
    (The K>17 2-D shapes previously fell back to the unrolled tap loop.)"""
    x = jnp.asarray(rng.normal(size=(1, 40, 40, 4)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(19, 19, 4, 8)).astype(np.float32))
    qw, sx, xq = _qops(x, w)
    got = conv2d_quant_pallas(
        xq, qw.q, qw.scale, None, x_scale=sx, mode="w8a8", regime="compound",
        tile_h=8, tile_w=8, cin_block=2, interpret=True,
    )
    want = qconv.conv2d_q(x, qw, None, mode="w8a8", x_scale=sx)
    np.testing.assert_allclose(got, want, **TIGHT)


# -- depthwise w8a8 kernel (mamba conv path) ----------------------------------

@pytest.mark.parametrize("activation", ["none", "silu"])
def test_depthwise_w8a8_kernel(rng, activation):
    from repro.kernels.sliding_conv_quant import conv1d_depthwise_quant_pallas

    x = jnp.asarray(rng.normal(size=(2, 50, 8)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(4, 8)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(8,)).astype(np.float32))
    qw = quant.quantize_depthwise_weight(w)
    sx = qconv.act_scale(x)
    xq = qconv.quantize_act(x, sx)
    got = conv1d_depthwise_quant_pallas(
        xq, qw.q, qw.scale, b, x_scale=sx, mode="w8a8",
        activation=activation, tile_l=16, interpret=True,
    )
    want = qconv.conv1d_depthwise_q(
        x, qw, b, mode="w8a8", x_scale=sx, padding="VALID",
        activation=activation,
    )
    np.testing.assert_allclose(got, want, **TIGHT)
    # fast path (compiled CPU serving) reorders float sums only
    fast = qconv.conv1d_depthwise_q(
        x, qw, b, mode="w8a8", x_scale=sx, padding="VALID",
        activation=activation, accumulate="fast",
    )
    np.testing.assert_allclose(fast, want, **TIGHT)


def test_depthwise_w8a8_ops_dispatch_blocked(rng):
    """ops.conv1d_depthwise(precision=) quantizes float operands, applies
    causal padding, and blocks channels."""
    x = jnp.asarray(rng.normal(size=(1, 40, 16)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(4, 16)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(16,)).astype(np.float32))
    got = ops.conv1d_depthwise(
        x, w, bias=b, activation="silu", precision="w8a8", c_block=8,
    )
    qw = quant.quantize_depthwise_weight(w)
    want = qconv.conv1d_depthwise_q(
        x, qw, b, mode="w8a8", x_scale=qconv.act_scale(x), activation="silu"
    )
    np.testing.assert_allclose(got, want, **TIGHT)


def test_mamba_w8a8_runs_int8_activations(rng):
    """With conv_precision="w8a8" the mamba conv path runs the int8
    depthwise kernel on both backends, within quant error of f32."""
    from repro.configs import get_config, smoke_config
    from repro.distributed.sharding import Runtime, init_params
    from repro.models.mamba import mamba_apply, mamba_defs

    cfg = smoke_config(get_config("jamba-1.5-large-398b"))
    p = init_params(mamba_defs(cfg), jax.random.key(0), "float32")
    x = jnp.asarray(rng.normal(size=(1, 16, cfg.d_model)).astype(np.float32))
    y32, _ = mamba_apply(p, x, cfg, Runtime())
    qp = quant.quantize_params({"m": p})["m"]
    cfg8 = cfg.replace(conv_precision="w8a8")
    y_jax, _ = mamba_apply(qp, x, cfg8, Runtime())
    y_plr, _ = mamba_apply(
        qp, x, cfg8.replace(conv_backend="sliding_pallas"), Runtime()
    )
    np.testing.assert_allclose(y_plr, y_jax, rtol=1e-4, atol=1e-4)
    rel = float(jnp.max(jnp.abs(y_jax - y32))) / (
        float(jnp.max(jnp.abs(y32))) + 1e-9
    )
    assert rel < 0.1


# -- requant chaining (whisper conv1 → conv2) ---------------------------------

def _chained_frontend(rng):
    from repro.configs import get_config, smoke_config
    from repro.models.whisper import Whisper, conv_frontend

    cfg = smoke_config(get_config("whisper-medium")).replace(
        conv_backend="sliding_pallas"
    )
    model = Whisper(cfg)
    params = model.init(jax.random.key(0))
    mels = jnp.asarray(rng.normal(size=(1, 32, 80)).astype(np.float32))
    calib = quant.Calibration()
    with quant.collecting(calib):
        f32 = conv_frontend(params["frontend"], mels, cfg)
    spec = calib.spec(chains=quant.CHAINS)
    qparams = quant.quantize_params(params, spec=spec)
    return cfg, params, qparams, mels, f32, spec


def test_chained_spec_marks_consumed_int8(rng):
    _, _, qparams, _, _, spec = _chained_frontend(rng)
    assert "out_scale" in spec["whisper/conv1"]
    np.testing.assert_allclose(
        float(spec["whisper/conv1"]["out_scale"]),
        float(spec["whisper/conv2"]["x_scale"]),
    )
    qw1 = qparams["frontend"]["conv1_w"]
    assert qw1.out_scale is not None


def test_chained_frontend_single_dequant_site(rng):
    """Chained: conv1 emits int8 directly (no f32 materialization between
    the convs) — exactly ONE dequant site remains (conv2's output)."""
    from repro.models.whisper import conv_frontend

    cfg, params, qparams, mels, f32, spec = _chained_frontend(rng)
    qcfg = cfg.replace(conv_precision="w8a8")
    with quant.counting_dequants() as sites:
        got = conv_frontend(qparams["frontend"], mels, qcfg)
    assert sites == ["whisper/conv2"]
    rel = float(jnp.max(jnp.abs(got - f32))) / (
        float(jnp.max(jnp.abs(f32))) + 1e-9
    )
    assert rel < 0.1

    # unchained spec (no out_scale): both convs dequantize to float
    qp2 = quant.quantize_params(params, spec=None)
    with quant.counting_dequants() as sites2:
        conv_frontend(qp2["frontend"], mels, qcfg)
    assert sorted(sites2) == ["whisper/conv1", "whisper/conv2"]


def test_chained_frontend_bit_exact_vs_oracle_composition(rng):
    """The chained Pallas path equals composing the int32-exact oracle
    convs (conv1 with out_scale → int8 → conv2) bit for bit."""
    from repro.models.whisper import conv_frontend

    cfg, _, qparams, mels, _, _ = _chained_frontend(rng)
    qcfg = cfg.replace(conv_precision="w8a8")
    got = conv_frontend(qparams["frontend"], mels, qcfg)
    fr = qparams["frontend"]
    qw1, qw2 = fr["conv1_w"], fr["conv2_w"]
    y1 = qconv.conv1d_q(
        mels, qw1, fr["conv1_b"], mode="w8a8", x_scale=qw1.x_scale,
        out_scale=qw1.out_scale, padding="SAME", activation="gelu_erf",
    )
    assert y1.dtype == jnp.int8  # the inter-conv activation IS int8
    y2 = qconv.conv1d_q(
        y1, qw2, fr["conv2_b"], mode="w8a8", x_scale=qw2.x_scale,
        padding="SAME", stride=2, activation="gelu_erf",
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(y2),
                               rtol=1e-5, atol=1e-6)


def test_three_deep_conv2d_chain_single_dequant(rng):
    """>2-deep chains (edge-CNN style conv→conv→conv through max pools):
    interior sites requantize, the tail dequants — exactly ONE dequant
    site — and the chained output stays close to the f32 stack. Max
    pooling commutes with the per-tensor int8 grid (monotonic), so codes
    pool exactly."""
    from repro import core
    from repro.models import layers as L

    x = jnp.asarray(rng.normal(size=(2, 16, 16, 4)).astype(np.float32))
    w1 = jnp.asarray(rng.normal(size=(3, 3, 4, 8)).astype(np.float32)) * 0.2
    w2 = jnp.asarray(rng.normal(size=(3, 3, 8, 8)).astype(np.float32)) * 0.2
    w3 = jnp.asarray(rng.normal(size=(3, 3, 8, 8)).astype(np.float32)) * 0.2

    def stack(ws, precision="fp"):
        h = x
        for i, w in enumerate(ws):
            h = L.conv2d_bias_act(
                h, w, None, activation="relu", padding="SAME",
                precision=precision, site=f"t3/c{i + 1}",
            )
            if i < 2:
                h = core.max_pool2d(h, (2, 2))
        return h

    calib = quant.Calibration()
    with quant.collecting(calib):
        f32 = stack((w1, w2, w3))
    spec = calib.spec(chains={"t3/c1": "t3/c2", "t3/c2": "t3/c3"})
    assert "out_scale" in spec["t3/c1"] and "out_scale" in spec["t3/c2"]
    qws = [
        qconv.quantize_weight(
            w, spec[f"t3/c{i + 1}"]["x_scale"],
            spec[f"t3/c{i + 1}"].get("out_scale"),
        )
        for i, w in enumerate((w1, w2, w3))
    ]
    with quant.counting_dequants() as sites:
        got = stack(qws, precision="w8a8")
    assert sites == ["t3/c3"]  # c1/c2 emitted int8 (through the pools)
    assert got.dtype != jnp.int8
    rel = float(jnp.max(jnp.abs(got - f32))) / (
        float(jnp.max(jnp.abs(f32))) + 1e-9
    )
    assert rel < 0.15


def test_llava_patch_embed_chains_into_projector(rng):
    """The first chained conv2d: patch_embed carries out_scale =
    the projector's calibrated input scale, emits int8 codes, and the
    projector performs the chain's single dequant."""
    from repro.models.llava import PATCH, patch_embed
    from repro.models.transformer import projector_apply

    images = jnp.asarray(rng.normal(size=(2, 28, 28, 3)).astype(np.float32))
    w = jnp.asarray(
        rng.normal(size=(PATCH, PATCH, 3, 32)).astype(np.float32) * 0.05
    )
    pj = {
        "w1": jnp.asarray(
            rng.normal(size=(32, 16)).astype(np.float32) * 0.1
        ),
        "b1": jnp.zeros((16,), jnp.float32),
        "w2": jnp.asarray(
            rng.normal(size=(16, 16)).astype(np.float32) * 0.1
        ),
    }
    calib = quant.Calibration()
    with quant.collecting(calib):
        f32 = projector_apply(pj, patch_embed(w, images))
    spec = calib.spec(chains=quant.CHAINS)
    assert "out_scale" in spec["llava/patch_embed"]
    qw = qconv.quantize_weight(
        w, spec["llava/patch_embed"]["x_scale"],
        spec["llava/patch_embed"]["out_scale"],
    )
    with quant.counting_dequants() as sites:
        codes = patch_embed(qw, images, precision="w8a8")
        assert codes.dtype == jnp.int8  # conv2d emitted on the chain grid
        got = projector_apply(
            pj, codes, x_scale=spec["llava/projector"]["x_scale"]
        )
    assert sites == ["llava/projector"]
    rel = float(jnp.max(jnp.abs(got - f32))) / (
        float(jnp.max(jnp.abs(f32))) + 1e-9
    )
    assert rel < 0.1


def test_projector_requires_scale_for_int8_input(rng):
    from repro.models.transformer import projector_apply

    pj = {
        "w1": jnp.ones((4, 4), jnp.float32),
        "b1": jnp.zeros((4,), jnp.float32),
        "w2": jnp.ones((4, 4), jnp.float32),
    }
    codes = jnp.ones((1, 2, 4), jnp.int8)
    with pytest.raises(ValueError, match="x_scale"):
        projector_apply(pj, codes)


def test_int8_max_pool_commutes_with_dequant(rng):
    """max(q)·s == max(q·s): pooling int8 codes is exact on a per-tensor
    grid (the property the edge-CNN chain rides through its pools)."""
    from repro.core import max_pool2d

    codes = jnp.asarray(
        rng.integers(-127, 128, size=(2, 8, 8, 4)), jnp.int8
    )
    s = 0.037
    pooled_codes = max_pool2d(codes, (2, 2))
    pooled_vals = max_pool2d(codes.astype(jnp.float32) * s, (2, 2))
    np.testing.assert_allclose(
        np.asarray(pooled_codes.astype(jnp.float32) * s),
        np.asarray(pooled_vals), rtol=1e-6,
    )


# -- calibration reservoir ----------------------------------------------------

def test_reservoir_is_deterministic_and_bounded(rng):
    a, b = quant.Calibration(reservoir=128, seed=7), quant.Calibration(
        reservoir=128, seed=7
    )
    for i in range(5):
        x = jnp.asarray(rng.normal(size=(1, 200, 4)).astype(np.float32))
        for c in (a, b):
            c.observe("s", x)
    st = a.stats["s"]
    assert st.vals.size == 128  # bounded, not grow-per-batch
    np.testing.assert_array_equal(st.vals, b.stats["s"].vals)
    np.testing.assert_allclose(float(a.site_scale("s")),
                               float(b.site_scale("s")))


def test_reservoir_represents_late_batches():
    """True reservoir sampling: every batch of the stream is (roughly)
    equally represented — the old first-come fill kept only early batches
    once full, biasing percentile clipping."""
    calib = quant.Calibration(reservoir=256, seed=0)
    for i in range(10):  # batch i holds the constant value i+1
        calib.observe("s", jnp.full((1, 1000, 1), float(i + 1)))
    vals = calib.stats["s"].vals
    assert vals.size == 256
    seen = {int(v) for v in vals}
    # a uniform 256-sample over 10k elements misses a given batch with
    # probability (0.9)^256 ≈ 2e-12 — all 10 batches must appear
    assert seen == set(range(1, 11))
    # and the 99.9th percentile reflects the LATE large values
    assert float(calib.site_scale("s")) > 9.0 / 127.0


def test_observe_skips_int8_codes():
    """A chained conv hands its consumer int8 CODES — observing those as
    activations would poison the stats; they are skipped."""
    calib = quant.Calibration()
    with quant.collecting(calib):
        quant.observe("s", jnp.ones((2, 4), jnp.int8))
    assert calib.seen == []


# -- quant 1-D dispatch fallback ----------------------------------------------

def test_quant_1d_tuned_regression_falls_back(rng, tmp_path, monkeypatch):
    """When the autotune cache shows the quant path measurably slower than
    the float path for a 1-D shape, ops.conv1d serves the float path (with
    a recorded reason) instead of the slower kernel — unless the call is
    pinned to int8 (requant chain), which must keep the quant kernels."""
    from repro.kernels.ops import _QUANT_FALLBACKS

    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "t.json"))
    autotune.invalidate()
    x = jnp.asarray(rng.normal(size=(1, 64, 8)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(3, 8, 8)).astype(np.float32))
    kq = autotune.conv1d_key(1, 64, 8, 8, 3, 1, "w8a8")
    kf = autotune.conv1d_key(1, 64, 8, 8, 3, 1, "float32")
    autotune.record(kq, {"tile_l": 64, "cin_block": 0, "cout_block": 0,
                         "regime": "custom", "us": 500.0})
    autotune.record(kf, {"tile_l": 64, "cin_block": 0, "cout_block": 0,
                         "regime": "custom", "us": 100.0})
    _QUANT_FALLBACKS.clear()
    got = ops.conv1d(x, w, precision="w8a8")
    assert kq in _QUANT_FALLBACKS
    want = ops.conv1d(x, w)  # the float sliding path
    np.testing.assert_allclose(got, want, **TIGHT)

    # pinned: int8 input stays on the quant kernels despite the cache entry
    qw, sx, xq = _qops(x, w)
    got8 = ops.conv1d(xq, w, precision="w8a8", x_scale=sx)
    ref8 = qconv.conv1d_q(x, qw, None, mode="w8a8", x_scale=sx)
    np.testing.assert_allclose(got8, ref8, **TIGHT)
    autotune.invalidate()


def test_quant_1d_no_fallback_without_tuned_timings(rng, tmp_path,
                                                    monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "t.json"))
    autotune.invalidate()
    from repro.kernels.ops import _QUANT_FALLBACKS

    _QUANT_FALLBACKS.clear()
    x = jnp.asarray(rng.normal(size=(1, 32, 4)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(3, 4, 4)).astype(np.float32))
    got = ops.conv1d(x, w, precision="w8a8")
    assert not _QUANT_FALLBACKS
    qw, sx, _ = _qops(x, w)
    want = qconv.conv1d_q(x, qw, None, mode="w8a8", x_scale=sx)
    np.testing.assert_allclose(got, want, **TIGHT)
    autotune.invalidate()
