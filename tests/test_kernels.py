"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.im2col_gemm import (
    conv1d_im2col_fused_pallas,
    conv1d_im2col_hbm,
    conv2d_im2col_fused_pallas,
    conv2d_im2col_hbm,
    matmul_pallas,
)
from repro.kernels.sliding_conv1d import (
    conv1d_depthwise_pallas,
    conv1d_sliding_pallas,
)
from repro.kernels.sliding_conv2d import conv2d_sliding_pallas

TOL = dict(rtol=3e-4, atol=3e-4)
BTOL = dict(rtol=5e-2, atol=5e-2)  # bf16


# -- conv1d regimes ----------------------------------------------------------

@pytest.mark.parametrize(
    "K,regime",
    [(3, "custom"), (5, "custom"), (2, "generic"), (7, "generic"),
     (17, "generic"), (18, "compound"), (31, "compound"), (48, "compound")],
)
def test_conv1d_all_regimes(rng, K, regime):
    x = jnp.asarray(rng.normal(size=(2, 300, 8)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(K, 8, 16)).astype(np.float32))
    got = conv1d_sliding_pallas(x, w, tile_l=64, interpret=True)
    np.testing.assert_allclose(got, ref.conv1d_ref(x, w), **TOL)
    # explicit regime must agree with auto
    got2 = conv1d_sliding_pallas(x, w, tile_l=64, regime=regime, interpret=True)
    np.testing.assert_allclose(got2, ref.conv1d_ref(x, w), **TOL)


@pytest.mark.parametrize("stride", [1, 2, 3])
@pytest.mark.parametrize("K", [3, 5, 9])
def test_conv1d_strided(rng, K, stride):
    x = jnp.asarray(rng.normal(size=(1, 257, 4)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(K, 4, 8)).astype(np.float32))
    got = conv1d_sliding_pallas(x, w, stride=stride, tile_l=32, interpret=True)
    np.testing.assert_allclose(got, ref.conv1d_ref(x, w, stride=stride), **TOL)


@pytest.mark.parametrize("shape", [(1, 70, 4), (3, 129, 16), (2, 512, 32)])
def test_conv1d_shape_sweep(rng, shape):
    B, L, C = shape
    x = jnp.asarray(rng.normal(size=shape).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(5, C, C)).astype(np.float32))
    got = conv1d_sliding_pallas(x, w, tile_l=48, interpret=True)
    np.testing.assert_allclose(got, ref.conv1d_ref(x, w), **TOL)


def test_conv1d_bf16(rng):
    x = jnp.asarray(rng.normal(size=(2, 200, 8))).astype(jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(5, 8, 8))).astype(jnp.bfloat16)
    got = conv1d_sliding_pallas(x, w, tile_l=64, interpret=True)
    want = ref.conv1d_ref(x, w)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **BTOL
    )


@pytest.mark.parametrize("K,stride", [(4, 1), (3, 2), (8, 1)])
def test_depthwise(rng, K, stride):
    x = jnp.asarray(rng.normal(size=(2, 300, 16)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(K, 16)).astype(np.float32))
    got = conv1d_depthwise_pallas(x, w, stride=stride, tile_l=64, interpret=True)
    np.testing.assert_allclose(
        got, ref.conv1d_depthwise_ref(x, w, stride=stride), **TOL
    )


# -- conv2d regimes ----------------------------------------------------------

@pytest.mark.parametrize(
    "kh,kw", [(3, 3), (5, 5), (7, 7), (17, 17), (19, 19), (1, 9), (9, 1)]
)
def test_conv2d_filter_sweep(rng, kh, kw):
    x = jnp.asarray(rng.normal(size=(1, 40, 40, 4)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(kh, kw, 4, 8)).astype(np.float32))
    got = conv2d_sliding_pallas(x, w, tile_h=8, tile_w=16, interpret=True)
    np.testing.assert_allclose(got, ref.conv2d_ref(x, w), **TOL)


@pytest.mark.parametrize("stride", [(2, 2), (2, 3)])
def test_conv2d_strided(rng, stride):
    x = jnp.asarray(rng.normal(size=(2, 33, 29, 4)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(5, 5, 4, 8)).astype(np.float32))
    got = conv2d_sliding_pallas(
        x, w, stride=stride, tile_h=8, tile_w=8, interpret=True
    )
    np.testing.assert_allclose(got, ref.conv2d_ref(x, w, stride=stride), **TOL)


# -- conv2d compound regime + halo re-padding path ----------------------------

@pytest.mark.parametrize("kh,kw", [(19, 19), (21, 23), (33, 19)])
def test_conv2d_compound_regime(rng, kh, kw):
    """kw > 17 → compound: filter rows chunked via the reduction grid dim."""
    x = jnp.asarray(rng.normal(size=(1, 44, 40, 4)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(kh, kw, 4, 8)).astype(np.float32))
    got = conv2d_sliding_pallas(
        x, w, tile_h=8, tile_w=8, regime="compound", interpret=True
    )
    np.testing.assert_allclose(got, ref.conv2d_ref(x, w), **TOL)


@pytest.mark.parametrize("stride", [(2, 2), (3, 2), (2, 3)])
@pytest.mark.parametrize("kh,kw", [(5, 5), (19, 19)])
def test_conv2d_strided_nondivisible(rng, kh, kw, stride):
    """stride > 1 with output shapes NOT divisible by the tile: the halo
    re-padding path must keep every tile's read in-bounds."""
    x = jnp.asarray(rng.normal(size=(2, 37, 31, 4)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(kh, kw, 4, 8)).astype(np.float32))
    got = conv2d_sliding_pallas(
        x, w, stride=stride, tile_h=5, tile_w=3, interpret=True
    )
    np.testing.assert_allclose(got, ref.conv2d_ref(x, w, stride=stride), **TOL)


def test_conv2d_compound_strided_nondivisible(rng):
    """compound regime + stride: chunked filter rows on the strided grid."""
    x = jnp.asarray(rng.normal(size=(1, 41, 43, 4)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(19, 19, 4, 8)).astype(np.float32))
    got = conv2d_sliding_pallas(
        x, w, stride=(2, 2), tile_h=4, tile_w=4, regime="compound",
        interpret=True,
    )
    np.testing.assert_allclose(
        got, ref.conv2d_ref(x, w, stride=(2, 2)), **TOL
    )


# -- channel blocking ---------------------------------------------------------

@pytest.mark.parametrize("K,regime", [(3, "custom"), (9, "generic"), (20, "compound")])
def test_conv1d_channel_blocked(rng, K, regime):
    """Cin/Cout blocks (incl. non-divisible) match the unblocked result."""
    x = jnp.asarray(rng.normal(size=(2, 120, 24)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(K, 24, 40)).astype(np.float32))
    got = conv1d_sliding_pallas(
        x, w, tile_l=32, cin_block=10, cout_block=16, regime=regime,
        interpret=True,
    )
    np.testing.assert_allclose(got, ref.conv1d_ref(x, w), **TOL)


def test_conv1d_512ch_blocked(rng):
    """Acceptance shape: Cin=Cout=512, k=3 through the blocked sliding path —
    the per-instance weight tile is (3, 128, 128), never (3, 512, 512)."""
    x = jnp.asarray(rng.normal(size=(1, 40, 512)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(3, 512, 512)).astype(np.float32))
    got = conv1d_sliding_pallas(
        x, w, tile_l=32, cin_block=128, cout_block=128, interpret=True
    )
    np.testing.assert_allclose(got, ref.conv1d_ref(x, w), rtol=2e-3, atol=2e-3)


def test_conv2d_channel_blocked(rng):
    x = jnp.asarray(rng.normal(size=(1, 24, 22, 12)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(5, 5, 12, 20)).astype(np.float32))
    got = conv2d_sliding_pallas(
        x, w, tile_h=8, tile_w=8, cin_block=5, cout_block=8, interpret=True
    )
    np.testing.assert_allclose(got, ref.conv2d_ref(x, w), **TOL)


def test_conv1d_depthwise_channel_blocked(rng):
    x = jnp.asarray(rng.normal(size=(2, 90, 20)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(4, 20)).astype(np.float32))
    got = conv1d_depthwise_pallas(x, w, tile_l=32, c_block=8, interpret=True)
    np.testing.assert_allclose(got, ref.conv1d_depthwise_ref(x, w), **TOL)


# -- fused epilogue (bias + activation) ---------------------------------------

def _act(name):
    return {
        "none": lambda v: v,
        "relu": jax.nn.relu,
        "gelu": lambda v: jax.nn.gelu(v, approximate=True),
        "gelu_erf": lambda v: jax.nn.gelu(v, approximate=False),
        "silu": jax.nn.silu,
    }[name]


def test_epilogue_erf_matches_float64_erf():
    """The kernel epilogue's erf (elementwise arithmetic only, for Mosaic)
    is float32 erf: within 5e-7 of float64 erf, past its ±4 clip too."""
    from scipy.special import erf as erf64

    from repro.kernels.sliding_conv1d import erf

    x = np.linspace(-10, 10, 200_001, dtype=np.float32)
    err = np.abs(np.asarray(erf(jnp.asarray(x)), np.float64)
                 - erf64(x.astype(np.float64)))
    assert err.max() < 5e-7, err.max()


@pytest.mark.parametrize("activation", ["none", "relu", "gelu", "gelu_erf",
                                        "silu"])
def test_conv1d_fused_epilogue_f32(rng, activation):
    """Fused conv+bias+act == unfused reference within f32 tolerance."""
    x = jnp.asarray(rng.normal(size=(2, 100, 16)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(5, 16, 24)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(24,)).astype(np.float32))
    got = conv1d_sliding_pallas(
        x, w, b, tile_l=32, activation=activation, interpret=True
    )
    want = _act(activation)(ref.conv1d_ref(x, w) + b)
    np.testing.assert_allclose(got, want, **TOL)


@pytest.mark.parametrize("activation", ["relu", "gelu", "gelu_erf"])
def test_conv1d_fused_epilogue_bf16(rng, activation):
    x = jnp.asarray(rng.normal(size=(2, 100, 16))).astype(jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(3, 16, 16))).astype(jnp.bfloat16)
    b = jnp.asarray(rng.normal(size=(16,))).astype(jnp.bfloat16)
    got = conv1d_sliding_pallas(
        x, w, b, tile_l=32, activation=activation, interpret=True
    )
    want = _act(activation)(
        ref.conv1d_ref(x, w).astype(jnp.float32) + b.astype(jnp.float32)
    )
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **BTOL
    )


@pytest.mark.parametrize("activation", ["relu", "silu"])
def test_conv2d_fused_epilogue(rng, activation):
    x = jnp.asarray(rng.normal(size=(1, 20, 18, 8)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(3, 3, 8, 16)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(16,)).astype(np.float32))
    got = conv2d_sliding_pallas(
        x, w, b, tile_h=8, tile_w=8, activation=activation, interpret=True
    )
    want = _act(activation)(ref.conv2d_ref(x, w) + b)
    np.testing.assert_allclose(got, want, **TOL)


def test_conv2d_fused_epilogue_bf16(rng):
    x = jnp.asarray(rng.normal(size=(1, 20, 18, 8))).astype(jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(3, 3, 8, 16))).astype(jnp.bfloat16)
    b = jnp.asarray(rng.normal(size=(16,))).astype(jnp.bfloat16)
    got = conv2d_sliding_pallas(
        x, w, b, tile_h=8, tile_w=8, activation="relu", interpret=True
    )
    want = jax.nn.relu(
        ref.conv2d_ref(x, w).astype(jnp.float32) + b.astype(jnp.float32)
    )
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **BTOL
    )


def test_depthwise_fused_epilogue(rng):
    """The Mamba path: depthwise conv→bias→silu in one launch."""
    x = jnp.asarray(rng.normal(size=(2, 80, 16)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(4, 16)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(16,)).astype(np.float32))
    got = conv1d_depthwise_pallas(
        x, w, b, tile_l=32, activation="silu", interpret=True
    )
    want = jax.nn.silu(ref.conv1d_depthwise_ref(x, w) + b)
    np.testing.assert_allclose(got, want, **TOL)


def test_conv1d_fused_blocked_epilogue(rng):
    """Blocking + epilogue compose: bias/act apply once, on the final
    reduction visit (not once per Cin block)."""
    x = jnp.asarray(rng.normal(size=(1, 64, 24)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(5, 24, 32)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(32,)).astype(np.float32))
    got = conv1d_sliding_pallas(
        x, w, b, tile_l=16, cin_block=8, cout_block=16, activation="gelu_erf",
        interpret=True,
    )
    want = jax.nn.gelu(ref.conv1d_ref(x, w) + b, approximate=False)
    np.testing.assert_allclose(got, want, **TOL)


# -- im2col baselines ---------------------------------------------------------

def test_matmul_tiled(rng):
    a = jnp.asarray(rng.normal(size=(200, 70)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(70, 90)).astype(np.float32))
    got = matmul_pallas(a, b, tm=64, tn=32, tk=32, interpret=True)
    np.testing.assert_allclose(got, ref.matmul_ref(a, b), **TOL)


@pytest.mark.parametrize("K", [3, 7, 17])
def test_im2col_variants_match_sliding(rng, K):
    x = jnp.asarray(rng.normal(size=(2, 200, 8)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(K, 8, 16)).astype(np.float32))
    want = ref.conv1d_ref(x, w)
    np.testing.assert_allclose(
        conv1d_im2col_fused_pallas(x, w, tile_l=64, interpret=True), want, **TOL
    )
    np.testing.assert_allclose(
        conv1d_im2col_hbm(x, w, interpret=True), want, **TOL
    )


def test_im2col_hbm_2d(rng):
    x = jnp.asarray(rng.normal(size=(1, 24, 26, 4)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(5, 5, 4, 8)).astype(np.float32))
    np.testing.assert_allclose(
        conv2d_im2col_hbm(x, w, interpret=True), ref.conv2d_ref(x, w), **TOL
    )


@pytest.mark.parametrize(
    "kh,kw,stride", [(3, 3, (1, 1)), (5, 5, (2, 2)), (7, 5, (2, 3))]
)
def test_im2col_fused_2d(rng, kh, kw, stride):
    """The fused-VMEM 2-D im2col baseline (column tile in scratch, one GEMM)
    — previously ops silently substituted the HBM-bloat variant for it."""
    x = jnp.asarray(rng.normal(size=(2, 33, 29, 4)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(kh, kw, 4, 8)).astype(np.float32))
    got = conv2d_im2col_fused_pallas(
        x, w, stride=stride, tile_h=8, tile_w=8, interpret=True
    )
    np.testing.assert_allclose(got, ref.conv2d_ref(x, w, stride=stride), **TOL)


# -- pooling -------------------------------------------------------------------

@pytest.mark.parametrize("op", ["sum", "avg", "max"])
@pytest.mark.parametrize("window", [2, 9, 64])
def test_pool_kernel(rng, op, window):
    x = jnp.asarray(rng.normal(size=(2, 200, 16)).astype(np.float32))
    got = ops.pool1d(x, window=window, op=op, interpret=True)
    np.testing.assert_allclose(
        got, ref.pool_ref(x, window=window, op=op), rtol=2e-4, atol=2e-4
    )


@pytest.mark.parametrize("window", [100, 256])
def test_max_pool_large_window(rng, window):
    """Windows larger than the output tile: the two-phase block
    prefix/suffix decomposition (incl. its -inf pad branch) stays exact."""
    x = jnp.asarray(rng.normal(size=(1, 300, 8)).astype(np.float32))
    got = ops.pool1d(x, window=window, op="max", interpret=True)
    np.testing.assert_allclose(
        got, ref.pool_ref(x, window=window, op="max"), rtol=2e-4, atol=2e-4
    )


@pytest.mark.parametrize("window", [4, 48, 256])
def test_max_pool_methods_agree(rng, window):
    """The shift-and-max kernel and the van Herk/Gil-Werman scan kernel
    are interchangeable evaluations of the same reduction."""
    x = jnp.asarray(rng.normal(size=(2, 300, 8)).astype(np.float32))
    a = ops.pool1d(x, window=window, op="max", method="scan", interpret=True)
    b = ops.pool1d(x, window=window, op="max", method="shift", interpret=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_max_pool_method_from_autotune_cache(rng, tmp_path, monkeypatch):
    """ops.pool1d resolves the max-pool evaluation per window size from
    the autotune cache (falling back to the crossover heuristic) instead
    of hardcoding one form — the BENCH pool rows showed each form losing
    on part of the window range."""
    from repro.kernels import autotune
    from repro.kernels.ops import POOL_SHIFT_MAX_WINDOW, _pool_method

    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "t.json"))
    autotune.invalidate()
    x = jnp.asarray(rng.normal(size=(1, 64, 4)).astype(np.float32))
    # heuristic when untuned: shift below the crossover, scan above
    assert _pool_method(x, 4, "max", None) == "shift"
    assert _pool_method(x, POOL_SHIFT_MAX_WINDOW, "max", None) == "scan"
    # a tuned entry overrides the heuristic
    key = autotune.pool1d_key(1, 64, 4, 4, "max", "float32")
    autotune.record(key, {"method": "scan", "us": 1.0})
    assert _pool_method(x, 4, "max", None) == "scan"
    # explicit argument wins over everything
    assert _pool_method(x, 4, "max", "shift") == "shift"
    # sum/avg always use the prefix-scan kernel
    assert _pool_method(x, 4, "sum", None) == "scan"
    # and the tuned method produces the same values
    got = ops.pool1d(x, window=4, op="max", interpret=True)
    np.testing.assert_allclose(
        got, ref.pool_ref(x, window=4, op="max"), rtol=2e-4, atol=2e-4
    )
    autotune.invalidate()


def test_autotune_pool1d_records_method(rng, tmp_path, monkeypatch):
    from repro.kernels import autotune

    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "t.json"))
    autotune.invalidate()
    x = jnp.asarray(rng.normal(size=(1, 96, 4)).astype(np.float32))
    r = autotune.autotune_pool1d(x, window=8, op="max", interpret=True)
    entry = autotune.lookup(autotune.pool1d_key(1, 96, 4, 8, "max",
                                                "float32"))
    assert entry is not None and entry["method"] in ("scan", "shift")
    assert r.best["method"] == entry["method"]
    autotune.invalidate()


# -- ops dispatch ---------------------------------------------------------------

@pytest.mark.parametrize("backend", ["sliding", "im2col_gemm", "im2col_hbm", "xla"])
@pytest.mark.parametrize("pad", ["VALID", "SAME", "CAUSAL"])
def test_ops_conv1d_dispatch(rng, backend, pad):
    x = jnp.asarray(rng.normal(size=(2, 100, 16)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(3, 16, 32)).astype(np.float32))
    got = ops.conv1d(x, w, padding=pad, backend=backend, interpret=True)
    want = ops.conv1d(x, w, padding=pad, backend="xla")
    np.testing.assert_allclose(got, want, **TOL)


@pytest.mark.parametrize("backend", ["sliding", "im2col_gemm", "im2col_hbm", "xla"])
def test_ops_conv1d_epilogue_all_backends(rng, backend):
    """conv+bias+act agrees across backends: fused in the sliding kernel,
    unfused elsewhere — same numerics either way."""
    x = jnp.asarray(rng.normal(size=(2, 60, 16)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(3, 16, 32)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(32,)).astype(np.float32))
    got = ops.conv1d(
        x, w, padding="SAME", backend=backend, bias=b, activation="relu",
        interpret=True,
    )
    want = jax.nn.relu(
        ops.conv1d(x, w, padding="SAME", backend="xla") + b
    )
    np.testing.assert_allclose(got, want, **TOL)


def test_ops_conv2d_epilogue(rng):
    x = jnp.asarray(rng.normal(size=(1, 20, 20, 8)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(5, 5, 8, 16)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(16,)).astype(np.float32))
    got = ops.conv2d(
        x, w, padding="SAME", bias=b, activation="gelu", interpret=True
    )
    want = jax.nn.gelu(
        ops.conv2d(x, w, padding="SAME", backend="xla") + b, approximate=True
    )
    np.testing.assert_allclose(got, want, **TOL)


@pytest.mark.parametrize(
    "backend", ["sliding", "im2col_gemm", "im2col_hbm", "xla"]
)
def test_ops_conv2d_dispatch(rng, backend):
    x = jnp.asarray(rng.normal(size=(1, 32, 32, 8)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(5, 5, 8, 16)).astype(np.float32))
    got = ops.conv2d(x, w, padding="SAME", backend=backend, interpret=True)
    want = ops.conv2d(x, w, padding="SAME", backend="xla")
    np.testing.assert_allclose(got, want, **TOL)


# -- SSM selective-scan kernel (VMEM-resident state) ---------------------------

@pytest.mark.parametrize(
    "B,L,D,N,td,cl",
    [(2, 64, 32, 8, 16, 16), (1, 100, 48, 4, 32, 32),
     (2, 256, 64, 16, 64, 128), (1, 37, 24, 8, 16, 16)],
)
def test_ssm_scan_kernel(rng, B, L, D, N, td, cl):
    from repro.kernels.ssm_scan import ssm_scan_pallas, ssm_scan_ref

    abar = jnp.asarray(rng.uniform(0.3, 1.0, size=(B, L, D, N)).astype(np.float32))
    bx = jnp.asarray(rng.normal(size=(B, L, D, N)).astype(np.float32))
    c = jnp.asarray(rng.normal(size=(B, L, N)).astype(np.float32))
    h0 = jnp.asarray(rng.normal(size=(B, D, N)).astype(np.float32))
    y1, h1 = ssm_scan_pallas(abar, bx, c, h0, tile_d=td, chunk_l=cl, interpret=True)
    y2, h2 = ssm_scan_ref(abar, bx, c, h0)
    np.testing.assert_allclose(y1, y2, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(h1, h2, rtol=2e-4, atol=2e-4)


def test_ssm_scan_kernel_bf16(rng):
    from repro.kernels.ssm_scan import ssm_scan_pallas, ssm_scan_ref

    B, L, D, N = 1, 64, 32, 8
    abar = jnp.asarray(rng.uniform(0.5, 1.0, size=(B, L, D, N))).astype(jnp.bfloat16)
    bx = jnp.asarray(rng.normal(size=(B, L, D, N))).astype(jnp.bfloat16)
    c = jnp.asarray(rng.normal(size=(B, L, N))).astype(jnp.bfloat16)
    h0 = jnp.zeros((B, D, N), jnp.float32)
    y1, h1 = ssm_scan_pallas(abar, bx, c, h0, tile_d=16, chunk_l=16, interpret=True)
    y2, h2 = ssm_scan_ref(abar, bx, c, h0)
    np.testing.assert_allclose(np.asarray(y1, np.float32),
                               np.asarray(y2, np.float32), rtol=1e-1, atol=1e-1)
