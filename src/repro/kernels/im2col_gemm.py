"""Pallas TPU kernels: im2col + GEMM convolution — the paper's BASELINE.

Two variants, bracketing what "GEMM-based convolution" costs on TPU:

  * ``conv{1d,2d}_im2col_fused_pallas`` — the column tile is materialized in
    VMEM *scratch* (explicit extra copies, k× VMEM footprint) and contracted
    with one GEMM. This models a well-engineered GEMM-conv where the bloat
    is kept on-chip.
  * ``conv{1d,2d}_im2col_hbm``    — the full (B, out, K·Cin) column tensor is
    materialized in HBM (exactly what Caffe/MlasConv-style im2col does),
    then fed to the tiled Pallas GEMM below. This is the memory-bloat
    baseline the paper's Fig. 1 speedups are measured against.

``matmul_pallas`` is the standard (M, N, K)-tiled MXU GEMM used by the HBM
variant and reusable elsewhere.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.sliding_conv1d import halo_input, halo_spec
from repro.kernels.sliding_conv2d import halo_input_2d

DEFAULT_TM = 128
DEFAULT_TN = 128
DEFAULT_TK = 128


# ---------------------------------------------------------------------------
# Tiled GEMM
# ---------------------------------------------------------------------------

def _matmul_kernel(a_ref, b_ref, o_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    o_ref[...] = (
        o_ref[...].astype(jnp.float32)
        + jnp.dot(a_ref[...], b_ref[...], preferred_element_type=jnp.float32)
    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tm", "tn", "tk", "interpret"))
def matmul_pallas(
    a: jax.Array,
    b: jax.Array,
    *,
    tm: int = DEFAULT_TM,
    tn: int = DEFAULT_TN,
    tk: int = DEFAULT_TK,
    interpret: bool = False,
) -> jax.Array:
    """C = A @ B with (tm, tn, tk) MXU tiling, f32 accumulation."""
    M, K = a.shape
    K2, N = b.shape
    assert K == K2, (K, K2)
    tm, tn, tk = min(tm, M), min(tn, N), min(tk, K)
    gm, gn, gk = pl.cdiv(M, tm), pl.cdiv(N, tn), pl.cdiv(K, tk)
    if gm * tm > M or gk * tk > K:
        a = jnp.pad(a, ((0, gm * tm - M), (0, gk * tk - K)))
    if gk * tk > K or gn * tn > N:
        b = jnp.pad(b, ((0, gk * tk - K), (0, gn * tn - N)))
    out = pl.pallas_call(
        _matmul_kernel,
        grid=(gm, gn, gk),
        in_specs=[
            pl.BlockSpec((tm, tk), lambda i, j, k: (i, k)),
            pl.BlockSpec((tk, tn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((gm * tm, gn * tn), a.dtype),
        interpret=interpret,
    )(a, b)
    return out[:M, :N]


# ---------------------------------------------------------------------------
# Fused im2col-in-VMEM GEMM conv (1-D)
# ---------------------------------------------------------------------------

def _im2col_fused_kernel(x_ref, w_ref, o_ref, col_ref, *, taps, tile_l, stride):
    x = x_ref[0]
    cin = x.shape[-1]
    # Explicit im2col materialization into VMEM scratch — the extra copy
    # traffic that the sliding kernels avoid.
    for k in range(taps):
        xs = x[k : k + (tile_l - 1) * stride + 1]
        if stride > 1:
            xs = xs[::stride]
        col_ref[:, k * cin : (k + 1) * cin] = xs
    wf = w_ref[...].reshape(taps * cin, w_ref.shape[2])
    o_ref[0] = jnp.dot(
        col_ref[...], wf, preferred_element_type=jnp.float32
    ).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("stride", "tile_l", "interpret")
)
def conv1d_im2col_fused_pallas(
    x: jax.Array,
    w: jax.Array,
    *,
    stride: int = 1,
    tile_l: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """VALID conv1d via per-tile im2col in VMEM scratch + one GEMM."""
    B, L, Cin = x.shape
    K, _, Cout = w.shape
    out_len = (L - K) // stride + 1
    tile_l = min(tile_l, out_len)
    n_tiles = pl.cdiv(out_len, tile_l)
    padded_out = n_tiles * tile_l
    step = tile_l * stride
    x, halo = halo_input(x, 1, (n_tiles - 1) * step, (tile_l - 1) * stride + K)
    kernel = functools.partial(
        _im2col_fused_kernel, taps=K, tile_l=tile_l, stride=stride
    )
    out = pl.pallas_call(
        kernel,
        grid=(B, n_tiles),
        in_specs=[
            halo_spec((halo, Cin), 1, lambda b, i: (b, i * step, 0)),
            pl.BlockSpec((K, Cin, Cout), lambda b, i: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, tile_l, Cout), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, padded_out, Cout), x.dtype),
        # VMEM scratch holding the k×-bloated column tile
        scratch_shapes=[pltpu_vmem((tile_l, K * Cin), jnp.float32)],
        interpret=interpret,
    )(x, w)
    return out[:, :out_len]


def pltpu_vmem(shape, dtype):
    """VMEM scratch shape (TPU memory space; plain scratch in interpret)."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, dtype)


# ---------------------------------------------------------------------------
# Fused im2col-in-VMEM GEMM conv (2-D)
# ---------------------------------------------------------------------------

def _im2col2d_fused_kernel(
    x_ref, w_ref, o_ref, col_ref, *, kh, kw, th, tw, sh, sw
):
    x = x_ref[0]
    cin = x.shape[-1]
    cout = w_ref.shape[-1]
    # Explicit (TH·TW, kh·kw·Cin) column tile in VMEM scratch — the kh·kw×
    # copy bloat the sliding kernels avoid, kept on-chip.
    for i in range(kh):
        for j in range(kw):
            xs = x[i : i + (th - 1) * sh + 1, j : j + (tw - 1) * sw + 1]
            if sh > 1 or sw > 1:
                xs = xs[::sh, ::sw]
            t = i * kw + j
            col_ref[:, t * cin : (t + 1) * cin] = xs.reshape(th * tw, cin)
    wf = w_ref[...].reshape(kh * kw * cin, cout)
    o_ref[0] = (
        jnp.dot(col_ref[...], wf, preferred_element_type=jnp.float32)
        .reshape(th, tw, cout)
        .astype(o_ref.dtype)
    )


@functools.partial(
    jax.jit, static_argnames=("stride", "tile_h", "tile_w", "interpret")
)
def conv2d_im2col_fused_pallas(
    x: jax.Array,
    w: jax.Array,
    *,
    stride: tuple[int, int] = (1, 1),
    tile_h: int = 16,
    tile_w: int = 64,
    interpret: bool = False,
) -> jax.Array:
    """VALID conv2d via per-tile im2col in VMEM scratch + one GEMM — the
    fused (well-engineered) GEMM-conv baseline; compare ``conv2d_im2col_hbm``
    for the true-bloat variant."""
    B, H, W, Cin = x.shape
    kh, kw, _, Cout = w.shape
    sh, sw = stride
    oh = (H - kh) // sh + 1
    ow = (W - kw) // sw + 1
    if oh < 1 or ow < 1:
        raise ValueError(
            f"filter ({kh},{kw}) (stride {stride}) exceeds input ({H},{W})"
        )
    th = min(tile_h, oh)
    tw = min(tile_w, ow)
    nh = pl.cdiv(oh, th)
    nw = pl.cdiv(ow, tw)
    x, halo_h, halo_w = halo_input_2d(
        x, (nh - 1) * th * sh, (th - 1) * sh + kh,
        (nw - 1) * tw * sw, (tw - 1) * sw + kw,
    )
    kernel = functools.partial(
        _im2col2d_fused_kernel, kh=kh, kw=kw, th=th, tw=tw, sh=sh, sw=sw
    )
    out = pl.pallas_call(
        kernel,
        grid=(B, nh, nw),
        in_specs=[
            halo_spec((halo_h, halo_w, Cin), 1, lambda b, i, j: (
                b, i * th * sh, j * tw * sw, 0,
            )),
            pl.BlockSpec((kh, kw, Cin, Cout), lambda b, i, j: (0, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, th, tw, Cout), lambda b, i, j: (b, i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((B, nh * th, nw * tw, Cout), x.dtype),
        scratch_shapes=[pltpu_vmem((th * tw, kh * kw * Cin), jnp.float32)],
        interpret=interpret,
    )(x, w)
    return out[:, :oh, :ow]


# ---------------------------------------------------------------------------
# HBM im2col baseline (the real MlasConv-style comparison target)
# ---------------------------------------------------------------------------

def conv1d_im2col_hbm(
    x: jax.Array,
    w: jax.Array,
    *,
    stride: int = 1,
    interpret: bool = False,
) -> jax.Array:
    """VALID conv1d: materialize (B·out, K·Cin) columns in HBM, then GEMM."""
    B, L, Cin = x.shape
    K, _, Cout = w.shape
    out_len = (L - K) // stride + 1
    span = (out_len - 1) * stride + 1
    cols = []
    for k in range(K):
        xs = jax.lax.slice_in_dim(x, k, k + span, axis=1)
        if stride > 1:
            xs = xs[:, ::stride]
        cols.append(xs)
    col = jnp.stack(cols, axis=2).reshape(B * out_len, K * Cin)  # HBM bloat
    y = matmul_pallas(col, w.reshape(K * Cin, Cout), interpret=interpret)
    return y.reshape(B, out_len, Cout)


def conv2d_im2col_hbm(
    x: jax.Array,
    w: jax.Array,
    *,
    stride: tuple[int, int] = (1, 1),
    interpret: bool = False,
) -> jax.Array:
    """VALID conv2d: full HBM im2col + tiled Pallas GEMM (paper baseline)."""
    B, H, W, Cin = x.shape
    kh, kw, _, Cout = w.shape
    sh, sw = stride
    oh = (H - kh) // sh + 1
    ow = (W - kw) // sw + 1
    span_h = (oh - 1) * sh + 1
    span_w = (ow - 1) * sw + 1
    cols = []
    for i in range(kh):
        for j in range(kw):
            xs = jax.lax.dynamic_slice(x, (0, i, j, 0), (B, span_h, span_w, Cin))
            if sh > 1 or sw > 1:
                xs = xs[:, ::sh, ::sw]
            cols.append(xs)
    col = jnp.stack(cols, axis=3).reshape(B * oh * ow, kh * kw * Cin)
    y = matmul_pallas(col, w.reshape(kh * kw * Cin, Cout), interpret=interpret)
    return y.reshape(B, oh, ow, Cout)
