"""Pallas TPU kernels: int8 quantized 1-D/2-D sliding-window convolution.

Post-training-quantized inference variants of the sliding kernels
(DESIGN.md §7). Two modes:

  * ``w8a8``  — weights AND activations int8. The tap matmuls run
    int8×int8 with **int32 accumulation** (the MXU's native s8 path on
    TPU; exact integer arithmetic in interpret mode), and the epilogue
    performs the dequant: ``y = act(acc_i32 · (s_x · s_w[cout]) + bias)``
    — dequant→bias→activation is fused into the final reduction visit,
    so the int32 accumulator never round-trips through HBM.
  * ``w8a16`` — weights int8, activations bf16/f32. The weight tile is
    dequantized **in VMEM registers** (``.astype`` on the loaded block);
    accumulation is f32 and the per-``cout`` weight scale folds into the
    same epilogue. This is the weight-only mode: 4× less weight HBM
    traffic, full-precision activations.

Optional **requant** epilogue: with ``out_scale`` set the activated f32
value is re-quantized to int8 (``round(y / s_y)`` clipped to ±127) inside
the kernel, so chained quantized convs never materialize f32 activations.

Grid/blocking structure is the forward kernels' (sliding_conv1d/2d):
``(B, spatial tiles…, Cout blocks, Cin-block reduction)`` with halo input
tiles as ``pl.Element`` blocks (``halo_spec``) and revisit-accumulation in VMEM
scratch — **int32 scratch** for w8a8, f32 for w8a16. All three regimes
are supported: ``custom`` (tap-stacked single matmul, K ∈ {3,5}),
``generic`` (unrolled tap loop, K ≤ 17), and ``compound`` (K > 17) —
taps/filter-rows processed in ``TAP_CHUNK``/``ROW_CHUNK`` chunks via the
reduction grid dimension revisiting the output block, exactly the f32
kernels' structure, so large quantized filters stay VMEM-bounded instead
of unrolling the whole tap range.

The **depthwise** variant (``conv1d_depthwise_quant_pallas``) is a VPU
kernel: per-tap shifted elementwise int8×int8 FMA with int32 accumulation
and per-channel dequant in the epilogue — the mamba/jamba serving conv
runs int8 activations, not just register-dequantized weights.

Quantization of the *input* activation (``round(x / s_x)``) happens in the
dispatch layer (one elementwise pass), not here: x arrives int8 for w8a8.
These kernels are inference-only — no custom VJP (QAT through the
backward kernels is a ROADMAP item).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.sliding_conv1d import (
    DEFAULT_TILE_L,
    TAP_CHUNK,
    _pad_axis,
    _resolve_block,
    _slide,
    apply_activation,
    halo_input,
    halo_spec,
    phase_split,
    phase_taps,
)
from repro.kernels.sliding_conv2d import (
    DEFAULT_TILE_H,
    DEFAULT_TILE_W,
    ROW_CHUNK,
    _shifted,
    halo_input_2d,
)


def _acc_dtype(w8a8: bool):
    return jnp.int32 if w8a8 else jnp.float32


def _dequant_epilogue(acc, os_ref, o_ref, *, s_ref, b_ref, activation,
                      shape=None):
    """Fused epilogue: dequant (per-cout scale) → bias → activation →
    optional requant. ``acc`` is the int32 (w8a8) / f32 (w8a16) accumulator."""
    y = acc.astype(jnp.float32) * s_ref[0].astype(jnp.float32)
    y = y + b_ref[0].astype(jnp.float32)
    y = apply_activation(y, activation)
    if shape is not None:
        y = y.reshape(*shape, y.shape[-1])
    if os_ref is not None:  # requant: int8 out on the quantized grid
        q = jnp.round(y / os_ref[0, 0].astype(jnp.float32))
        y = jnp.clip(q, -127, 127)
    o_ref[0] = y.astype(o_ref.dtype)


def _reduce_dequant(acc, rest, *, n_red, red_axis, requant, finish):
    """Accumulate this visit's partial into the output block (quant flavor
    of ``sliding_conv1d._reduce_store``): int32/f32 VMEM scratch across
    revisits, dequant epilogue on the last visit only."""
    os_ref = rest[0] if requant else None
    o_ref = rest[1] if requant else rest[0]
    acc_ref = rest[-1] if n_red > 1 else None
    if n_red == 1:
        finish(acc, os_ref, o_ref)
        return
    r = pl.program_id(red_axis)

    @pl.when(r == 0)
    def _first():
        acc_ref[...] = acc

    @pl.when(r > 0)
    def _accum():
        acc_ref[...] += acc

    @pl.when(r == n_red - 1)
    def _done():
        finish(acc_ref[...], os_ref, o_ref)


def _qkernel_1d(
    x_ref, w_ref, s_ref, b_ref, *rest, taps, tile_l, n_red,
    activation, w8a8, requant, regime,
):
    """int8 sliding conv1d body. w8a8: int8 slides × int8 taps → int32;
    w8a16: float slides × register-dequantized taps → f32."""
    cout = w_ref.shape[2]
    adt = _acc_dtype(w8a8)
    if regime == "custom":
        cols = [_slide(x_ref, k, tile_l) for k in range(taps)]
        stacked = jnp.concatenate(cols, axis=-1)  # (TL, K·cb) — VMEM only
        wf = w_ref[...].reshape(taps * w_ref.shape[1], cout)
        if not w8a8:
            stacked = stacked.astype(jnp.float32)
            wf = wf.astype(jnp.float32)
        acc = jnp.dot(stacked, wf, preferred_element_type=adt)
    else:
        acc = jnp.zeros((tile_l, cout), adt)
        for k in range(taps):
            xs = _slide(x_ref, k, tile_l)
            wk = w_ref[k]
            if not w8a8:
                xs = xs.astype(jnp.float32)
                wk = wk.astype(jnp.float32)
            acc += jnp.dot(xs, wk, preferred_element_type=adt)
    _reduce_dequant(
        acc, rest, n_red=n_red, red_axis=3, requant=requant,
        finish=functools.partial(
            _dequant_epilogue, s_ref=s_ref, b_ref=b_ref, activation=activation
        ),
    )


def _qkernel_2d(
    x_ref, w_ref, s_ref, b_ref, *rest, kh, kw, th, tw, sh, sw, n_red,
    activation, w8a8, requant, regime,
):
    x = x_ref[0]
    cout = w_ref.shape[-1]
    adt = _acc_dtype(w8a8)
    if regime == "custom":
        cin = x.shape[-1]
        cols = [
            _shifted(x, i, j, th, tw, sh, sw).reshape(th * tw, cin)
            for i in range(kh)
            for j in range(kw)
        ]
        stacked = jnp.concatenate(cols, axis=-1)
        wf = w_ref[...].reshape(kh * kw * cin, cout)
        if not w8a8:
            stacked = stacked.astype(jnp.float32)
            wf = wf.astype(jnp.float32)
        acc = jnp.dot(stacked, wf, preferred_element_type=adt)
    else:
        acc = jnp.zeros((th * tw, cout), adt)
        for i in range(kh):
            for j in range(kw):
                xs = _shifted(x, i, j, th, tw, sh, sw).reshape(th * tw, -1)
                wk = w_ref[i, j]
                if not w8a8:
                    xs = xs.astype(jnp.float32)
                    wk = wk.astype(jnp.float32)
                acc += jnp.dot(xs, wk, preferred_element_type=adt)
    _reduce_dequant(
        acc, rest, n_red=n_red, red_axis=4, requant=requant,
        finish=functools.partial(
            _dequant_epilogue, s_ref=s_ref, b_ref=b_ref,
            activation=activation, shape=(th, tw),
        ),
    )


def _quant_regime(regime: str | None, k: int) -> str:
    """custom for the paper's k ∈ {3,5}, unrolled tap loop up to K=17,
    TAP_CHUNK/ROW_CHUNK-chunked reduction grid above (same thresholds as
    the f32 ``repro.core.conv.regime_for``)."""
    if regime in ("custom", "generic", "compound"):
        return regime
    if k in (3, 5):
        return "custom"
    return "generic" if k <= 17 else "compound"


def _scales(w_scale, x_scale, cout, n_co, ob, w8a8):
    """Per-cout dequant scale row (1, n_co·ob): w8a8 folds the activation
    scale in (the int32 accumulator dequantizes by s_x·s_w in one mul)."""
    s = jnp.broadcast_to(
        jnp.asarray(w_scale, jnp.float32).reshape(-1), (cout,)
    )
    if w8a8:
        s = s * jnp.asarray(x_scale, jnp.float32).reshape(())
    return _pad_axis(s.reshape(1, cout), 1, n_co * ob)


def _bias_row(bias, cout, n_co, ob):
    if bias is None:
        return jnp.zeros((1, n_co * ob), jnp.float32)
    return _pad_axis(bias.reshape(1, cout).astype(jnp.float32), 1, n_co * ob)


@functools.partial(
    jax.jit,
    static_argnames=(
        "mode", "stride", "tile_l", "cin_block", "cout_block", "regime",
        "activation", "out_dtype", "interpret",
    ),
)
def conv1d_quant_pallas(
    x: jax.Array,
    w_q: jax.Array,
    w_scale: jax.Array,
    bias: jax.Array | None = None,
    *,
    x_scale: jax.Array | None = None,
    out_scale: jax.Array | None = None,
    mode: str = "w8a8",
    stride: int = 1,
    tile_l: int = DEFAULT_TILE_L,
    cin_block: int | None = None,
    cout_block: int | None = None,
    regime: str | None = None,
    activation: str = "none",
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    """VALID int8 1-D sliding conv. w_q: int8 (K, Cin, Cout); w_scale:
    f32 (Cout,) per-output-channel absmax scales.

    ``mode="w8a8"``: x must be int8 (pre-quantized on the ``x_scale``
    grid); int32 accumulation. ``mode="w8a16"``: x bf16/f32; the weight
    block dequantizes in registers, f32 accumulation. ``out_scale`` set →
    int8 output (requant fused after the activation), else ``out_dtype``.
    """
    w8a8 = mode == "w8a8"
    if w8a8 and x_scale is None:
        raise ValueError("w8a8 needs the activation scale x_scale")
    B, L, Cin = x.shape
    K, _, Cout = w_q.shape
    out_len = (L - K) // stride + 1
    if out_len < 1:
        raise ValueError(
            f"filter K={K} (stride {stride}) exceeds input length {L}"
        )
    regime = _quant_regime(regime, K)
    if stride > 1:  # stride → channels: the kernel slides by one row
        out = conv1d_quant_pallas(
            phase_split(x, stride), phase_taps(w_q, stride), w_scale, bias,
            x_scale=x_scale, out_scale=out_scale, mode=mode, tile_l=tile_l,
            cin_block=cin_block, cout_block=cout_block, regime=regime,
            activation=activation, out_dtype=out_dtype, interpret=interpret,
        )
        return out[:, :out_len]
    tile_l = min(tile_l, out_len)
    n_tiles = pl.cdiv(out_len, tile_l)
    padded_out = n_tiles * tile_l

    cb = _resolve_block(Cin, cin_block)
    ob = _resolve_block(Cout, cout_block)
    n_ci = pl.cdiv(Cin, cb)
    n_co = pl.cdiv(Cout, ob)
    if n_ci * cb > Cin:
        x = _pad_axis(x, 2, n_ci * cb)
        w_q = _pad_axis(w_q, 1, n_ci * cb)
    if n_co * ob > Cout:
        w_q = _pad_axis(w_q, 2, n_co * ob)
    scale2d = _scales(w_scale, x_scale, Cout, n_co, ob, w8a8)
    bias2d = _bias_row(bias, Cout, n_co, ob)

    requant = out_scale is not None
    if regime == "compound":
        # large-K chunking (the f32 compound structure): the reduction grid
        # sweeps Cin blocks × tap chunks; chunk c covers taps
        # [c·TAP_CHUNK, (c+1)·TAP_CHUNK). The kernel body is the unrolled
        # loop over ONE chunk (taps=TAP_CHUNK), so the VMEM working set is
        # chunk-bounded regardless of K.
        n_chunks = pl.cdiv(K, TAP_CHUNK)
        # zero taps contribute nothing (int8 zeros)
        w_q = _pad_axis(w_q, 0, n_chunks * TAP_CHUNK)
        n_red = n_ci * n_chunks
        x, halo = halo_input(
            x, 1, (n_tiles - 1) * tile_l + (n_chunks - 1) * TAP_CHUNK,
            tile_l - 1 + TAP_CHUNK,
        )
        kernel = functools.partial(
            _qkernel_1d, taps=TAP_CHUNK, tile_l=tile_l,
            n_red=n_red, activation=activation, w8a8=w8a8, requant=requant,
            regime="generic",
        )
        # reduction index r decomposes as (cin block, tap chunk): the tap
        # chunk is fastest so a cin block's taps complete consecutively
        in_specs = [
            halo_spec((halo, cb), n_ci, lambda b, i, co, r: (
                b, i * tile_l + (r % n_chunks) * TAP_CHUNK, r // n_chunks,
            )),
            pl.BlockSpec(
                (TAP_CHUNK, cb, ob),
                lambda b, i, co, r: (r % n_chunks, r // n_chunks, co),
            ),
            pl.BlockSpec((1, ob), lambda b, i, co, r: (0, co)),
            pl.BlockSpec((1, ob), lambda b, i, co, r: (0, co)),
        ]
    else:
        n_red = n_ci
        kernel = functools.partial(
            _qkernel_1d, taps=K, tile_l=tile_l, n_red=n_red,
            activation=activation, w8a8=w8a8, requant=requant, regime=regime,
        )
        x, halo = halo_input(x, 1, (n_tiles - 1) * tile_l, tile_l - 1 + K)
        in_specs = [
            halo_spec((halo, cb), n_ci, lambda b, i, co, r: (b, i * tile_l, r)),
            pl.BlockSpec((K, cb, ob), lambda b, i, co, r: (0, r, co)),
            pl.BlockSpec((1, ob), lambda b, i, co, r: (0, co)),  # dequant scale
            pl.BlockSpec((1, ob), lambda b, i, co, r: (0, co)),  # bias
        ]
    args = [x, w_q, scale2d, bias2d]
    if requant:
        in_specs.append(pl.BlockSpec((1, 1), lambda b, i, co, r: (0, 0)))
        args.append(jnp.asarray(out_scale, jnp.float32).reshape(1, 1))
    odt = jnp.int8 if requant else jnp.dtype(out_dtype)
    out = pl.pallas_call(
        kernel,
        grid=(B, n_tiles, n_co, n_red),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, tile_l, ob), lambda b, i, co, r: (b, i, co)
        ),
        out_shape=jax.ShapeDtypeStruct((B, padded_out, n_co * ob), odt),
        scratch_shapes=(
            []
            if n_red == 1
            else [pltpu.VMEM((tile_l, ob), _acc_dtype(w8a8))]
        ),
        interpret=interpret,
    )(*args)
    return out[:, :out_len, :Cout]


@functools.partial(
    jax.jit,
    static_argnames=(
        "mode", "stride", "tile_h", "tile_w", "cin_block", "cout_block",
        "regime", "activation", "out_dtype", "interpret",
    ),
)
def conv2d_quant_pallas(
    x: jax.Array,
    w_q: jax.Array,
    w_scale: jax.Array,
    bias: jax.Array | None = None,
    *,
    x_scale: jax.Array | None = None,
    out_scale: jax.Array | None = None,
    mode: str = "w8a8",
    stride: tuple[int, int] = (1, 1),
    tile_h: int = DEFAULT_TILE_H,
    tile_w: int = DEFAULT_TILE_W,
    cin_block: int | None = None,
    cout_block: int | None = None,
    regime: str | None = None,
    activation: str = "none",
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    """VALID int8 2-D sliding conv. x: (B,H,W,Cin) int8 (w8a8) or float
    (w8a16); w_q: int8 HWIO; w_scale: f32 (Cout,). See conv1d_quant_pallas."""
    w8a8 = mode == "w8a8"
    if w8a8 and x_scale is None:
        raise ValueError("w8a8 needs the activation scale x_scale")
    B, H, W, Cin = x.shape
    kh, kw, _, Cout = w_q.shape
    sh, sw = stride
    oh = (H - kh) // sh + 1
    ow = (W - kw) // sw + 1
    if oh < 1 or ow < 1:
        raise ValueError(
            f"filter ({kh},{kw}) (stride {stride}) exceeds input ({H},{W})"
        )
    if regime not in ("custom", "generic", "compound"):
        regime = (
            "custom"
            if (kh == kw and kh in (3, 5))
            else ("generic" if kw <= 17 else "compound")
        )
    th = min(tile_h, oh)
    tw = min(tile_w, ow)
    nh = pl.cdiv(oh, th)
    nw = pl.cdiv(ow, tw)

    cb = _resolve_block(Cin, cin_block)
    ob = _resolve_block(Cout, cout_block)
    n_ci = pl.cdiv(Cin, cb)
    n_co = pl.cdiv(Cout, ob)
    if n_ci * cb > Cin:
        x = _pad_axis(x, 3, n_ci * cb)
        w_q = _pad_axis(w_q, 2, n_ci * cb)
    if n_co * ob > Cout:
        w_q = _pad_axis(w_q, 3, n_co * ob)
    scale2d = _scales(w_scale, x_scale, Cout, n_co, ob, w8a8)
    bias2d = _bias_row(bias, Cout, n_co, ob)

    requant = out_scale is not None
    if regime == "compound":
        # filter-ROW chunking (the f32 compound structure): reduction grid
        # sweeps Cin blocks × row chunks, the body unrolls ROW_CHUNK×kw taps
        n_chunks = pl.cdiv(kh, ROW_CHUNK)
        w_q = _pad_axis(w_q, 0, n_chunks * ROW_CHUNK)
        n_red = n_ci * n_chunks
        x, halo_h, halo_w = halo_input_2d(
            x, (nh - 1) * th * sh + (n_chunks - 1) * ROW_CHUNK,
            (th - 1) * sh + ROW_CHUNK, (nw - 1) * tw * sw, (tw - 1) * sw + kw,
        )
        kernel = functools.partial(
            _qkernel_2d, kh=ROW_CHUNK, kw=kw, th=th, tw=tw, sh=sh, sw=sw,
            n_red=n_red, activation=activation, w8a8=w8a8, requant=requant,
            regime="generic",
        )
        in_specs = [
            halo_spec((halo_h, halo_w, cb), n_ci, lambda b, i, j, co, r: (
                b, i * th * sh + (r % n_chunks) * ROW_CHUNK, j * tw * sw,
                r // n_chunks,
            )),
            pl.BlockSpec(
                (ROW_CHUNK, kw, cb, ob),
                lambda b, i, j, co, r: (r % n_chunks, 0, r // n_chunks, co),
            ),
            pl.BlockSpec((1, ob), lambda b, i, j, co, r: (0, co)),
            pl.BlockSpec((1, ob), lambda b, i, j, co, r: (0, co)),
        ]
    else:
        n_red = n_ci
        kernel = functools.partial(
            _qkernel_2d, kh=kh, kw=kw, th=th, tw=tw, sh=sh, sw=sw,
            n_red=n_red, activation=activation, w8a8=w8a8, requant=requant,
            regime=regime,
        )
        x, halo_h, halo_w = halo_input_2d(
            x, (nh - 1) * th * sh, (th - 1) * sh + kh,
            (nw - 1) * tw * sw, (tw - 1) * sw + kw,
        )
        in_specs = [
            halo_spec((halo_h, halo_w, cb), n_ci, lambda b, i, j, co, r: (
                b, i * th * sh, j * tw * sw, r,
            )),
            pl.BlockSpec(
                (kh, kw, cb, ob), lambda b, i, j, co, r: (0, 0, r, co)
            ),
            pl.BlockSpec((1, ob), lambda b, i, j, co, r: (0, co)),
            pl.BlockSpec((1, ob), lambda b, i, j, co, r: (0, co)),
        ]
    args = [x, w_q, scale2d, bias2d]
    if requant:
        in_specs.append(pl.BlockSpec((1, 1), lambda b, i, j, co, r: (0, 0)))
        args.append(jnp.asarray(out_scale, jnp.float32).reshape(1, 1))
    odt = jnp.int8 if requant else jnp.dtype(out_dtype)
    out = pl.pallas_call(
        kernel,
        grid=(B, nh, nw, n_co, n_red),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, th, tw, ob), lambda b, i, j, co, r: (b, i, j, co)
        ),
        out_shape=jax.ShapeDtypeStruct((B, nh * th, nw * tw, n_co * ob), odt),
        scratch_shapes=(
            []
            if n_red == 1
            else [pltpu.VMEM((th * tw, ob), _acc_dtype(w8a8))]
        ),
        interpret=interpret,
    )(*args)
    return out[:, :oh, :ow, :Cout]


# ---------------------------------------------------------------------------
# depthwise (VPU) int8 kernel — the mamba/jamba serving conv
# ---------------------------------------------------------------------------

def _qkernel_depthwise(
    x_ref, w_ref, s_ref, b_ref, *rest, taps, tile_l, stride, activation,
    w8a8, requant,
):
    """int8 depthwise body: per-tap shifted elementwise FMA on the VPU —
    int8×int8→int32 (w8a8) or float×register-dequantized-int8→f32 (w8a16);
    per-channel dequant rides the shared epilogue. Channels are independent
    (no reduction grid dim), so no revisit scratch is needed."""
    os_ref = rest[0] if requant else None
    o_ref = rest[1] if requant else rest[0]
    adt = _acc_dtype(w8a8)
    acc = jnp.zeros((tile_l, x_ref.shape[-1]), adt)
    for k in range(taps):
        xs = _slide(x_ref, k, tile_l, stride)
        acc += xs.astype(adt) * w_ref[k].astype(adt)
    _dequant_epilogue(
        acc, os_ref, o_ref, s_ref=s_ref, b_ref=b_ref, activation=activation
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "mode", "stride", "tile_l", "c_block", "activation", "out_dtype",
        "interpret",
    ),
)
def conv1d_depthwise_quant_pallas(
    x: jax.Array,
    w_q: jax.Array,
    w_scale: jax.Array,
    bias: jax.Array | None = None,
    *,
    x_scale: jax.Array | None = None,
    out_scale: jax.Array | None = None,
    mode: str = "w8a8",
    stride: int = 1,
    tile_l: int = DEFAULT_TILE_L,
    c_block: int | None = None,
    activation: str = "none",
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    """VALID int8 depthwise sliding conv. x: (B, L, C) int8 (w8a8) or float
    (w8a16); w_q: int8 (K, C); w_scale: f32 (C,) per-channel tap-axis
    absmax scales. ``out_scale`` fuses an int8 requant after the
    activation; otherwise output is ``out_dtype``."""
    w8a8 = mode == "w8a8"
    if w8a8 and x_scale is None:
        raise ValueError("w8a8 needs the activation scale x_scale")
    B, L, C = x.shape
    K, _ = w_q.shape
    out_len = (L - K) // stride + 1
    if out_len < 1:
        raise ValueError(
            f"filter K={K} (stride {stride}) exceeds input length {L}"
        )
    tile_l = min(tile_l, out_len)
    n_tiles = pl.cdiv(out_len, tile_l)
    padded_out = n_tiles * tile_l
    step = tile_l * stride
    x, halo = halo_input(x, 1, (n_tiles - 1) * step, (tile_l - 1) * stride + K)
    cb = _resolve_block(C, c_block)
    n_c = pl.cdiv(C, cb)
    if n_c * cb > C:
        x = _pad_axis(x, 2, n_c * cb)
        w_q = _pad_axis(w_q, 1, n_c * cb)
    s = jnp.broadcast_to(jnp.asarray(w_scale, jnp.float32).reshape(-1), (C,))
    if w8a8:
        s = s * jnp.asarray(x_scale, jnp.float32).reshape(())
    scale2d = _pad_axis(s.reshape(1, C), 1, n_c * cb)
    bias2d = _bias_row(bias, C, n_c, cb)

    requant = out_scale is not None
    kernel = functools.partial(
        _qkernel_depthwise, taps=K, tile_l=tile_l, stride=stride,
        activation=activation, w8a8=w8a8, requant=requant,
    )
    in_specs = [
        halo_spec((halo, cb), n_c, lambda b, i, c: (b, i * step, c)),
        pl.BlockSpec((K, cb), lambda b, i, c: (0, c)),
        pl.BlockSpec((1, cb), lambda b, i, c: (0, c)),  # dequant scale
        pl.BlockSpec((1, cb), lambda b, i, c: (0, c)),  # bias
    ]
    args = [x, w_q, scale2d, bias2d]
    if requant:
        in_specs.append(pl.BlockSpec((1, 1), lambda b, i, c: (0, 0)))
        args.append(jnp.asarray(out_scale, jnp.float32).reshape(1, 1))
    odt = jnp.int8 if requant else jnp.dtype(out_dtype)
    out = pl.pallas_call(
        kernel,
        grid=(B, n_tiles, n_c),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, tile_l, cb), lambda b, i, c: (b, i, c)),
        out_shape=jax.ShapeDtypeStruct((B, padded_out, n_c * cb), odt),
        interpret=interpret,
    )(*args)
    return out[:, :out_len, :C]
