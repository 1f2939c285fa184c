"""Pallas TPU kernels: backward passes for the sliding-window convolutions.

The companion paper (Snytsar, arXiv:2305.16513) derives the sliding-sum
kernel structure for both directions; this module is the reverse-mode half
that makes the Pallas path in ``repro.kernels.ops`` trainable. Structure
(DESIGN.md §6):

  * **dx** — a sliding *correlation* of the upstream gradient with the
    spatially-flipped, Cin/Cout-transposed weights. ``stride > 1`` is
    handled by dilating dy (inserting ``stride-1`` zeros between rows),
    after which dx is an ordinary stride-1 VALID sliding conv — so dx
    REUSES the forward sliding kernels (same regimes, same channel
    blocking, its own autotune shape key). The weight flip/transpose is a
    pure layout transform done once outside the kernel.
  * **dw** — a halo-tiled sliding *reduction* over (x, dy): the grid walks
    output tiles exactly like the forward kernel, but the reduction grid
    dimensions are (batch × spatial tiles) and the revisited output block
    is the **weight gradient** ``(K, cin_block, cout_block)``, accumulated
    in f32 VMEM scratch. Each visit contributes one tap-sliced
    ``x_tileᵀ @ dy_tile`` MXU matmul per tap.
  * **db** — emitted by the same dw kernel launch as a second output: the
    ``(1, cout_block)`` reduction of dy, accumulated in its own f32
    scratch on the ``cin_block == 0`` visits only (dy does not vary with
    the Cin block, so other visits would double-count).
  * **d_act** — ``act_bwd`` forms ``dz = dy · act'(z)`` from the saved
    post-bias pre-activation residual ``z`` (``save_preact=True`` in the
    forward kernels); exact VJP of the epilogue's f32 activation.

All kernels accumulate in f32 and cast once to the parameter dtype; padded
output rows / channels are zero in dy and therefore contribute nothing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.sliding_conv1d import (
    DEFAULT_TILE_L,
    _pad_axis,
    _resolve_block,
    _slide,
    apply_activation,
    conv1d_depthwise_pallas,
    conv1d_sliding_pallas,
    halo_input,
    halo_spec,
    phase_split,
)
from repro.kernels.sliding_conv2d import (
    DEFAULT_TILE_H,
    DEFAULT_TILE_W,
    _shifted,
    conv2d_sliding_pallas,
    halo_input_2d,
)


# ---------------------------------------------------------------------------
# epilogue backward
# ---------------------------------------------------------------------------

def act_bwd(dy: jax.Array, z: jax.Array | None, activation: str) -> jax.Array:
    """dz = dy · act'(z) from the saved pre-activation residual (f32 math)."""
    if activation in (None, "none"):
        return dy
    if z is None:
        raise ValueError(f"activation {activation!r} needs the saved preact")
    zf = z.astype(jnp.float32)
    _, vjp = jax.vjp(lambda t: apply_activation(t, activation), zf)
    return vjp(dy.astype(jnp.float32))[0].astype(dy.dtype)


# ---------------------------------------------------------------------------
# dilation helpers (stride > 1 backward)
# ---------------------------------------------------------------------------

def dilate1d(dy: jax.Array, stride: int) -> jax.Array:
    """Insert ``stride-1`` zero rows between dy rows along axis 1."""
    if stride == 1:
        return dy
    B, n, C = dy.shape
    out = jnp.zeros((B, (n - 1) * stride + 1, C), dy.dtype)
    return out.at[:, ::stride].set(dy)


def dilate2d(dy: jax.Array, stride: tuple[int, int]) -> jax.Array:
    """Insert zeros between dy rows/cols along axes 1, 2."""
    sh, sw = stride
    if sh == 1 and sw == 1:
        return dy
    B, h, w, C = dy.shape
    out = jnp.zeros((B, (h - 1) * sh + 1, (w - 1) * sw + 1, C), dy.dtype)
    return out.at[:, ::sh, ::sw].set(dy)


# ---------------------------------------------------------------------------
# dx — sliding correlation with flipped, transposed weights
# ---------------------------------------------------------------------------
# These produce the dilated+padded gradient and the transformed weights; the
# actual conv runs through the caller-supplied forward dispatch (so dx gets
# its own autotune shape key and channel blocking).

def conv1d_dx_operands(dz, w, *, stride):
    """(dilated+padded dz, flipped Cin↔Cout-transposed weights) for dx."""
    K = w.shape[0]
    dzp = jnp.pad(dilate1d(dz, stride), ((0, 0), (K - 1, K - 1), (0, 0)))
    wt = jnp.flip(w, 0).swapaxes(1, 2)  # (K, Cout, Cin)
    return dzp, wt


def conv2d_dx_operands(dz, w, *, stride):
    kh, kw = w.shape[:2]
    dzp = jnp.pad(
        dilate2d(dz, stride),
        ((0, 0), (kh - 1, kh - 1), (kw - 1, kw - 1), (0, 0)),
    )
    wt = jnp.flip(w, (0, 1)).swapaxes(2, 3)  # (kh, kw, Cout, Cin)
    return dzp, wt


def _fit_len(dx, L, axis=1):
    """Zero-pad dx up to the forward input length (trailing rows the forward
    pass never read get zero gradient)."""
    if dx.shape[axis] < L:
        pads = [(0, 0)] * dx.ndim
        pads[axis] = (0, L - dx.shape[axis])
        dx = jnp.pad(dx, pads)
    return dx


def conv1d_dx(dz, w, *, stride, L, tile_l=None, interpret=False):
    """dx via the forward sliding kernel on the dilated gradient (no tuned
    dispatch — ``repro.kernels.ops`` routes dx through its tuned path; this
    helper is the direct kernel-level form used by tests)."""
    dzp, wt = conv1d_dx_operands(dz, w, stride=stride)
    dx = conv1d_sliding_pallas(
        dzp, wt, None, stride=1,
        tile_l=tile_l or DEFAULT_TILE_L, interpret=interpret,
    )
    return _fit_len(dx, L)


def conv1d_depthwise_dx(dz, w, *, stride, L, tile_l=None, c_block=None,
                        interpret=False):
    K = w.shape[0]
    dzp = jnp.pad(dilate1d(dz, stride), ((0, 0), (K - 1, K - 1), (0, 0)))
    dx = conv1d_depthwise_pallas(
        dzp, jnp.flip(w, 0), None, stride=1,
        tile_l=tile_l or DEFAULT_TILE_L, c_block=c_block, interpret=interpret,
    )
    return _fit_len(dx, L)


# ---------------------------------------------------------------------------
# dw/db kernels — halo-tiled sliding reduction over (x, dy)
# ---------------------------------------------------------------------------

def _rs_flags(red_ids: tuple, red_sizes: tuple):
    """(first-visit, last-visit) predicates over the reduction grid dims."""
    first = red_ids[0] == 0
    last = red_ids[0] == red_sizes[0] - 1
    for rid, n in zip(red_ids[1:], red_sizes[1:]):
        first &= rid == 0
        last &= rid == n - 1
    return first, last


def _accumulate(acc, scratch, out_ref, first, last, gate=None):
    """Scratch-accumulate ``acc`` across reduction visits; flush on the last
    visit. ``gate`` (e.g. "cin block == 0" for db) restricts participation."""
    if gate is not None:
        first = first & gate
        last = last & gate
        add = gate & ~first
    else:
        add = ~first

    @pl.when(first)
    def _init():
        scratch[...] = acc

    @pl.when(add)
    def _add():
        scratch[...] += acc

    @pl.when(last)
    def _flush():
        out_ref[...] = scratch[...].astype(out_ref.dtype)


def _dw1d_kernel(x_ref, dz_ref, *rest, taps, tile_l, nb, nt, has_bias):
    """One visit: per-tap ``x_slideᵀ @ dz`` partial products for this
    (cout block, cin block) weight-gradient tile."""
    if has_bias:
        dw_ref, db_ref, dw_acc, db_acc = rest
    else:
        (dw_ref, dw_acc), db_ref, db_acc = rest, None, None
    dz = dz_ref[0].astype(jnp.float32)
    acc = jnp.stack(
        [
            jnp.dot(
                _slide(x_ref, k, tile_l).astype(jnp.float32).T, dz,
                preferred_element_type=jnp.float32,
            )
            for k in range(taps)
        ]
    )  # (K, cin_block, cout_block)
    first, last = _rs_flags(
        (pl.program_id(2), pl.program_id(3)), (nb, nt)
    )
    _accumulate(acc, dw_acc, dw_ref, first, last)
    if has_bias:
        _accumulate(
            dz.sum(axis=0, keepdims=True), db_acc, db_ref, first, last,
            gate=pl.program_id(1) == 0,  # dy is Cin-block invariant
        )


@functools.partial(
    jax.jit,
    static_argnames=(
        "w_shape_k", "stride", "tile_l", "cin_block", "cout_block",
        "has_bias", "interpret",
    ),
)
def conv1d_bwd_dw_pallas(
    x: jax.Array,
    dz: jax.Array,
    w_shape_k: int,
    *,
    stride: int = 1,
    tile_l: int = DEFAULT_TILE_L,
    cin_block: int | None = None,
    cout_block: int | None = None,
    has_bias: bool = False,
    interpret: bool = False,
):
    """Weight/bias gradient of the VALID 1-D sliding conv.

    x: (B, L, Cin) — the (padded) forward input; dz: (B, out_len, Cout) —
    the post-epilogue gradient. Returns ``(dw, db)`` with
    dw: (K, Cin, Cout) f32 and db: (Cout,) f32 (db is None without bias).
    A stride above 1 runs the stride-1 kernel over ``phase_split(x)`` and
    folds the phase-stacked taps back (see ``phase_taps``).
    """
    K = w_shape_k
    B, L, Cin = x.shape
    _, out_len, Cout = dz.shape
    if stride > 1:
        dw, db = conv1d_bwd_dw_pallas(
            phase_split(x, stride), dz, pl.cdiv(K, stride), tile_l=tile_l,
            cin_block=cin_block, cout_block=cout_block, has_bias=has_bias,
            interpret=interpret,
        )
        return dw.reshape(-1, Cin, Cout)[:K], db
    tile_l = min(tile_l, out_len)
    n_tiles = pl.cdiv(out_len, tile_l)
    padded_out = n_tiles * tile_l
    x, halo = halo_input(x, 1, (n_tiles - 1) * tile_l, tile_l - 1 + K)
    if padded_out > out_len:  # zero rows contribute nothing to the reduction
        dz = jnp.pad(dz, ((0, 0), (0, padded_out - out_len), (0, 0)))
    cb = _resolve_block(Cin, cin_block)
    ob = _resolve_block(Cout, cout_block)
    n_ci = pl.cdiv(Cin, cb)
    n_co = pl.cdiv(Cout, ob)
    if n_ci * cb > Cin:
        x = _pad_axis(x, 2, n_ci * cb)
    if n_co * ob > Cout:
        dz = _pad_axis(dz, 2, n_co * ob)

    kernel = functools.partial(
        _dw1d_kernel, taps=K, tile_l=tile_l, nb=B, nt=n_tiles,
        has_bias=has_bias,
    )
    # grid: weight-gradient blocks outermost, the (batch, spatial-tile)
    # reduction innermost so each (co, ci) block's visits are consecutive.
    in_specs = [
        halo_spec((halo, cb), n_ci, lambda co, ci, b, i: (b, i * tile_l, ci)),
        pl.BlockSpec((1, tile_l, ob), lambda co, ci, b, i: (b, i, co)),
    ]
    out_specs = [
        pl.BlockSpec((K, cb, ob), lambda co, ci, b, i: (0, ci, co)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((K, n_ci * cb, n_co * ob), jnp.float32),
    ]
    scratch = [pltpu.VMEM((K, cb, ob), jnp.float32)]
    if has_bias:
        out_specs.append(pl.BlockSpec((1, ob), lambda co, ci, b, i: (0, co)))
        out_shape.append(jax.ShapeDtypeStruct((1, n_co * ob), jnp.float32))
        scratch.append(pltpu.VMEM((1, ob), jnp.float32))
    out = pl.pallas_call(
        kernel,
        grid=(n_co, n_ci, B, n_tiles),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
    )(x, dz)
    dw = out[0][:, :Cin, :Cout]
    db = out[1][0, :Cout] if has_bias else None
    return dw, db


def _dw_depthwise_kernel(
    x_ref, dz_ref, *rest, taps, tile_l, stride, nb, nt, has_bias
):
    if has_bias:
        dw_ref, db_ref, dw_acc, db_acc = rest
    else:
        (dw_ref, dw_acc), db_ref, db_acc = rest, None, None
    dz = dz_ref[0].astype(jnp.float32)
    acc = jnp.stack(
        [
            (_slide(x_ref, k, tile_l, stride).astype(jnp.float32) * dz).sum(
                axis=0
            )
            for k in range(taps)
        ]
    )  # (K, c_block)
    first, last = _rs_flags(
        (pl.program_id(1), pl.program_id(2)), (nb, nt)
    )
    _accumulate(acc, dw_acc, dw_ref, first, last)
    if has_bias:
        _accumulate(dz.sum(axis=0, keepdims=True), db_acc, db_ref, first, last)


@functools.partial(
    jax.jit,
    static_argnames=(
        "w_shape_k", "stride", "tile_l", "c_block", "has_bias", "interpret",
    ),
)
def conv1d_depthwise_bwd_dw_pallas(
    x: jax.Array,
    dz: jax.Array,
    w_shape_k: int,
    *,
    stride: int = 1,
    tile_l: int = DEFAULT_TILE_L,
    c_block: int | None = None,
    has_bias: bool = False,
    interpret: bool = False,
):
    """Weight/bias gradient of the VALID depthwise conv. x: (B, L, C),
    dz: (B, out_len, C) → dw (K, C) f32, db (C,) f32 | None."""
    K = w_shape_k
    B, L, C = x.shape
    out_len = dz.shape[1]
    tile_l = min(tile_l, out_len)
    n_tiles = pl.cdiv(out_len, tile_l)
    padded_out = n_tiles * tile_l
    step = tile_l * stride
    x, halo = halo_input(x, 1, (n_tiles - 1) * step, (tile_l - 1) * stride + K)
    if padded_out > out_len:
        dz = jnp.pad(dz, ((0, 0), (0, padded_out - out_len), (0, 0)))
    cb = _resolve_block(C, c_block)
    n_c = pl.cdiv(C, cb)
    if n_c * cb > C:
        x = _pad_axis(x, 2, n_c * cb)
        dz = _pad_axis(dz, 2, n_c * cb)
    kernel = functools.partial(
        _dw_depthwise_kernel, taps=K, tile_l=tile_l, stride=stride, nb=B,
        nt=n_tiles, has_bias=has_bias,
    )
    in_specs = [
        halo_spec((halo, cb), n_c, lambda c, b, i: (b, i * step, c)),
        pl.BlockSpec((1, tile_l, cb), lambda c, b, i: (b, i, c)),
    ]
    out_specs = [pl.BlockSpec((K, cb), lambda c, b, i: (0, c))]
    out_shape = [jax.ShapeDtypeStruct((K, n_c * cb), jnp.float32)]
    scratch = [pltpu.VMEM((K, cb), jnp.float32)]
    if has_bias:
        out_specs.append(pl.BlockSpec((1, cb), lambda c, b, i: (0, c)))
        out_shape.append(jax.ShapeDtypeStruct((1, n_c * cb), jnp.float32))
        scratch.append(pltpu.VMEM((1, cb), jnp.float32))
    out = pl.pallas_call(
        kernel,
        grid=(n_c, B, n_tiles),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
    )(x, dz)
    dw = out[0][:, :C]
    db = out[1][0, :C] if has_bias else None
    return dw, db


def _dw2d_kernel(
    x_ref, dz_ref, *rest, kh, kw, th, tw, sh, sw, nb, nh, nw, has_bias
):
    if has_bias:
        dw_ref, db_ref, dw_acc, db_acc = rest
    else:
        (dw_ref, dw_acc), db_ref, db_acc = rest, None, None
    x = x_ref[0]
    cin = x.shape[-1]
    dz = dz_ref[0].astype(jnp.float32).reshape(th * tw, -1)
    rows = []
    for i in range(kh):
        row = []
        for j in range(kw):
            xs = _shifted(x, i, j, th, tw, sh, sw).reshape(th * tw, cin)
            row.append(
                jnp.dot(
                    xs.astype(jnp.float32).T, dz,
                    preferred_element_type=jnp.float32,
                )
            )
        rows.append(jnp.stack(row))
    acc = jnp.stack(rows)  # (kh, kw, cin_block, cout_block)
    first, last = _rs_flags(
        (pl.program_id(2), pl.program_id(3), pl.program_id(4)), (nb, nh, nw)
    )
    _accumulate(acc, dw_acc, dw_ref, first, last)
    if has_bias:
        _accumulate(
            dz.sum(axis=0, keepdims=True), db_acc, db_ref, first, last,
            gate=pl.program_id(1) == 0,
        )


@functools.partial(
    jax.jit,
    static_argnames=(
        "w_shape_hw", "stride", "tile_h", "tile_w", "cin_block",
        "cout_block", "has_bias", "interpret",
    ),
)
def conv2d_bwd_dw_pallas(
    x: jax.Array,
    dz: jax.Array,
    w_shape_hw: tuple[int, int],
    *,
    stride: tuple[int, int] = (1, 1),
    tile_h: int = DEFAULT_TILE_H,
    tile_w: int = DEFAULT_TILE_W,
    cin_block: int | None = None,
    cout_block: int | None = None,
    has_bias: bool = False,
    interpret: bool = False,
):
    """Weight/bias gradient of the VALID 2-D sliding conv. x: (B,H,W,Cin),
    dz: (B,oh,ow,Cout) → dw (kh,kw,Cin,Cout) f32, db (Cout,) f32 | None."""
    kh, kw = w_shape_hw
    sh, sw = stride
    B, H, W, Cin = x.shape
    _, oh, ow, Cout = dz.shape
    th = min(tile_h, oh)
    tw = min(tile_w, ow)
    nh = pl.cdiv(oh, th)
    nw = pl.cdiv(ow, tw)
    x, halo_h, halo_w = halo_input_2d(
        x, (nh - 1) * th * sh, (th - 1) * sh + kh,
        (nw - 1) * tw * sw, (tw - 1) * sw + kw,
    )
    if nh * th > oh or nw * tw > ow:
        dz = jnp.pad(
            dz, ((0, 0), (0, nh * th - oh), (0, nw * tw - ow), (0, 0))
        )
    cb = _resolve_block(Cin, cin_block)
    ob = _resolve_block(Cout, cout_block)
    n_ci = pl.cdiv(Cin, cb)
    n_co = pl.cdiv(Cout, ob)
    if n_ci * cb > Cin:
        x = _pad_axis(x, 3, n_ci * cb)
    if n_co * ob > Cout:
        dz = _pad_axis(dz, 3, n_co * ob)
    kernel = functools.partial(
        _dw2d_kernel, kh=kh, kw=kw, th=th, tw=tw, sh=sh, sw=sw, nb=B,
        nh=nh, nw=nw, has_bias=has_bias,
    )
    in_specs = [
        halo_spec((halo_h, halo_w, cb), n_ci, lambda co, ci, b, i, j: (
            b, i * th * sh, j * tw * sw, ci,
        )),
        pl.BlockSpec((1, th, tw, ob), lambda co, ci, b, i, j: (b, i, j, co)),
    ]
    out_specs = [
        pl.BlockSpec((kh, kw, cb, ob), lambda co, ci, b, i, j: (0, 0, ci, co)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((kh, kw, n_ci * cb, n_co * ob), jnp.float32),
    ]
    scratch = [pltpu.VMEM((kh, kw, cb, ob), jnp.float32)]
    if has_bias:
        out_specs.append(
            pl.BlockSpec((1, ob), lambda co, ci, b, i, j: (0, co))
        )
        out_shape.append(jax.ShapeDtypeStruct((1, n_co * ob), jnp.float32))
        scratch.append(pltpu.VMEM((1, ob), jnp.float32))
    out = pl.pallas_call(
        kernel,
        grid=(n_co, n_ci, B, nh, nw),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
    )(x, dz)
    dw = out[0][:, :, :Cin, :Cout]
    db = out[1][0, :Cout] if has_bias else None
    return dw, db
