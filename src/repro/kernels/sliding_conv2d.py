"""Pallas TPU kernels: 2-D Sliding Window convolution (paper §2, main result).

The 2-D extension keeps the 1-D structure: the kernel walks the kh×kw filter
taps, each tap being a 2-D-shifted in-VMEM view of the halo tile followed by
an MXU matmul over channels. Regimes (selected on the filter *width* kw, as
in the paper where the width determines hardware-vector fit):

  * ``custom``   (kh=kw ∈ {3,5}) — all taps stacked along channels in VMEM,
    ONE (TH·TW, kh·kw·Cin) @ (kh·kw·Cin, Cout) matmul.
  * ``generic``  (kw ≤ 17)       — unrolled tap loop, kh·kw shifted matmuls.
  * ``compound`` (kw > 17)       — filter *rows* processed in chunks of
    ``ROW_CHUNK`` via the reduction grid dimension revisiting the output
    block (accumulation), so the VMEM working set stays bounded for large
    filters: chunk c covers filter rows [c·ROW_CHUNK, (c+1)·ROW_CHUNK).

Channel blocking (DESIGN.md §3): ``cin_block``/``cout_block`` add Cout-block
and Cin-block grid dimensions; a kernel instance holds only a
``(kh, kw, cin_block, cout_block)`` weight tile and a
``(halo_h, halo_w, cin_block)`` input tile. Cin-block partials accumulate in
an f32 VMEM scratch across output-block revisits (reduction innermost).

Fused epilogue: ``bias`` (Cout,) + ``activation`` (none/relu/gelu/silu)
applied on the last reduction visit — conv→bias→act in one launch.

Layout NHWC, weights HWIO, f32 accumulation. Output tiling is (TH, TW);
input blocks carry a (kh-1, kw-1) halo as ``pl.Element`` blocks whose index
maps return element offsets (``halo_input_2d``, ``sliding_conv1d.halo_spec``).
The im2col column tensor is never materialized — compare
``repro.kernels.im2col_gemm``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.sliding_conv1d import (
    _pad_axis,
    _reduce_store,
    _resolve_block,
    apply_activation,
    halo_rows,
    halo_spec,
)

DEFAULT_TILE_H = 16
DEFAULT_TILE_W = 128
ROW_CHUNK = 4  # filter rows per compound chunk


def halo_input_2d(x, last_h: int, span_h: int, last_w: int, span_w: int):
    """2-D ``sliding_conv1d.halo_input``: the width halo (the block's
    second-to-last axis) is rounded up to whole sublanes, and ``x``
    (B, H, W, C) is zero-padded so the windows read at element offsets
    ``(last_h, last_w)`` stay in bounds. Returns ``(x, halo_h, halo_w)``."""
    halo_w = halo_rows(span_w)
    x = _pad_axis(_pad_axis(x, 1, last_h + span_h), 2, last_w + halo_w)
    return x, span_h, halo_w


def _shifted(x, i, j, th, tw, sh, sw):
    xs = x[i : i + (th - 1) * sh + 1, j : j + (tw - 1) * sw + 1]
    if sh > 1 or sw > 1:
        xs = xs[::sh, ::sw]
    return xs


def _finish(acc, bias_ref, o_ref, z_ref=None, *, th, tw, activation):
    cout = o_ref.shape[-1]
    if bias_ref is not None:
        acc = acc + bias_ref[0].astype(jnp.float32)
    if z_ref is not None:  # pre-activation residual for the backward pass
        z_ref[0] = acc.reshape(th, tw, cout).astype(z_ref.dtype)
    o_ref[0] = apply_activation(acc, activation).reshape(th, tw, cout).astype(
        o_ref.dtype
    )


def _kernel_generic(
    x_ref, w_ref, *rest, kh, kw, th, tw, sh, sw, n_red, activation, has_bias,
    n_out,
):
    x = x_ref[0]
    cout = w_ref.shape[-1]
    acc = jnp.zeros((th * tw, cout), jnp.float32)
    for i in range(kh):
        for j in range(kw):
            xs = _shifted(x, i, j, th, tw, sh, sw).reshape(th * tw, -1)
            acc += jnp.dot(xs, w_ref[i, j], preferred_element_type=jnp.float32)
    _reduce_store(
        acc, rest, has_bias=has_bias, n_red=n_red, red_axis=4, n_out=n_out,
        finish=functools.partial(_finish, th=th, tw=tw, activation=activation),
    )


def _kernel_custom(
    x_ref, w_ref, *rest, kh, kw, th, tw, sh, sw, n_red, activation, has_bias,
    n_out,
):
    x = x_ref[0]
    cin = x.shape[-1]
    cout = w_ref.shape[-1]
    cols = []
    for i in range(kh):
        for j in range(kw):
            cols.append(_shifted(x, i, j, th, tw, sh, sw).reshape(th * tw, cin))
    stacked = jnp.concatenate(cols, axis=-1)  # (TH*TW, kh*kw*cin): VMEM only
    wf = w_ref[...].reshape(kh * kw * cin, cout)
    acc = jnp.dot(stacked, wf, preferred_element_type=jnp.float32)
    _reduce_store(
        acc, rest, has_bias=has_bias, n_red=n_red, red_axis=4, n_out=n_out,
        finish=functools.partial(_finish, th=th, tw=tw, activation=activation),
    )


def _kernel_compound(
    x_ref, w_ref, *rest, rows, kw, th, tw, sh, sw, n_red, activation, has_bias,
    n_out,
):
    x = x_ref[0]
    cout = w_ref.shape[-1]
    acc = jnp.zeros((th * tw, cout), jnp.float32)
    for i in range(rows):  # filter rows within this chunk
        for j in range(kw):
            xs = _shifted(x, i, j, th, tw, sh, sw).reshape(th * tw, -1)
            acc += jnp.dot(xs, w_ref[i, j], preferred_element_type=jnp.float32)
    _reduce_store(
        acc, rest, has_bias=has_bias, n_red=n_red, red_axis=4, n_out=n_out,
        finish=functools.partial(_finish, th=th, tw=tw, activation=activation),
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "stride", "tile_h", "tile_w", "cin_block", "cout_block", "regime",
        "activation", "interpret", "save_preact",
    ),
)
def conv2d_sliding_pallas(
    x: jax.Array,
    w: jax.Array,
    bias: jax.Array | None = None,
    *,
    stride: tuple[int, int] = (1, 1),
    tile_h: int = DEFAULT_TILE_H,
    tile_w: int = DEFAULT_TILE_W,
    cin_block: int | None = None,
    cout_block: int | None = None,
    regime: str | None = None,
    activation: str = "none",
    interpret: bool = False,
    save_preact: bool = False,
) -> jax.Array:
    """VALID 2-D sliding conv. x: (B,H,W,Cin), w: (kh,kw,Cin,Cout).

    ``bias`` (Cout,) + ``activation`` fuse into the epilogue; ``cin_block``/
    ``cout_block`` bound the VMEM working set (None = full channel axis).
    ``save_preact=True`` returns ``(y, z)`` with the pre-activation residual.
    """
    B, H, W, Cin = x.shape
    kh, kw, _, Cout = w.shape
    sh, sw = stride
    oh = (H - kh) // sh + 1
    ow = (W - kw) // sw + 1
    if oh < 1 or ow < 1:
        raise ValueError(
            f"filter ({kh},{kw}) (stride {stride}) exceeds input ({H},{W})"
        )
    if regime is None:
        from repro.core.conv import regime_for

        regime = (
            "custom" if (kh == kw and kh in (3, 5)) else regime_for(kw)
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
        w = _pad_axis(w, 2, n_ci * cb)
    if n_co * ob > Cout:
        w = _pad_axis(w, 3, n_co * ob)
    has_bias = bias is not None
    if has_bias:
        bias2d = _pad_axis(bias.reshape(1, Cout), 1, n_co * ob)

    n_out = 2 if save_preact else 1
    if regime == "compound":
        n_chunks = pl.cdiv(kh, ROW_CHUNK)
        w = _pad_axis(w, 0, n_chunks * ROW_CHUNK)
        n_red = n_ci * n_chunks
        x, halo_h, halo_w = halo_input_2d(
            x, (nh - 1) * th * sh + (n_chunks - 1) * ROW_CHUNK,
            (th - 1) * sh + ROW_CHUNK, (nw - 1) * tw * sw, (tw - 1) * sw + kw,
        )
        kernel = functools.partial(
            _kernel_compound, rows=ROW_CHUNK, kw=kw, th=th, tw=tw, sh=sh,
            sw=sw, n_red=n_red, activation=activation, has_bias=has_bias,
            n_out=n_out,
        )
        # reduction r = (cin block, filter-row chunk), chunk fastest
        in_specs = [
            halo_spec((halo_h, halo_w, cb), n_ci, lambda b, i, j, co, r: (
                b, i * th * sh + (r % n_chunks) * ROW_CHUNK, j * tw * sw,
                r // n_chunks,
            )),
            pl.BlockSpec(
                (ROW_CHUNK, kw, cb, ob),
                lambda b, i, j, co, r: (r % n_chunks, 0, r // n_chunks, co),
            ),
        ]
    else:
        n_red = n_ci
        body = _kernel_custom if regime == "custom" else _kernel_generic
        kernel = functools.partial(
            body, kh=kh, kw=kw, th=th, tw=tw, sh=sh, sw=sw,
            n_red=n_red, activation=activation, has_bias=has_bias,
            n_out=n_out,
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
        ]
    args = [x, w]
    if has_bias:
        in_specs.append(
            pl.BlockSpec((1, ob), lambda b, i, j, co, r: (0, co))
        )
        args.append(bias2d)
    out_spec = pl.BlockSpec(
        (1, th, tw, ob), lambda b, i, j, co, r: (b, i, j, co)
    )
    out_sds = jax.ShapeDtypeStruct((B, nh * th, nw * tw, n_co * ob), x.dtype)
    out = pl.pallas_call(
        kernel,
        name="conv2d_sliding_pallas",
        grid=(B, nh, nw, n_co, n_red),
        in_specs=in_specs,
        out_specs=[out_spec] * n_out,
        out_shape=[out_sds] * n_out,
        # the single-visit fast path accumulates in registers, no scratch
        scratch_shapes=(
            [] if n_red == 1 else [pltpu.VMEM((th * tw, ob), jnp.float32)]
        ),
        interpret=interpret,
    )(*args)
    if save_preact:
        y, z = out
        return y[:, :oh, :ow, :Cout], z[:, :oh, :ow, :Cout]
    return out[0][:, :oh, :ow, :Cout]
