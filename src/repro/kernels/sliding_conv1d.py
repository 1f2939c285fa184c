"""Pallas TPU kernels: 1-D Sliding Window convolution (paper §2, 1-D case).

Three regimes, mirroring the paper's CPU kernels (see DESIGN.md §2 for the
CPU→TPU mapping):

  * ``custom``   (K ∈ {3, 5})   — tap-stacked VMEM gather + ONE MXU matmul of
    shape (TL, K·Cin) @ (K·Cin, Cout). This is the "optimal number of
    operations" variant: the K× stacking happens in VMEM *registers*, never
    in HBM, and the MXU sees a single large contraction instead of K small
    ones (the paper's Conclusion-§3 "small matrix multiplication"
    reformulation).
  * ``generic``  (K ≤ 17)       — unrolled shift-and-accumulate: each tap is
    a shifted in-VMEM read followed by a (TL, Cin) @ (Cin, Cout) MXU matmul.
    The shift is an address offset into the halo tile — the TPU analogue of
    the CPU vector slide.
  * ``compound`` (K > 17)       — the tap range no longer fits one halo tile
    comfortably; taps are processed in chunks of ``TAP_CHUNK`` via the
    reduction grid dimension that *revisits* the output block, accumulating
    partial sums — the analogue of the paper's compound-vector kernel
    operating on multiple hardware vectors.

Channel blocking (DESIGN.md §3): when ``cin_block``/``cout_block`` are set,
the grid gains Cout-block and Cin-block dimensions so a kernel instance only
holds a ``(K, cin_block, cout_block)`` weight tile and a ``(halo, cin_block)``
input tile in VMEM — large-channel layers no longer load full ``(K, Cin,
Cout)`` weights per tile. Partial Cin-block products are accumulated in an
f32 VMEM scratch across output-block revisits (the reduction dimension is
innermost in the grid, so each output block's reduction completes before the
block is flushed).

Fused epilogue: ``bias`` (Cout,) and ``activation`` (none/relu/gelu/
gelu_erf/silu) are applied inside the kernel on the final reduction visit —
conv→bias→act is one kernel launch, not three HBM round-trips.

Training residuals: with ``save_preact=True`` the kernels emit a SECOND
output ``z = acc + bias`` (the post-bias, pre-activation value, cast to the
output dtype) on the same final reduction visit. The custom-VJP layer in
``repro.kernels.ops`` saves ``z`` so the backward pass can form
``dz = dy · act'(z)`` without recomputing the convolution (DESIGN.md §6).

All kernels: NLC layout, stride ≥ 1 (loaded-tile register slicing), f32
accumulation, bf16/f32 in/out. HBM traffic is O(input + output) — the im2col
column matrix is never materialized (compare ``repro.kernels.im2col_gemm``).
Halo (overlapping) input windows are ``pl.Element`` blocks (``halo_spec``):
their index maps return element offsets, so consecutive tiles may share
K-1 rows. The halo is rounded up to whole sublanes (``halo_rows``) so the
block satisfies the TPU's (8, 128) tiling; the extra rows are zero padding
the taps never read. A stride above 1 becomes channels (``phase_split``):
the multi-channel kernels always slide by one row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_TILE_L = 256
TAP_CHUNK = 16  # taps per compound chunk ~= one "hardware vector" of taps


# erf as x·P(x²)/Q(x²) on [-4, 4], beyond which float32 erf is ±1: the
# rational approximation XLA and Eigen evaluate float32 erf with. Mosaic
# lowers no erf, and this takes multiplies, adds and one divide. Largest
# error against float64 erf over [-10, 10]: 4.5e-7 (CPU reading).
_ERF_P = (-2.72614225801306e-10, 2.77068142495902e-08, -2.10102402082508e-06,
          -5.69250639462346e-05, -7.34990630326855e-04,
          -2.95459980854025e-03, -1.60960333262415e-02)
_ERF_Q = (-1.45660718464996e-05, -2.13374055278905e-04,
          -1.68282697438203e-03, -7.37332916720468e-03,
          -1.42647390514189e-02)


def erf(x: jax.Array) -> jax.Array:
    """float32 erf from elementwise arithmetic alone (kernel epilogues)."""
    x = jnp.clip(x, -4.0, 4.0)
    x2 = x * x
    p, q = _ERF_P[0], _ERF_Q[0]
    for c in _ERF_P[1:]:
        p = p * x2 + c
    for c in _ERF_Q[1:]:
        q = q * x2 + c
    return x * p / q


def apply_activation(x: jax.Array, activation: str) -> jax.Array:
    """Epilogue activation on the f32 accumulator (static dispatch):
    ``gelu`` is the tanh approximation, ``gelu_erf`` the exact GELU."""
    if activation in (None, "none"):
        return x
    if activation == "relu":
        return jax.nn.relu(x)
    if activation == "gelu":
        return jax.nn.gelu(x, approximate=True)
    if activation == "gelu_erf":
        return 0.5 * x * (1.0 + erf(x * 0.7071067811865476))
    if activation == "silu":
        return jax.nn.silu(x)
    raise ValueError(f"unknown activation {activation!r}")


def _epilogue(acc, bias_ref, o_ref, z_ref=None, *, activation: str):
    """bias-add + activation on the f32 accumulator, cast, store.

    ``z_ref``, when present, receives the post-bias pre-activation value —
    the residual the backward pass needs for ``dz = dy · act'(z)``."""
    if bias_ref is not None:
        acc = acc + bias_ref[0].astype(jnp.float32)
    if z_ref is not None:
        z_ref[0] = acc.astype(z_ref.dtype)
    o_ref[0] = apply_activation(acc, activation).astype(o_ref.dtype)


def _slide(x_ref, k: int, tile: int, stride: int = 1):
    """Tap-k shifted rows of the halo tile (the paper's vector slide), read
    straight from the VMEM ref. A stride above 1 is a strided load, which
    the TPU supports for 32-bit data only: the multi-channel kernels never
    need one (``phase_split`` turns their stride into channels)."""
    return x_ref[0, pl.ds(k, tile, stride=stride), :]


def phase_split(x: jax.Array, stride: int) -> jax.Array:
    """(B, L, C) → (B, ⌈L/s⌉, s·C): row ``j·s + p`` moves to row ``j``,
    channels ``[p·C, (p+1)·C)``. A free reshape once L is padded to a
    multiple of ``s``. A stride-s conv over ``x`` is the stride-1 conv over
    ``phase_split(x, s)`` with the taps of ``phase_taps(w, s)``, so the
    kernels only ever slide by one row."""
    B, L, C = x.shape
    x = _pad_axis(x, 1, pl.cdiv(L, stride) * stride)
    return x.reshape(B, -1, stride * C)


def phase_taps(w: jax.Array, stride: int) -> jax.Array:
    """(K, Cin, Cout) → (⌈K/s⌉, s·Cin, Cout): tap ``t·s + p`` moves to tap
    ``t``, input channels ``[p·Cin, (p+1)·Cin)``; the padded taps are
    zero. The inverse of ``reshape(-1, Cin, Cout)[:K]``."""
    K, Cin, Cout = w.shape
    w = _pad_axis(w, 0, pl.cdiv(K, stride) * stride)
    return w.reshape(-1, stride * Cin, Cout)


# ---------------------------------------------------------------------------
# kernel bodies
# ---------------------------------------------------------------------------
# Common structure: grid (B, L-tiles, Cout-blocks, reduction) with the
# reduction dimension (Cin blocks × tap chunks) innermost. acc_ref is an f32
# VMEM scratch persisting across the reduction sweep of one output block.

def _unpack(rest, has_bias: bool, n_out: int, has_scratch: bool):
    """Split the trailing kernel refs into (bias_ref, output refs, scratch)."""
    i = 1 if has_bias else 0
    bias_ref = rest[0] if has_bias else None
    outs = rest[i : i + n_out]
    acc_ref = rest[i + n_out] if has_scratch else None
    return bias_ref, outs, acc_ref


def _reduce_store(acc, rest, *, has_bias, n_red, red_axis, finish, n_out=1):
    """Fold this visit's partial product into the output block.

    n_red == 1 (unblocked channels, single tap chunk — the common hot path):
    no scratch is allocated and the register accumulator goes straight
    through the epilogue. Otherwise the f32 scratch carries partials across
    output-block revisits: first visit stores, later visits add, last visit
    runs ``finish(acc, bias_ref, *outs)``. ``n_out`` is 2 when the kernel
    also emits the pre-activation residual (save_preact).
    """
    bias_ref, outs, acc_ref = _unpack(rest, has_bias, n_out, n_red > 1)
    if n_red == 1:
        finish(acc, bias_ref, *outs)
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
        finish(acc_ref[...], bias_ref, *outs)


def _kernel_generic(
    x_ref, w_ref, *rest, taps, tile_l, n_red, activation, has_bias, n_out,
):
    """Unrolled shift-and-MXU-matmul over taps (generic / vector-slide).
    x_ref holds the (TL + K - 1, cin_block) halo tile, VMEM-resident."""
    cout = w_ref.shape[2]
    acc = jnp.zeros((tile_l, cout), jnp.float32)
    for k in range(taps):
        acc += jnp.dot(
            _slide(x_ref, k, tile_l), w_ref[k],
            preferred_element_type=jnp.float32,
        )
    _reduce_store(
        acc, rest, has_bias=has_bias, n_red=n_red, red_axis=3, n_out=n_out,
        finish=functools.partial(_epilogue, activation=activation),
    )


def _kernel_custom(
    x_ref, w_ref, *rest, taps, tile_l, n_red, activation, has_bias, n_out,
):
    """Tap-stacked single-matmul kernel for K in {3, 5} (custom regime)."""
    cols = [_slide(x_ref, k, tile_l) for k in range(taps)]
    stacked = jnp.concatenate(cols, axis=-1)  # (TL, K*cin_block) — VMEM only
    wf = w_ref[...].reshape(taps * w_ref.shape[1], w_ref.shape[2])
    acc = jnp.dot(stacked, wf, preferred_element_type=jnp.float32)
    _reduce_store(
        acc, rest, has_bias=has_bias, n_red=n_red, red_axis=3, n_out=n_out,
        finish=functools.partial(_epilogue, activation=activation),
    )


def _kernel_compound(
    x_ref, w_ref, *rest, chunk, tile_l, n_red, activation, has_bias, n_out,
):
    """Tap-chunked accumulation (compound regime): the reduction dimension
    sweeps Cin blocks × tap chunks; chunk c covers taps [c·chunk, (c+1)·chunk).
    """
    cout = w_ref.shape[2]
    acc = jnp.zeros((tile_l, cout), jnp.float32)
    for k in range(chunk):  # taps within the chunk: unrolled slides
        acc += jnp.dot(
            _slide(x_ref, k, tile_l), w_ref[k],
            preferred_element_type=jnp.float32,
        )
    _reduce_store(
        acc, rest, has_bias=has_bias, n_red=n_red, red_axis=3, n_out=n_out,
        finish=functools.partial(_epilogue, activation=activation),
    )


def _kernel_depthwise(
    x_ref, w_ref, *rest, taps, tile_l, stride, activation, has_bias, n_out
):
    """Depthwise (VPU) kernel: per-tap shifted elementwise FMA — the most
    literal TPU transcription of the paper's vector-slide inner loop."""
    bias_ref, outs, _ = _unpack(rest, has_bias, n_out, False)
    o_ref = outs[0]
    acc = jnp.zeros(o_ref.shape[1:], jnp.float32)
    for k in range(taps):
        acc += _slide(x_ref, k, tile_l, stride).astype(jnp.float32) * w_ref[
            k
        ].astype(jnp.float32)
    _epilogue(acc, bias_ref, *outs, activation=activation)


# ---------------------------------------------------------------------------
# pallas_call wrappers
# ---------------------------------------------------------------------------

SUBLANES = 8  # the TPU's second-to-last tiling: block rows come in eights


def halo_rows(n: int) -> int:
    """A halo of ``n`` rows, rounded up to whole sublanes."""
    return -(-n // SUBLANES) * SUBLANES


def halo_input(x: jax.Array, axis: int, last: int, span: int):
    """Round a halo of ``span`` rows up to whole sublanes and zero-pad
    ``x`` along ``axis`` so the read at element offset ``last`` (the last
    tile's) stays in bounds. Returns ``(x, halo)``."""
    halo = halo_rows(span)
    return _pad_axis(x, axis, last + halo), halo


def halo_spec(block: tuple[int, ...], n_c: int, index_map) -> pl.BlockSpec:
    """BlockSpec of a ``(1, *block)`` halo tile of a channels-last array
    whose last axis holds ``n_c`` blocks of ``block[-1]`` channels.
    ``index_map`` returns (batch, element offset per spatial axis…,
    channel block).

    The TPU takes element windows on every axis or on none, so the channel
    block becomes an element offset too. With one channel block it is the
    constant 0: the compiler must prove a lane offset a multiple of 128,
    and ``c · cb`` over a grid index ``c`` is not provably one when cb is
    not."""
    cb = block[-1]

    def element_map(*ids):
        *lead, c = index_map(*ids)
        return (*lead, c * cb if n_c > 1 else 0)

    return pl.BlockSpec(tuple(pl.Element(n) for n in (1, *block)), element_map)


def _resolve_block(total: int, block: int | None) -> int:
    if block is None or block <= 0:
        return total
    return min(block, total)


def _pad_axis(a: jax.Array, axis: int, to: int) -> jax.Array:
    if a.shape[axis] >= to:
        return a
    pads = [(0, 0)] * a.ndim
    pads[axis] = (0, to - a.shape[axis])
    return jnp.pad(a, pads)


@functools.partial(
    jax.jit,
    static_argnames=(
        "stride", "tile_l", "cin_block", "cout_block", "regime",
        "activation", "interpret", "save_preact",
    ),
)
def conv1d_sliding_pallas(
    x: jax.Array,
    w: jax.Array,
    bias: jax.Array | None = None,
    *,
    stride: int = 1,
    tile_l: int = DEFAULT_TILE_L,
    cin_block: int | None = None,
    cout_block: int | None = None,
    regime: str | None = None,
    activation: str = "none",
    interpret: bool = False,
    save_preact: bool = False,
) -> jax.Array:
    """VALID 1-D sliding conv. x: (B, L, Cin), w: (K, Cin, Cout).

    Padding is handled by the caller (``repro.kernels.ops``) so the kernel
    grid stays rectangular. Output length: (L - K) // stride + 1.
    ``bias`` (Cout,) and ``activation`` are fused into the kernel epilogue.
    ``cin_block``/``cout_block`` bound the per-instance VMEM working set;
    None means unblocked (full channel dimension).
    ``save_preact=True`` returns ``(y, z)`` where ``z`` is the post-bias
    pre-activation residual for the backward pass.
    """
    B, L, Cin = x.shape
    K, _, Cout = w.shape
    out_len = (L - K) // stride + 1
    if out_len < 1:
        raise ValueError(
            f"filter K={K} (stride {stride}) exceeds input length {L}"
        )
    if regime is None:
        from repro.core.conv import regime_for

        regime = regime_for(K)
    if stride > 1:
        out = conv1d_sliding_pallas(
            phase_split(x, stride), phase_taps(w, stride), bias,
            tile_l=tile_l, cin_block=cin_block, cout_block=cout_block,
            regime=regime, activation=activation, interpret=interpret,
            save_preact=save_preact,
        )
        if save_preact:
            return tuple(o[:, :out_len] for o in out)
        return out[:, :out_len]
    tile_l = min(tile_l, out_len)
    n_tiles = pl.cdiv(out_len, tile_l)
    padded_out = n_tiles * tile_l

    # -- channel blocking: pad Cin/Cout to block multiples (zero taps/outputs
    #    contribute nothing / are trimmed), one grid dim per blocked axis.
    cb = _resolve_block(Cin, cin_block)
    ob = _resolve_block(Cout, cout_block)
    n_ci = pl.cdiv(Cin, cb)
    n_co = pl.cdiv(Cout, ob)
    if n_ci * cb > Cin:
        x = _pad_axis(x, 2, n_ci * cb)
        w = _pad_axis(w, 1, n_ci * cb)
    if n_co * ob > Cout:
        w = _pad_axis(w, 2, n_co * ob)
    has_bias = bias is not None
    if has_bias:
        bias2d = _pad_axis(bias.reshape(1, Cout), 1, n_co * ob)

    out_dtype = x.dtype
    n_out = 2 if save_preact else 1

    if regime == "compound":
        n_chunks = pl.cdiv(K, TAP_CHUNK)
        w = _pad_axis(w, 0, n_chunks * TAP_CHUNK)
        n_red = n_ci * n_chunks
        x, halo = halo_input(
            x, 1, (n_tiles - 1) * tile_l + (n_chunks - 1) * TAP_CHUNK,
            tile_l - 1 + TAP_CHUNK,
        )
        kernel = functools.partial(
            _kernel_compound, chunk=TAP_CHUNK, tile_l=tile_l,
            n_red=n_red, activation=activation, has_bias=has_bias,
            n_out=n_out,
        )
        # reduction index r decomposes as (cin block, tap chunk): the tap
        # chunk is fastest so a cin block's taps complete consecutively.
        in_specs = [
            halo_spec((halo, cb), n_ci, lambda b, i, co, r: (
                b, i * tile_l + (r % n_chunks) * TAP_CHUNK, r // n_chunks,
            )),
            pl.BlockSpec(
                (TAP_CHUNK, cb, ob),
                lambda b, i, co, r: (r % n_chunks, r // n_chunks, co),
            ),
        ]
    else:
        n_red = n_ci
        body = _kernel_custom if regime == "custom" else _kernel_generic
        kernel = functools.partial(
            body, taps=K, tile_l=tile_l,
            n_red=n_red, activation=activation, has_bias=has_bias,
            n_out=n_out,
        )
        x, halo = halo_input(x, 1, (n_tiles - 1) * tile_l, tile_l - 1 + K)
        in_specs = [
            halo_spec((halo, cb), n_ci, lambda b, i, co, r: (b, i * tile_l, r)),
            pl.BlockSpec((K, cb, ob), lambda b, i, co, r: (0, r, co)),
        ]
    args = [x, w]
    if has_bias:
        in_specs.append(
            pl.BlockSpec((1, ob), lambda b, i, co, r: (0, co))
        )
        args.append(bias2d)
    out_spec = pl.BlockSpec((1, tile_l, ob), lambda b, i, co, r: (b, i, co))
    out_sds = jax.ShapeDtypeStruct((B, padded_out, n_co * ob), out_dtype)
    out = pl.pallas_call(
        kernel,
        name="conv1d_sliding_pallas",
        grid=(B, n_tiles, n_co, n_red),
        in_specs=in_specs,
        out_specs=[out_spec] * n_out,
        out_shape=[out_sds] * n_out,
        # the single-visit fast path accumulates in registers, no scratch
        scratch_shapes=(
            [] if n_red == 1 else [pltpu.VMEM((tile_l, ob), jnp.float32)]
        ),
        interpret=interpret,
    )(*args)
    if save_preact:
        y, z = out
        return y[:, :out_len, :Cout], z[:, :out_len, :Cout]
    return out[0][:, :out_len, :Cout]


@functools.partial(
    jax.jit,
    static_argnames=(
        "stride", "tile_l", "c_block", "activation", "interpret",
        "save_preact",
    ),
)
def conv1d_depthwise_pallas(
    x: jax.Array,
    w: jax.Array,
    bias: jax.Array | None = None,
    *,
    stride: int = 1,
    tile_l: int = DEFAULT_TILE_L,
    c_block: int | None = None,
    activation: str = "none",
    interpret: bool = False,
    save_preact: bool = False,
) -> jax.Array:
    """VALID depthwise sliding conv. x: (B, L, C), w: (K, C).

    ``bias`` (C,) + ``activation`` fuse into the epilogue (the Mamba conv
    path is conv→bias→silu in one launch). ``c_block`` blocks the channel
    axis (channels are independent in depthwise — no reduction revisits).
    ``save_preact=True`` additionally returns the pre-activation residual.
    """
    B, L, C = x.shape
    K, _ = w.shape
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
        w = _pad_axis(w, 1, n_c * cb)
    has_bias = bias is not None
    n_out = 2 if save_preact else 1
    kernel = functools.partial(
        _kernel_depthwise, taps=K, tile_l=tile_l, stride=stride,
        activation=activation, has_bias=has_bias, n_out=n_out,
    )
    in_specs = [
        halo_spec((halo, cb), n_c, lambda b, i, c: (b, i * step, c)),
        pl.BlockSpec((K, cb), lambda b, i, c: (0, c)),
    ]
    args = [x, w]
    if has_bias:
        in_specs.append(pl.BlockSpec((1, cb), lambda b, i, c: (0, c)))
        args.append(_pad_axis(bias.reshape(1, C), 1, n_c * cb))
    out_spec = pl.BlockSpec((1, tile_l, cb), lambda b, i, c: (b, i, c))
    out_sds = jax.ShapeDtypeStruct((B, padded_out, n_c * cb), x.dtype)
    out = pl.pallas_call(
        kernel,
        name="conv1d_depthwise_pallas",
        grid=(B, n_tiles, n_c),
        in_specs=in_specs,
        out_specs=[out_spec] * n_out,
        out_shape=[out_sds] * n_out,
        interpret=interpret,
    )(*args)
    if save_preact:
        y, z = out
        return y[:, :out_len, :C], z[:, :out_len, :C]
    return out[0][:, :out_len, :C]
