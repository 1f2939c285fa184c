"""Pallas TPU kernel: sliding-window pooling via the two-phase scan.

The companion-paper (arXiv:2305.16513) kernel structure shared by pooling
and 1-D convolution: phase 1 computes an in-VMEM prefix scan along the
window axis; phase 2 emits the strided difference (sum/avg) or combines the
block prefix/suffix scans (max — the van Herk / Gil-Werman decomposition).
Phase 2 does O(n) work per tile independent of window size — the property
the paper exploits for large-window pooling. Phase 1 is a log-depth scan
built from row shifts (``_scan_rows``): log₂(tile) passes for sum/avg,
log₂(w) for max.

Backward kernels (DESIGN.md §6):

  * sum/avg — the gradient is itself a sliding sum: every input row j is
    covered by the windows [j-w+1, j], so ``dx = sum-pool(pad(dy, w-1))``
    and the forward two-phase kernel is REUSED on the padded gradient
    (scaled by 1/w for avg).
  * max — ``dx[j] = Σ_k dy[j-k] · [x[j] == y[j-k]]``: a shift-and-select
    over the w windows covering j, using the saved forward output y as the
    argmax witness (``_max_pool_bwd_kernel``). Zero-padded dy rows gate out
    out-of-range windows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.sliding_conv1d import halo_rows, halo_spec

DEFAULT_TILE = 512


def _halo_pad(x, rows: int, value=0.0):
    """Pad axis 1 of ``x`` with ``value`` up to ``rows`` rows."""
    if x.shape[1] >= rows:
        return x
    return jnp.pad(
        x, ((0, 0), (0, rows - x.shape[1]), (0, 0)), constant_values=value
    )


def _shift_rows(x, d: int):
    """Rows of ``x`` moved ``d`` down (up for d < 0); vacated rows are 0."""
    pad = jnp.zeros((abs(d),) + x.shape[1:], x.dtype)
    if d > 0:
        return jnp.concatenate([pad, x[:-d]], axis=0)
    return jnp.concatenate([x[-d:], pad], axis=0)


def _scan_rows(x, op, *, seg: int | None = None, reverse: bool = False):
    """Inclusive log-depth (Hillis–Steele) scan of ``op`` along axis 0 —
    built from row shifts, which the TPU lowers (its compiler has no
    cumsum/cummax). ``seg`` restarts the scan every ``seg`` rows;
    ``reverse`` scans from the end (a suffix scan)."""
    n = seg or x.shape[0]
    if seg is not None:
        pos = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0) % seg
        if reverse:
            pos = seg - 1 - pos
    d = 1
    while d < n:
        y = op(x, _shift_rows(x, -d if reverse else d))
        x = y if seg is None else jnp.where(pos >= d, y, x)
        d *= 2
    return x


def _sum_pool_kernel(x_ref, o_ref, *, window, tile_l):
    x = x_ref[0].astype(jnp.float32)
    s = _scan_rows(x, jnp.add)  # phase 1: prefix scan in VMEM
    upper = s[window - 1 : window - 1 + tile_l]
    lower = _shift_rows(s, 1)[:tile_l]
    o_ref[0] = (upper - lower).astype(o_ref.dtype)  # phase 2: difference


def _max_pool_shift_kernel(x_ref, o_ref, *, window, tile_l):
    """Shift-and-max loop: O(n·w) comparisons but no block reshuffle — the
    lower-constant form that beats the two-phase scan for small windows
    (the per-shape crossover is measured by ``autotune.autotune_pool1d``
    and consulted by ``ops.pool1d``; hardcoding either form lost: shift
    1.4× slower at w=256, scan 2× slower at w=16)."""
    x = x_ref[0]
    acc = x[:tile_l]
    for k in range(1, window):
        acc = jnp.maximum(acc, x[k : k + tile_l])
    o_ref[0] = acc


def _max_pool_kernel(x_ref, o_ref, *, window, tile_l):
    """Two-phase max: block prefix/suffix cummax (van Herk / Gil-Werman).

    The halo tile is split into window-aligned blocks; phase 1 computes the
    within-block prefix max P and suffix max S (log-depth scans, O(n·log w)
    comparisons), phase 2 emits ``y[j] = max(S[j], P[j+w-1])`` in O(n) (vs
    the O(n·w) shift-and-max loop).
    """
    x = x_ref[0]
    if window == 1:
        o_ref[0] = x[:tile_l]
        return
    # window-aligned blocks start at the tile's first row
    pre = _scan_rows(x, jnp.maximum, seg=window)
    suf = _scan_rows(x, jnp.maximum, seg=window, reverse=True)
    o_ref[0] = jnp.maximum(suf[:tile_l], pre[window - 1 : window - 1 + tile_l])


@functools.partial(
    jax.jit, static_argnames=("window", "op", "tile_l", "method", "interpret")
)
def sliding_pool_pallas(
    x: jax.Array,
    *,
    window: int,
    op: str = "sum",
    tile_l: int = DEFAULT_TILE,
    method: str = "scan",
    interpret: bool = False,
) -> jax.Array:
    """VALID sliding pooling along axis 1. x: (B, L, C) -> (B, L-window+1, C).

    ``method`` selects the max-pool evaluation: ``"scan"`` (two-phase
    van Herk / Gil-Werman block cummax) or ``"shift"`` (shift-and-max loop);
    sum/avg always use the prefix-scan kernel."""
    B, L, C = x.shape
    out_len = L - window + 1
    if out_len < 1:
        raise ValueError(f"window {window} exceeds length {L}")
    tile_l = min(tile_l, out_len)
    n_tiles = pl.cdiv(out_len, tile_l)
    padded_out = n_tiles * tile_l
    halo = halo_rows(tile_l + window - 1)
    pad_val = 0.0 if op in ("sum", "avg") else -jnp.inf
    x = _halo_pad(x, (n_tiles - 1) * tile_l + halo, pad_val)
    if op in ("sum", "avg"):
        body = _sum_pool_kernel
    else:
        body = _max_pool_shift_kernel if method == "shift" else _max_pool_kernel
    kernel = functools.partial(body, window=window, tile_l=tile_l)
    out = pl.pallas_call(
        kernel,
        name="sliding_pool_pallas",
        grid=(B, n_tiles),
        in_specs=[halo_spec((halo, C), 1, lambda b, i: (b, i * tile_l, 0))],
        out_specs=pl.BlockSpec((1, tile_l, C), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, padded_out, C), x.dtype),
        interpret=interpret,
    )(x)
    out = out[:, :out_len]
    if op == "avg":
        out = (out.astype(jnp.float32) / window).astype(x.dtype)
    return out


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def sum_pool_bwd(dy: jax.Array, *, window: int, interpret: bool = False):
    """dx of sum pooling: a sliding sum of dy over the w windows covering
    each input row — the forward two-phase kernel on the padded gradient."""
    dyp = jnp.pad(dy, ((0, 0), (window - 1, window - 1), (0, 0)))
    return sliding_pool_pallas(dyp, window=window, op="sum", interpret=interpret)


def _max_pool_count_kernel(x_ref, y_ref, cnt_ref, *, window, tile_l):
    """cnt[i] = #{m ∈ [0, w) : x[i+m] == y[i]} — ties per window, used to
    split the window's gradient so total mass stays dy (a valid
    subgradient; crediting every tie in full would inflate it ×ties)."""
    x = x_ref[0]  # (tile_l + w - 1, C) input halo
    y = y_ref[0]  # (tile_l, C) forward maxima
    cnt = jnp.zeros(y.shape, jnp.float32)
    for m in range(window):
        cnt += (x[m : m + tile_l] == y).astype(jnp.float32)
    cnt_ref[0] = cnt


def _max_pool_bwd_kernel(x_ref, y_ref, dy_ref, o_ref, *, window, tile_l):
    """dx[j] = Σ_k dy[j-k] · [x[j] == y[j-k]], k ∈ [0, w): shift-and-select
    against the saved forward max y (zero-padded dy gates invalid windows;
    dy arrives pre-divided by the window tie count)."""
    x = x_ref[0]
    y = y_ref[0]   # (tile_l + w - 1, C) halo of the zero-padded forward out
    dy = dy_ref[0]
    acc = jnp.zeros(x.shape, jnp.float32)
    for k in range(window):
        off = window - 1 - k
        ys = y[off : off + tile_l]
        dys = dy[off : off + tile_l].astype(jnp.float32)
        acc += jnp.where(x == ys, dys, 0.0)
    o_ref[0] = acc.astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("window", "tile_l", "interpret")
)
def max_pool_bwd_pallas(
    x: jax.Array,
    y: jax.Array,
    dy: jax.Array,
    *,
    window: int,
    tile_l: int = DEFAULT_TILE,
    interpret: bool = False,
) -> jax.Array:
    """dx of max pooling. x: (B, L, C) forward input, y/dy: (B, out_len, C)
    forward output and upstream gradient. Each window's gradient is split
    evenly across its tied maxima (total mass per window == dy)."""
    B, L, C = x.shape
    out_len = y.shape[1]
    tile_l = min(tile_l, L)
    n_tiles = pl.cdiv(L, tile_l)
    padded = n_tiles * tile_l
    if padded > L:
        x = jnp.pad(x, ((0, 0), (0, padded - L), (0, 0)))

    # pass 1: per-window tie count (≥ 1: the max always occurs), then split
    to = min(tile_l, out_len)
    nt_o = pl.cdiv(out_len, to)
    pad_o = nt_o * to - out_len
    halo_o = halo_rows(to + window - 1)
    xp = _halo_pad(x, (nt_o - 1) * to + halo_o)  # last tile's halo end
    yp = jnp.pad(y, ((0, 0), (0, pad_o), (0, 0))) if pad_o else y
    cnt = pl.pallas_call(
        functools.partial(_max_pool_count_kernel, window=window, tile_l=to),
        name="max_pool_count_pallas",
        grid=(B, nt_o),
        in_specs=[
            halo_spec((halo_o, C), 1, lambda b, i: (b, i * to, 0)),
            pl.BlockSpec((1, to, C), lambda b, i: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, to, C), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, nt_o * to, C), jnp.float32),
        interpret=interpret,
    )(xp, yp)[:, :out_len]
    dy = (dy.astype(jnp.float32) / jnp.maximum(cnt, 1.0)).astype(dy.dtype)

    # pass 2: scatter each window's (split) gradient onto its argmaxes.
    # front pad (w-1) aligns dy[j-k] reads; zero dy rows nullify windows that
    # fall outside [0, out_len) regardless of the y pad value.
    halo = halo_rows(tile_l + window - 1)
    rear = (n_tiles - 1) * tile_l + halo - (window - 1) - out_len
    y = jnp.pad(y, ((0, 0), (window - 1, rear), (0, 0)))
    dy = jnp.pad(dy, ((0, 0), (window - 1, rear), (0, 0)))
    kernel = functools.partial(
        _max_pool_bwd_kernel, window=window, tile_l=tile_l
    )
    out = pl.pallas_call(
        kernel,
        name="max_pool_bwd_pallas",
        grid=(B, n_tiles),
        in_specs=[
            pl.BlockSpec((1, tile_l, C), lambda b, i: (b, i, 0)),
            halo_spec((halo, C), 1, lambda b, i: (b, i * tile_l, 0)),
            halo_spec((halo, C), 1, lambda b, i: (b, i * tile_l, 0)),
        ],
        out_specs=pl.BlockSpec((1, tile_l, C), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, padded, C), jnp.float32),
        interpret=interpret,
    )(x, y, dy)
    return out[:, :L].astype(x.dtype)
