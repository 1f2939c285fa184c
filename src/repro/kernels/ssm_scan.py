"""Pallas TPU kernel: selective-SSM chunk scan with VMEM-resident state.

The paper's central insight — stream over the unmodified input, keep the
moving window/state on-chip — applied to the Mamba recurrence. The XLA
formulation materializes the (B, L, d_inner, N) hidden-state tensor in HBM
(§Perf jamba cell: ~11 TB of traffic per layer); this kernel keeps ``h``
in a VMEM scratch across sequential grid steps, so HBM traffic is just the
interface: read abar/bx/C once, write y once — an N·(= 16×) reduction on
the state stream.

    h_t = abar_t ⊙ h_{t-1} + bx_t          (B, D, N) state
    y_t = Σ_n h_t[...,n] · C_t[n]          (B, D) output

Grid: (B, D_tiles, L_chunks) with L innermost — TPU executes the grid
sequentially, so the scratch carries the state chunk to chunk. Forward
only (serving/prefill); the training path keeps the XLA chunked scan
(backward kernel = reverse-sweep with per-chunk recompute — documented
follow-up). Validated against the pure-jnp oracle in interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.im2col_gemm import pltpu_vmem

DEFAULT_TILE_D = 256
DEFAULT_CHUNK_L = 128


def _kernel(abar_ref, bx_ref, c_ref, h0_ref, y_ref, hlast_ref, h_scr,
            *, chunk_l: int, n_chunks: int):
    lc = pl.program_id(2)

    @pl.when(lc == 0)
    def _init():
        h_scr[...] = h0_ref[0].astype(jnp.float32)

    def step(t, h):
        a_t = abar_ref[0, t].astype(jnp.float32)   # (d_tile, N)
        b_t = bx_ref[0, t].astype(jnp.float32)
        h = a_t * h + b_t
        c_t = c_ref[0, t].astype(jnp.float32)      # (N,)
        y_ref[0, t] = jnp.sum(h * c_t[None, :], axis=-1).astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk_l, step, h_scr[...])
    h_scr[...] = h

    @pl.when(lc == n_chunks - 1)
    def _emit():
        hlast_ref[0] = h.astype(hlast_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("tile_d", "chunk_l", "interpret")
)
def ssm_scan_pallas(
    abar: jax.Array,
    bx: jax.Array,
    c: jax.Array,
    h0: jax.Array,
    *,
    tile_d: int = DEFAULT_TILE_D,
    chunk_l: int = DEFAULT_CHUNK_L,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """abar/bx: (B, L, D, N); c: (B, L, N); h0: (B, D, N) f32.
    Returns (y (B, L, D), h_last (B, D, N))."""
    B, L, D, N = abar.shape
    tile_d = min(tile_d, D)
    chunk_l = min(chunk_l, L)
    nd = pl.cdiv(D, tile_d)
    nl = pl.cdiv(L, chunk_l)
    if nd * tile_d != D or nl * chunk_l != L:
        pad_d, pad_l = nd * tile_d - D, nl * chunk_l - L
        # identity padding: abar=1, bx=0 keep the carried state unchanged
        # through padded timesteps (h_last must reflect the true L)
        abar = jnp.pad(abar, ((0, 0), (0, pad_l), (0, pad_d), (0, 0)),
                       constant_values=1.0)
        bx = jnp.pad(bx, ((0, 0), (0, pad_l), (0, pad_d), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad_l), (0, 0)))
        h0 = jnp.pad(h0, ((0, 0), (0, pad_d), (0, 0)))
    kernel = functools.partial(_kernel, chunk_l=chunk_l, n_chunks=nl)
    y, h_last = pl.pallas_call(
        kernel,
        name="ssm_scan_pallas",
        grid=(B, nd, nl),  # L innermost: scratch carries state sequentially
        in_specs=[
            pl.BlockSpec((1, chunk_l, tile_d, N), lambda b, d, l: (b, l, d, 0)),
            pl.BlockSpec((1, chunk_l, tile_d, N), lambda b, d, l: (b, l, d, 0)),
            pl.BlockSpec((1, chunk_l, N), lambda b, d, l: (b, l, 0)),
            pl.BlockSpec((1, tile_d, N), lambda b, d, l: (b, d, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk_l, tile_d), lambda b, d, l: (b, l, d)),
            pl.BlockSpec((1, tile_d, N), lambda b, d, l: (b, d, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, nl * chunk_l, nd * tile_d), abar.dtype),
            jax.ShapeDtypeStruct((B, nd * tile_d, N), jnp.float32),
        ],
        scratch_shapes=[pltpu_vmem((tile_d, N), jnp.float32)],
        interpret=interpret,
    )(abar, bx, c, h0)
    return y[:, :L, :D], h_last[:, :D]


def ssm_scan_ref(abar, bx, c, h0):
    """Pure-jnp oracle (the XLA chunked-scan semantics)."""

    def step(h, inp):
        a_t, b_t, c_t = inp
        h = a_t * h + b_t
        y = jnp.sum(h * c_t[:, None, :], axis=-1)
        return h, y

    xs = (
        jnp.moveaxis(abar.astype(jnp.float32), 1, 0),
        jnp.moveaxis(bx.astype(jnp.float32), 1, 0),
        jnp.moveaxis(c.astype(jnp.float32), 1, 0),
    )
    h_last, ys = jax.lax.scan(step, h0.astype(jnp.float32), xs)
    return jnp.moveaxis(ys, 0, 1).astype(abar.dtype), h_last
