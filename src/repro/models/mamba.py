"""Mamba (selective SSM) block — the Jamba hybrid's workhorse layer.

Paper-technique site: the causal depthwise conv1d (k = 4) inside every Mamba
block is a sliding-window convolution. It routes through
``cfg.conv_backend``:

  * ``sliding``        — ``repro.core.conv1d_depthwise_sliding`` (the paper's
                         shift-and-FMA algorithm, XLA-visible — used in the
                         dry-run so cost_analysis sees the real FLOPs),
  * ``sliding_pallas`` — the Pallas VPU kernel
                         (``repro.kernels.ops.conv1d_depthwise``; TPU runtime
                         path, validated in interpret mode),
  * ``xla``            — the XLA grouped-conv baseline.

Selective scan: chunked — outer ``lax.scan`` carries the (B, d_inner, N)
state across chunks (checkpointed boundaries), inner chunk evaluated with a
log-depth associative scan. Peak activation memory is O(chunk) states, not
O(L).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import ParamDef, Runtime
from repro.models.layers import conv_bias_act

Array = jax.Array

SSM_CHUNK = 256


def mamba_defs(cfg: ModelConfig) -> dict[str, ParamDef]:
    d, di = cfg.d_model, cfg.mamba_d_inner
    N, K, R = cfg.mamba_d_state, cfg.mamba_conv_k, cfg.resolved_dt_rank
    return {
        "in_proj": ParamDef((d, 2 * di), ("embed", "conv_inner"), init="fan_in"),
        "conv_w": ParamDef((K, di), (None, "conv_inner"), init="fan_in"),
        "conv_b": ParamDef((di,), ("conv_inner",), init="zeros"),
        "x_proj": ParamDef((di, R + 2 * N), ("conv_inner", None), init="fan_in"),
        "dt_proj": ParamDef((R, di), (None, "conv_inner"), init="fan_in"),
        "dt_bias": ParamDef((di,), ("conv_inner",), init="small", dtype="float32"),
        "A_log": ParamDef((di, N), ("conv_inner", None), init="small",
                          dtype="float32", scale=0.5),
        "D": ParamDef((di,), ("conv_inner",), init="ones", dtype="float32"),
        "out_proj": ParamDef((di, d), ("conv_inner", "embed"), init="fan_in"),
    }


def _resolve_conv_w(p, dt) -> Array:
    """The depthwise conv weight, dequantized if ``repro.quant.apply``
    swapped in a weight-only int8 leaf (the K×C weight dequantizes in
    registers — a dedicated int8 depthwise kernel is a ROADMAP item)."""
    from repro.quant.qconv import QuantizedWeight

    w = p["conv_w"]
    if isinstance(w, QuantizedWeight):
        return w.dequant(dt)
    return w.astype(dt)


def _conv_act(x: Array, w: Any, b: Array, cfg: ModelConfig) -> Array:
    """Causal depthwise conv→bias→silu through ``layers.conv_bias_act``.

    On the Pallas path the bias and silu run in the kernel's fused epilogue
    (one launch); the pure-JAX/XLA paths apply them unfused.

    With ``cfg.conv_precision == "w8a8"`` and an int8 ``QuantizedWeight``
    leaf (from ``quant.apply``), the conv runs int8 *activations* through
    the dedicated depthwise kernel (Pallas VPU int8×int8→int32, or the
    compiled ``qconv`` fast path on non-Pallas backends); any other
    ``QuantizedWeight`` dequantizes in registers. This is the PREFILL
    path; the per-token decode window conv (``mamba_apply`` with
    ``state``) is an O(K·C) elementwise product with nothing to win from
    int8 and stays float. The activation scale is the leaf's calibrated
    ``x_scale`` when present, dynamic absmax otherwise (mamba sites
    execute under the period scan, where calibration can't observe)."""
    from repro.quant.qconv import QuantizedWeight

    w8a8 = isinstance(w, QuantizedWeight) and cfg.conv_precision == "w8a8"
    return conv_bias_act(
        "conv1d_depthwise", x, w, b, mode="w8a8" if w8a8 else None,
        activation="silu", padding="CAUSAL", backend=cfg.conv_backend,
    )


SUBCHUNK = 32


def _assoc_scan(abar, bx, h0):
    def combine(l, r):
        al, bl = l
        ar, br = r
        return al * ar, bl * ar + br

    a_cum, b_cum = jax.lax.associative_scan(combine, (abar, bx), axis=1)
    h_all = a_cum * h0[:, None] + b_cum
    return h_all, h_all[:, -1]


def _chunk_scan(abar: Array, bx: Array, h0: Array) -> tuple[Array, Array]:
    """h_t = abar_t * h_{t-1} + bx_t within a chunk, two-level evaluation.

    abar/bx: (B, c, D, N); h0: (B, D, N). Returns (h_all, h_last).

    The inner associative scan materializes ~2x its input across tree
    levels; running it per SUBCHUNK inside a sequential lax.scan bounds the
    materialized working set to (B, SUBCHUNK, D, N) while keeping log-depth
    parallelism within sub-chunks (§Perf jamba iteration)."""
    # NOTE (§Perf jamba iter 2, REFUTED): a two-level scan (sequential over
    # sub-chunks) was tried to bound the associative-scan tree materialization
    # — it DOUBLED the traffic (520s vs 251s memory term): the sub-chunk scan
    # forces its xs stacks and per-iteration h_all ys to materialize, which
    # the single-level tree had fused. Single-level kept.
    return _assoc_scan(abar, bx, h0)


def mamba_apply(
    p, x: Array, cfg: ModelConfig, rt: Runtime, state: dict | None = None,
    return_state: bool = False,
):
    """x: (B, L, d_model). state (decode): {"conv": (B, K-1, di),
    "ssm": (B, di, N)}. Returns (y, new_state or None). return_state=True
    (prefill) emits the final {"conv", "ssm"} state from a fresh start."""
    B, Lt, d = x.shape
    di, N, K = cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_conv_k
    dt_r = cfg.resolved_dt_rank
    dt = x.dtype

    # Mamba's natural layout: sequence replicated (the conv + scan need full
    # L), d_inner sharded over `model`. Entering here from the SP (seq-
    # sharded) residual stream, the all-gather happens on x once — keeping
    # the in/out_proj weight-grad partials (d, 2·di) properly e-sharded
    # instead of full-size f32 per device.
    x = rt.constrain(x, "batch", None, None)
    xz = jnp.einsum("bld,de->ble", x, p["in_proj"].astype(dt))
    xz = rt.constrain(xz, "batch", None, "conv_inner")
    xin, z = jnp.split(xz, 2, axis=-1)

    if state is None:
        xc = _conv_act(xin, p["conv_w"], p["conv_b"], cfg)
        new_conv = None
    else:
        hist = jnp.concatenate([state["conv"].astype(dt), xin], axis=1)
        w = _resolve_conv_w(p, dt)
        xc = (hist * w[None]).sum(axis=1, keepdims=True) + p["conv_b"].astype(dt)
        new_conv = hist[:, 1:]
        xc = jax.nn.silu(xc)

    A = -jnp.exp(p["A_log"])  # (di, N)

    def _ssm_params(xc_blk):
        """Per-chunk SSM parameters — recomputed in backward (remat)."""
        xdbc = jnp.einsum("blc,ce->ble", xc_blk, p["x_proj"].astype(dt))
        dtr, Bp, Cp = jnp.split(xdbc, [dt_r, dt_r + N], axis=-1)
        delta = jax.nn.softplus(
            jnp.einsum("blr,rc->blc", dtr.astype(jnp.float32),
                       p["dt_proj"].astype(jnp.float32))
            + p["dt_bias"]
        )  # (B, c, di) f32
        abar = jnp.exp(delta[..., None] * A[None, None])
        bx = (delta * xc_blk.astype(jnp.float32))[..., None] * Bp.astype(
            jnp.float32
        )[:, :, None, :]
        return abar, bx, Cp

    if state is None:
        # Stream chunk-by-chunk: the (B, c, di, N) state tensor exists for
        # ONE chunk at a time; each chunk emits its (B, c, di) output
        # immediately. Chunk steps are checkpointed, so backward recomputes
        # per chunk from the carried boundary state (O(c) peak memory, not
        # O(L) — on jamba-398b this is 4.3 GB/layer saved).
        c = min(SSM_CHUNK, Lt)
        n = Lt // c
        if n * c < Lt:  # ragged tail: pad, outputs trimmed below
            pad = (n + 1) * c - Lt
            xc_p = jnp.pad(xc, ((0, 0), (0, pad), (0, 0)))
            n += 1
        else:
            xc_p = xc
        xs = jnp.moveaxis(xc_p.reshape(B, n, c, di), 1, 0)

        @jax.checkpoint
        def step(h, xc_blk):
            abar, bx, Cp = _ssm_params(xc_blk)
            h_all, h_last = _chunk_scan(abar, bx, h)
            y_blk = jnp.einsum("blcn,bln->blc", h_all.astype(dt), Cp)
            return h_last, y_blk

        h0 = jnp.zeros((B, di, N), jnp.float32)
        h_last, y_chunks = jax.lax.scan(step, h0, xs)
        y = jnp.moveaxis(y_chunks, 0, 1).reshape(B, n * c, di)[:, :Lt]
        new_ssm = None
    else:
        abar, bx, Cp = _ssm_params(xc)
        h = abar[:, 0] * state["ssm"] + bx[:, 0]  # single decode step
        new_ssm = h
        y = jnp.einsum("blcn,bln->blc", h[:, None].astype(dt), Cp)
    y = y + xc * p["D"].astype(dt)
    y = y * jax.nn.silu(z)
    out = jnp.einsum("blc,cd->bld", y, p["out_proj"].astype(dt))
    if state is not None:
        return out, {"conv": new_conv.astype(state["conv"].dtype), "ssm": new_ssm}
    if return_state:
        return out, {"conv": xin[:, -(K - 1):], "ssm": h_last}
    return out, None


def mamba_state_defs(cfg: ModelConfig, n_layers: int, batch: int):
    di, N, K = cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_conv_k
    return {
        "conv": ParamDef(
            (n_layers, batch, K - 1, di),
            ("layers", "batch", None, "conv_inner"), init="zeros",
        ),
        "ssm": ParamDef(
            (n_layers, batch, di, N),
            ("layers", "batch", "conv_inner", None), init="zeros",
            dtype="float32",
        ),
    }
