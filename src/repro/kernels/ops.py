"""Public jit'd entry points for the Pallas kernels (backend dispatch layer).

Call sites across the framework use these wrappers, which

  * resolve padding (SAME/CAUSAL/VALID/explicit) *outside* the kernels so
    the Pallas grids stay rectangular,
  * pick the paper's kernel regime from the filter size
    (``repro.core.conv.regime_for``),
  * resolve tile/channel-block choices: explicit arguments win, then the
    shape-keyed autotuner cache (``repro.kernels.autotune``), then defaults
    — with automatic channel blocking above ``AUTO_BLOCK_THRESHOLD`` so
    large-channel layers never load a full ``(K, Cin, Cout)`` weight tile
    into VMEM,
  * fuse the ``bias`` + ``activation`` epilogue into the sliding kernels
    (one launch for conv→bias→act); non-sliding backends apply it unfused,
  * make the sliding path **differentiable**: ``conv1d``, ``conv2d``,
    ``conv1d_depthwise`` and ``pool1d`` carry a ``jax.custom_vjp`` whose
    backward passes are themselves sliding-window Pallas kernels
    (``repro.kernels.sliding_conv_bwd``, DESIGN.md §6) — dx as a sliding
    correlation of the dilated gradient with flipped/transposed weights
    (tuned under its own autotune shape key), dw/db as a halo-tiled
    sliding reduction, d_act from the saved pre-activation residual,
  * select execution mode: real Pallas lowering on TPU, ``interpret=True``
    everywhere else (this container is CPU-only — interpret mode executes
    the kernel body in Python and is how kernels are validated here),
  * fall back to the pure-JAX ``repro.core`` implementation for configs the
    kernels don't cover (dilation > 1, grouped non-depthwise convs), and
  * wrap every dispatch site in a **graceful-degradation ladder**
    (DESIGN.md §10): pallas kernel → compiled-JAX twin → reference. A rung
    that raises at dispatch/trace time is demoted for the process lifetime
    and the event recorded reason-coded in the central health registry
    (re-exported here as ``HEALTH``); the next rung serves the call, so a
    kernel that fails to compile degrades throughput instead of crashing
    serving. ``repro.faults`` can inject failures at any rung for chaos
    testing.

``backend`` selects the paper's technique (``sliding``) vs the baselines
(``im2col_gemm`` fused-VMEM, ``im2col_hbm`` true-bloat, ``xla``).

``conv1d``, ``conv1d_depthwise`` and ``conv2d`` only gather their
arguments: one dispatcher, ``_conv``, makes the choices above for all
three, reading each op's kernels, twins and tuning key from one table,
``_CONV``.
"""
from __future__ import annotations

import functools
import sys
from typing import Callable, Literal, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import faults, health
from repro.core import conv as core_conv
from repro.health import HEALTH
from repro.obs import metrics as obs_metrics
from repro.kernels import (
    attention_decode as attn_dec,
    autotune,
    im2col_gemm,
    ref as kernels_ref,
    sliding_conv1d,
    sliding_conv2d,
    sliding_conv_bwd,
    sliding_conv_quant,
    sliding_pool,
)
from repro.kernels.sliding_conv1d import apply_activation
from repro.quant import qconv
from repro.quant.apply import quantize_depthwise_weight

Backend = Literal["sliding", "im2col_gemm", "im2col_hbm", "xla"]
# "fp" = full-precision path; the int8 modes dispatch to the quantized
# sliding kernels (repro.kernels.sliding_conv_quant, DESIGN.md §7)
Precision = Literal["fp", "w8a8", "w8a16"]


def use_interpret() -> bool:
    """Pallas interpret mode unless running on a real TPU."""
    return jax.default_backend() != "tpu"


def _ladder(site: str, rungs, *, key: str | None = None):
    """Graceful-degradation dispatch (DESIGN.md §10).

    ``rungs`` is an ordered list of ``(name, thunk)`` — pallas kernel →
    compiled-JAX twin → reference. Rungs already demoted for this site are
    skipped; a rung that raises is demoted for the rest of the process
    (so under ``jax.jit`` a re-trace at a new shape skips it too) with a
    reason-coded ``HEALTH`` event, and the next rung serves the call. The
    last rung's failure propagates — there is nothing left to degrade to.
    ``faults.maybe_fail_rung`` fires inside the try, so injected failures
    exercise exactly this path. Dispatch happens at trace time; a kernel
    that traces fine but dies *at runtime* is covered by the guest trap:
    ``faults.guest_trap`` wraps the winning rung's output (armed by
    runtime-fault injections or the ``REPRO_RUNTIME_SENTINEL`` non-finite
    sentinel), records the (site, rung, key) attribution trip, and the
    failure surfaces from the compiled call to serve/train's runtime
    catch layer, which demotes here and re-jits (DESIGN.md §15). The
    ``key`` kwarg is REQUIRED at every call site (lint-enforced): it is
    the dispatch-key metadata that attribution rides on.

    Demotions are circuit breakers, not process-lifetime: a successful
    dispatch credits ``HEALTH.note_success``, and once a demoted rung's
    cooldown elapses ``HEALTH.is_demoted`` grants it one probation call
    through this exact path — success repromotes it, failure re-demotes
    with a grown cooldown.

    Observability (DESIGN.md §12): the winning rung bumps
    ``dispatch.calls`` under its autotune shape ``key``. Dispatch runs at
    trace time, so a jitted hot loop pays this once per shape, never per
    step.
    """
    live = [(n, t) for n, t in rungs if not HEALTH.is_demoted(site, n)]
    if not live:
        live = [rungs[-1]]  # fully demoted site: keep serving the oracle
    for i, (name, thunk) in enumerate(live):
        try:
            faults.maybe_fail_rung(name, site)
            out = thunk()
            out = faults.guest_trap(site, name, key, out)
            obs_metrics.REGISTRY.counter("dispatch.calls").inc(
                1.0, site=site, key=key or site, rung=name
            )
            HEALTH.note_success(site, name)
            return out
        except Exception as e:  # noqa: BLE001 — any failure → next rung
            if i + 1 == len(live):
                raise
            # canonicalize onto the frozen health.Reason vocabulary: a
            # fault kind passes through, anything else becomes the rung's
            # own error code with the exception repr in detail. An eager
            # guest-trap trip (no jit boundary between us and the
            # debug.callback) loses its FaultError type through XLA —
            # recover the kind from the attribution mailbox.
            trip = faults.consume_trip(site)
            default = trip.kind if trip is not None else f"{name}_error"
            reason = health.canon_reason(e, default=default)
            HEALTH.record(
                site, reason, f"demote:{name}->{live[i + 1][0]}",
                detail=repr(e)[:200],
            )
            HEALTH.demote(site, name, reason=reason)
    raise AssertionError("unreachable")


def _scale_bad(s) -> str | None:
    """Reason code when a *concrete* quant scale is unusable. Tracers pass:
    under ``jax.jit`` the scales were already validated eagerly by
    ``quant.apply.quantize_params`` before entering the jitted call."""
    if s is None or isinstance(s, jax.core.Tracer):
        return None
    v = np.asarray(s)
    if not np.all(np.isfinite(v)):
        return "quant_scale_nan"
    if np.any(v <= 0):
        return "quant_scale_zero"
    return None


def _guard_quant_scales(site, x, w, w_scale, x_scale):
    """Numeric guard on the int8 chain: a zero/NaN scale reaching dispatch
    would emit all-zero or NaN codes and poison every downstream token.
    Returns ``(x_scale, to_float)`` — when the operands are recoverable the
    site degrades (float weights → the float path, float activations → a
    dynamic absmax scale) with a logged event; int8-pinned operands whose
    scale is unusable cannot be recovered at this layer and raise."""
    bad_w = _scale_bad(w_scale) if w.dtype == jnp.int8 else None
    if bad_w:
        HEALTH.record(site, bad_w, "error:w_scale")
        raise ValueError(f"unusable int8 w_scale at {site} ({bad_w})")
    bad_x = _scale_bad(x_scale)
    if not bad_x:
        return x_scale, False
    if x.dtype == jnp.int8:
        HEALTH.record(site, bad_x, "error:x_scale")
        raise ValueError(f"unusable x_scale for int8 input at {site} ({bad_x})")
    if w.dtype != jnp.int8:
        HEALTH.record(site, bad_x, "fallback:fp")
        return x_scale, True
    HEALTH.record(site, bad_x, "fallback:dynamic_scale")
    return None, False


def _pad(x, w, padding, dilation):
    """Apply SAME/CAUSAL/VALID/explicit padding to a 1-D or 2-D input, so
    the kernels see a VALID conv."""
    if x.ndim == 3:
        pads = [core_conv._resolve_pad_1d(padding, w.shape[0], dilation)]
    else:
        pads = list(core_conv._resolve_pad_2d(padding, *w.shape[:2], dilation))
    if any(lo or hi for lo, hi in pads):
        x = jnp.pad(x, ((0, 0), *pads, (0, 0)))
    return x


def _dilated(dilation) -> bool:
    return dilation > 1 if isinstance(dilation, int) else dilation != (1, 1)


def epilogue_unfused(y, bias, activation):
    """bias+activation outside the kernel (baseline backends). Matches the
    fused kernel epilogue's numerics: bias add + activation in f32, one
    cast back to the output dtype."""
    if bias is None and activation in (None, "none"):
        return y
    yf = y.astype(jnp.float32)
    if bias is not None:
        yf = yf + bias.astype(jnp.float32)
    return apply_activation(yf, activation).astype(y.dtype)


def _auto_block(c: int, explicit: int | None) -> int | None:
    if explicit is not None:
        return explicit or None  # 0 means "force unblocked"
    if c > autotune.AUTO_BLOCK_THRESHOLD:
        return autotune.AUTO_BLOCK
    return None


def _tuned_fill(key: str, **fields):
    """Fill None fields from the autotune cache entry for this shape key.

    Resolution precedence (every conv and the decode attention): explicit
    caller argument → tuned cache entry → caller-side default."""
    tuned = autotune.lookup(key)
    if tuned is not None:
        # .get(): a partial / hand-edited cache entry falls back to defaults
        # rather than crashing dispatch for that shape
        fields = {
            k: (tuned.get(k) if v is None else v) for k, v in fields.items()
        }
    return fields


# ---------------------------------------------------------------------------
# conv dispatch: one table row per op, one dispatcher for all of them
# ---------------------------------------------------------------------------

_TILE_DEFAULTS = {
    "tile_l": sliding_conv1d.DEFAULT_TILE_L,
    "tile_h": sliding_conv2d.DEFAULT_TILE_H,
    "tile_w": sliding_conv2d.DEFAULT_TILE_W,
}


def _resolve(op, x, w, stride, tune, dtype_key: str | None = None):
    """explicit args → tuned cache entry → defaults (+ auto blocking).
    Returns ``(shape key, forward kernel kwargs)`` — the key labels the obs
    dispatch series for this call.

    ``dtype_key`` overrides the dtype field of the autotune shape key —
    the quantized paths tune under their precision name ("w8a8"/"w8a16")
    so int8 tilings never collide with float ones."""
    key = _CONV[op].key(x, w, stride, dtype_key or x.dtype.name)
    kw = {"stride": stride}
    for name, v in _tuned_fill(key, **tune).items():
        if name in _TILE_DEFAULTS:
            v = _TILE_DEFAULTS[name] if v is None else v
        elif name == "cout_block":
            v = _auto_block(w.shape[-1], v)
        elif name != "regime":  # cin_block / c_block: the input's channels
            v = _auto_block(x.shape[-1], v)
        kw[name] = v
    return key, kw


def _bwd_tiles(op, x, w, stride, explicit, fwd):
    """Backward dw-kernel tiles: explicit arg → ``|grad`` cache entry →
    default. Depthwise has no grad key: its dw kernel takes the forward
    tile."""
    if all(v is not None for v in explicit.values()):
        return explicit
    if not _CONV[op].tuned_bwd:
        return {k: fwd[k] if v is None else v for k, v in explicit.items()}
    key = _CONV[op].key(x, w, stride, x.dtype.name, grad=True)
    tuned = autotune.lookup(key) or {}
    return {
        k: (tuned.get(k) or _TILE_DEFAULTS[k]) if v is None else v
        for k, v in explicit.items()
    }


def _sliding_kernel(op, x, w, stride, interpret):
    """The forward kernel WITHOUT the custom VJP, at the config tuned for
    its own shape key: dx of a multi-channel conv is this conv over the
    dilated gradient."""
    _, kw = _resolve(op, x, w, stride, dict.fromkeys(_CONV[op].tunables))
    return _CONV[op].kernel(
        x, w, None, activation="none", interpret=interpret, **kw
    )


class _SlidingCfg(NamedTuple):
    """Static kernel configuration threaded through the custom VJP."""
    op: str
    kernel: tuple  # forward kernel kwargs, as (name, value) pairs
    bwd: tuple  # backward dw-kernel tiles, likewise
    activation: str
    has_bias: bool
    interpret: bool


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _sliding_op(cfg: _SlidingCfg, x, w, bias):
    return _CONV[cfg.op].kernel(
        x, w, bias, activation=cfg.activation, interpret=cfg.interpret,
        **dict(cfg.kernel),
    )


def _sliding_fwd(cfg: _SlidingCfg, x, w, bias):
    if cfg.activation in (None, "none"):
        y = _sliding_op(cfg, x, w, bias)
        z = None  # y IS the (cast) pre-activation — nothing extra to save
    else:
        y, z = _CONV[cfg.op].kernel(
            x, w, bias, activation=cfg.activation, interpret=cfg.interpret,
            save_preact=True, **dict(cfg.kernel),
        )
    return y, (x, w, bias, z)


def _sliding_bwd(cfg: _SlidingCfg, res, dy):
    x, w, bias, z = res
    dz = sliding_conv_bwd.act_bwd(dy, z, cfg.activation).astype(x.dtype)
    dx, dw, db = _CONV[cfg.op].bwd(
        x, w, dz, dict(cfg.kernel), dict(cfg.bwd), cfg.has_bias,
        cfg.interpret,
    )
    dbias = db.astype(bias.dtype) if cfg.has_bias else None
    return dx, dw.astype(w.dtype), dbias


_sliding_op.defvjp(_sliding_fwd, _sliding_bwd)


def _conv1d_bwd(x, w, dz, kw, bwd, has_bias, interpret):
    # dx: stride-1 sliding conv of the dilated gradient with the flipped,
    # Cin↔Cout-transposed weights — tuned under its own shape key
    dzp, wt = sliding_conv_bwd.conv1d_dx_operands(dz, w, stride=kw["stride"])
    dx = _sliding_kernel("conv1d", dzp, wt, 1, interpret)
    dx = sliding_conv_bwd._fit_len(dx, x.shape[1])
    dw, db = sliding_conv_bwd.conv1d_bwd_dw_pallas(
        x, dz, w.shape[0], stride=kw["stride"], tile_l=bwd["tile_l"],
        cin_block=kw["cin_block"], cout_block=kw["cout_block"],
        has_bias=has_bias, interpret=interpret,
    )
    return dx, dw, db


def _conv1d_depthwise_bwd(x, w, dz, kw, bwd, has_bias, interpret):
    dx = sliding_conv_bwd.conv1d_depthwise_dx(
        dz, w, stride=kw["stride"], L=x.shape[1], tile_l=kw["tile_l"],
        c_block=kw["c_block"], interpret=interpret,
    )
    dw, db = sliding_conv_bwd.conv1d_depthwise_bwd_dw_pallas(
        x, dz, w.shape[0], stride=kw["stride"], tile_l=bwd["tile_l"],
        c_block=kw["c_block"], has_bias=has_bias, interpret=interpret,
    )
    return dx, dw, db


def _conv2d_bwd(x, w, dz, kw, bwd, has_bias, interpret):
    dzp, wt = sliding_conv_bwd.conv2d_dx_operands(dz, w, stride=kw["stride"])
    dx = _sliding_kernel("conv2d", dzp, wt, (1, 1), interpret)
    dx = sliding_conv_bwd._fit_len(dx, x.shape[1], 1)
    dx = sliding_conv_bwd._fit_len(dx, x.shape[2], 2)
    dw, db = sliding_conv_bwd.conv2d_bwd_dw_pallas(
        x, dz, w.shape[:2], stride=kw["stride"], tile_h=bwd["tile_h"],
        tile_w=bwd["tile_w"], cin_block=kw["cin_block"],
        cout_block=kw["cout_block"], has_bias=has_bias, interpret=interpret,
    )
    return dx, dw, db


class _ConvOp(NamedTuple):
    """One conv's row of the dispatch table."""
    key: Callable  # (x, w, stride, dtype[, grad]) → autotune shape key
    tunables: tuple[str, ...]  # the forward kernel's tile/block arguments
    tuned_bwd: bool  # dw tiles tuned under the |grad key (else forward's)
    kernel: Callable  # fp Pallas kernel, differentiated by _sliding_op
    bwd: Callable  # its Pallas backward kernels → (dx, dw, db)
    quant_kernel: Callable  # int8 Pallas kernel
    quantize_weight: Callable  # float weight → QuantizedWeight
    qconv: Callable  # pure-JAX int8 twin
    twin: Callable  # pure-JAX sliding twin of the fp kernel
    ref: Callable  # XLA reference (also the ``xla`` backend)
    dilated: Callable | None  # repro.core conv for dilation > 1
    baselines: dict  # im2col backend → (x, w, stride, tune, interpret)
    measured_fallback: bool  # int8 → float where tuned timings say so


_CONV = {
    "conv1d": _ConvOp(
        key=lambda x, w, s, dt, grad=False: autotune.conv1d_key(
            *x.shape, w.shape[2], w.shape[0], s, dt, grad=grad),
        tunables=("tile_l", "cin_block", "cout_block", "regime"),
        tuned_bwd=True,
        kernel=sliding_conv1d.conv1d_sliding_pallas,
        bwd=_conv1d_bwd,
        quant_kernel=sliding_conv_quant.conv1d_quant_pallas,
        quantize_weight=qconv.quantize_weight,
        qconv=qconv.conv1d_q,
        twin=core_conv.conv1d_sliding,
        ref=core_conv.conv1d_xla,
        dilated=core_conv.conv1d,
        baselines={
            "im2col_gemm": lambda x, w, s, tune, i: (
                im2col_gemm.conv1d_im2col_fused_pallas(
                    x, w, stride=s, interpret=i,
                    tile_l=_TILE_DEFAULTS["tile_l"] if tune["tile_l"] is None
                    else tune["tile_l"])),
            "im2col_hbm": lambda x, w, s, tune, i: (
                im2col_gemm.conv1d_im2col_hbm(x, w, stride=s, interpret=i)),
        },
        measured_fallback=True,
    ),
    "conv1d_depthwise": _ConvOp(
        key=lambda x, w, s, dt: autotune.conv1d_dw_key(
            *x.shape, w.shape[0], s, dt),
        tunables=("tile_l", "c_block"),
        tuned_bwd=False,
        kernel=sliding_conv1d.conv1d_depthwise_pallas,
        bwd=_conv1d_depthwise_bwd,
        quant_kernel=sliding_conv_quant.conv1d_depthwise_quant_pallas,
        quantize_weight=quantize_depthwise_weight,
        qconv=qconv.conv1d_depthwise_q,
        twin=core_conv.conv1d_depthwise_sliding,
        ref=lambda x, w, stride, padding: core_conv.conv1d_xla(
            x, w[:, None, :], stride=stride, padding=padding,
            groups=x.shape[-1]),
        dilated=None,
        baselines={},
        measured_fallback=False,
    ),
    "conv2d": _ConvOp(
        key=lambda x, w, s, dt, grad=False: autotune.conv2d_key(
            *x.shape, w.shape[3], *w.shape[:2], *s, dt, grad=grad),
        tunables=("tile_h", "tile_w", "cin_block", "cout_block", "regime"),
        tuned_bwd=True,
        kernel=sliding_conv2d.conv2d_sliding_pallas,
        bwd=_conv2d_bwd,
        quant_kernel=sliding_conv_quant.conv2d_quant_pallas,
        quantize_weight=qconv.quantize_weight,
        qconv=qconv.conv2d_q,
        twin=core_conv.conv2d_sliding,
        ref=core_conv.conv2d_xla,
        dilated=core_conv.conv2d,
        baselines={
            # the fused-VMEM baseline, not the HBM-bloat one
            "im2col_gemm": lambda x, w, s, tune, i: (
                im2col_gemm.conv2d_im2col_fused_pallas(
                    x, w, stride=s, interpret=i)),
            "im2col_hbm": lambda x, w, s, tune, i: (
                im2col_gemm.conv2d_im2col_hbm(x, w, stride=s, interpret=i)),
        },
        measured_fallback=False,
    ),
}


def _check_quant_dispatch(precision, backend, dilation):
    if backend != "sliding":
        raise ValueError(
            f"precision={precision!r} is implemented for the sliding "
            f"backend only (got backend={backend!r})"
        )
    if _dilated(dilation):
        raise ValueError("quantized convs cover dilation == 1 only")


# shape key → reason for shapes where the quant path measurably loses to the
# float path and dispatch fell back (logged once per shape; inspectable).
# DispatchLog dedup-counts repeats per key — a long serving run hitting the
# same fallback every step bumps a counter instead of growing state. Named:
# hits mirror into the obs registry (dispatch.log_calls / facts) so
# metrics.json carries the fallback record
_QUANT_FALLBACKS = health.DispatchLog("quant_fallback")


def _quant_fallback_reason(op, x, w, stride, precision) -> str | None:
    """Measured-regression guard for the quant dispatch of the ops whose
    table row asks for it (1-D): when the autotune cache holds timings for
    BOTH this shape's quant path and its float path and the float one is
    faster (the per-tap 1-D regime is accumulator-traffic-bound — int8
    operands buy nothing once upcast, so small-K 1-D shapes can lose to
    bf16/f32), dispatch the float path instead of silently serving the
    slower kernel."""
    kq = _CONV[op].key(x, w, stride, precision)
    kf = _CONV[op].key(x, w, stride, x.dtype.name)
    tq, tf = autotune.lookup(kq), autotune.lookup(kf)
    if not (tq and tf):
        return None
    us_q, us_f = tq.get("us"), tf.get("us")
    if us_q is None or us_f is None or us_q <= us_f:
        return None
    reason = (
        f"tuned {precision} path {us_q:.0f}us > {x.dtype.name} "
        f"{us_f:.0f}us for {kq}; serving the float path"
    )
    first = kq not in _QUANT_FALLBACKS
    _QUANT_FALLBACKS[kq] = reason  # repeat hits bump the per-key count
    if first:
        print(f"[quant] fallback: {reason}", file=sys.stderr)
        HEALTH.record(
            f"{op}.{precision}", "quant_slower", "fallback:fp", detail=kq,
        )
    return reason


def _conv(op, x, w, *, stride, padding, dilation, backend, bias, activation,
          tune, bwd_tune, interpret, precision, w_scale, x_scale, out_scale):
    """The one conv dispatch behind ``conv1d``, ``conv1d_depthwise`` and
    ``conv2d``; ``op`` names the table row. ``tune`` holds the forward
    kernel's explicit tile/block/regime arguments, ``bwd_tune`` the dw
    kernel's tiles.

    A quantized ``precision`` runs the int8 ladder (Pallas kernel → qconv
    "fast" → qconv exact int32). It falls through to the float path of
    the same call when the scale guard finds an unusable activation scale
    on float operands, or when tuned timings say the float kernel is
    faster — unless the call is pinned to int8: int8 input or a fused
    requant (a chained site keeps its int8 contract), or explicit
    tile/block/regime arguments (the autotuner measures the exact config
    it asked for, and a fallback would record the float path under the
    quant key). The float path: ``xla``, then dilation > 1 through
    ``repro.core``, then the sliding ladder (custom-VJP Pallas op → JAX
    sliding twin → XLA reference) or an im2col baseline."""
    spec = _CONV[op]
    interpret = use_interpret() if interpret is None else interpret
    if precision != "fp":
        _check_quant_dispatch(precision, backend, dilation)
        x, padding = _pad(x, w, padding, dilation), "VALID"
        site = f"{op}.{precision}"
        x_scale, to_float = _guard_quant_scales(site, x, w, w_scale, x_scale)
        if (
            not to_float and spec.measured_fallback
            and x.dtype != jnp.int8 and out_scale is None
            and all(v is None for v in tune.values())
            and _quant_fallback_reason(op, x, w, stride, precision)
        ):
            to_float = True
            if w.dtype == jnp.int8:
                if w_scale is None:
                    raise ValueError("int8 weights need their w_scale")
                w = (w.astype(jnp.float32) * w_scale).astype(x.dtype)
        if not to_float:
            out_dtype = jnp.float32 if x.dtype == jnp.int8 else x.dtype
            if w.dtype != jnp.int8:
                qw = spec.quantize_weight(w)
                w, w_scale = qw.q, qw.scale
            elif w_scale is None:
                raise ValueError("int8 weights need their w_scale")
            if precision == "w8a8" and x.dtype != jnp.int8:
                x_scale = qconv.act_scale(x) if x_scale is None else x_scale
                x = qconv.quantize_act(x, x_scale)
            key, kw = _resolve(op, x, w, stride, tune, dtype_key=precision)

            def q_jax(accumulate):
                # "fast" = compiled serving evaluation, "int32" = exact oracle
                return spec.qconv(
                    x, qconv.QuantizedWeight(w, w_scale), bias,
                    mode=precision, x_scale=x_scale, out_scale=out_scale,
                    stride=stride, padding="VALID", activation=activation,
                    accumulate=accumulate, out_dtype=out_dtype,
                )

            return _ladder(site, key=key, rungs=[
                ("pallas", lambda: spec.quant_kernel(
                    x, w, w_scale, bias, x_scale=x_scale,
                    out_scale=out_scale, mode=precision,
                    activation=activation, out_dtype=out_dtype,
                    interpret=interpret, **kw,
                )),
                ("jax", lambda: q_jax("fast")),
                ("ref", lambda: q_jax("int32")),
            ])
    if backend == "xla":
        y = spec.ref(x, w, stride=stride, padding=padding, dilation=dilation)
        return epilogue_unfused(y, bias, activation)
    if _dilated(dilation):  # kernels cover dilation=1; core handles the rest
        y = spec.dilated(
            x, w, stride=stride, padding=padding, dilation=dilation,
            backend="sliding" if backend == "sliding" else "im2col_gemm",
        )
        return epilogue_unfused(y, bias, activation)
    x = _pad(x, w, padding, dilation)
    if backend == "sliding":
        key, kw = _resolve(op, x, w, stride, tune)
        cfg = _SlidingCfg(
            op, tuple(kw.items()),
            tuple(_bwd_tiles(op, x, w, stride, bwd_tune, kw).items()),
            activation, bias is not None, interpret,
        )
        return _ladder(op, key=key, rungs=[
            ("pallas", lambda: _sliding_op(cfg, x, w, bias)),
            ("jax", lambda: epilogue_unfused(
                spec.twin(x, w, stride=stride, padding="VALID"),
                bias, activation,
            )),
            ("ref", lambda: epilogue_unfused(
                spec.ref(x, w, stride=stride, padding="VALID"),
                bias, activation,
            )),
        ])
    if backend not in spec.baselines:
        raise ValueError(backend)
    y = spec.baselines[backend](x, w, stride, tune, interpret)
    return epilogue_unfused(y, bias, activation)


def conv1d(
    x: jax.Array,
    w: jax.Array,
    *,
    stride: int = 1,
    padding="VALID",
    dilation: int = 1,
    backend: Backend = "sliding",
    bias: jax.Array | None = None,
    activation: str = "none",
    tile_l: int | None = None,
    cin_block: int | None = None,
    cout_block: int | None = None,
    regime: str | None = None,
    bwd_tile_l: int | None = None,
    interpret: bool | None = None,
    precision: Precision = "fp",
    w_scale: jax.Array | None = None,
    x_scale: jax.Array | None = None,
    out_scale: jax.Array | None = None,
) -> jax.Array:
    """Multi-channel 1-D convolution. x: (B,L,Cin), w: (K,Cin,Cout).

    ``bias`` (Cout,) + ``activation`` (none/relu/gelu/gelu_erf/silu) are
    fused into the sliding kernel's epilogue; baseline backends apply them
    unfused.
    The sliding path is differentiable (custom VJP with Pallas backward
    kernels); ``bwd_tile_l`` overrides the backward dw-kernel tile.

    ``precision`` ∈ {"fp", "w8a8", "w8a16"} selects the int8 quantized
    sliding kernels (inference-only, no VJP): ``w`` may be pre-quantized
    int8 (+ ``w_scale`` per-Cout) or float (quantized here); for w8a8,
    ``x`` is quantized onto ``x_scale`` (dynamic absmax when None) and
    ``out_scale`` fuses an int8 requant after the activation. Tuned under
    the precision-suffixed autotune shape key.
    """
    return _conv(
        "conv1d", x, w, stride=stride, padding=padding, dilation=dilation,
        backend=backend, bias=bias, activation=activation,
        tune=dict(tile_l=tile_l, cin_block=cin_block, cout_block=cout_block,
                  regime=regime),
        bwd_tune=dict(tile_l=bwd_tile_l), interpret=interpret,
        precision=precision, w_scale=w_scale, x_scale=x_scale,
        out_scale=out_scale,
    )


def conv1d_depthwise(
    x: jax.Array,
    w: jax.Array,
    *,
    stride: int = 1,
    padding="CAUSAL",
    bias: jax.Array | None = None,
    activation: str = "none",
    tile_l: int | None = None,
    c_block: int | None = None,
    bwd_tile_l: int | None = None,
    interpret: bool | None = None,
    precision: Precision = "fp",
    w_scale: jax.Array | None = None,
    x_scale: jax.Array | None = None,
    out_scale: jax.Array | None = None,
) -> jax.Array:
    """Depthwise 1-D sliding conv (Mamba conv path). x: (B,L,C), w: (K,C).

    conv→bias→activation is one kernel launch (fused epilogue); the path is
    differentiable end-to-end (Pallas backward kernels).

    ``precision`` ∈ {"w8a8", "w8a16"} dispatches the int8 depthwise VPU
    kernel (inference-only): ``w`` may be pre-quantized int8 (+ ``w_scale``
    per-channel over the tap axis) or float (quantized here); for w8a8 the
    input quantizes onto ``x_scale`` (dynamic absmax when None). Tuned
    under the depthwise precision-named autotune shape key.
    """
    return _conv(
        "conv1d_depthwise", x, w, stride=stride, padding=padding, dilation=1,
        backend="sliding", bias=bias, activation=activation,
        tune=dict(tile_l=tile_l, c_block=c_block),
        bwd_tune=dict(tile_l=bwd_tile_l), interpret=interpret,
        precision=precision, w_scale=w_scale, x_scale=x_scale,
        out_scale=out_scale,
    )


def conv2d(
    x: jax.Array,
    w: jax.Array,
    *,
    stride: tuple[int, int] = (1, 1),
    padding="VALID",
    dilation: tuple[int, int] = (1, 1),
    backend: Backend = "sliding",
    bias: jax.Array | None = None,
    activation: str = "none",
    tile_h: int | None = None,
    tile_w: int | None = None,
    cin_block: int | None = None,
    cout_block: int | None = None,
    regime: str | None = None,
    bwd_tile_h: int | None = None,
    bwd_tile_w: int | None = None,
    interpret: bool | None = None,
    precision: Precision = "fp",
    w_scale: jax.Array | None = None,
    x_scale: jax.Array | None = None,
    out_scale: jax.Array | None = None,
) -> jax.Array:
    """Multi-channel 2-D convolution. x: (B,H,W,Cin), w: (kh,kw,Cin,Cout).

    ``bias``/``activation`` fuse into the sliding kernel epilogue; the
    sliding path is differentiable (custom VJP, Pallas backward kernels).
    ``precision`` selects the int8 quantized kernels — see ``conv1d``.
    """
    return _conv(
        "conv2d", x, w, stride=stride, padding=padding, dilation=dilation,
        backend=backend, bias=bias, activation=activation,
        tune=dict(tile_h=tile_h, tile_w=tile_w, cin_block=cin_block,
                  cout_block=cout_block, regime=regime),
        bwd_tune=dict(tile_h=bwd_tile_h, tile_w=bwd_tile_w),
        interpret=interpret, precision=precision, w_scale=w_scale,
        x_scale=x_scale, out_scale=out_scale,
    )


# ---------------------------------------------------------------------------
# fused decode attention (single-query, int8 or fp KV cache)
# ---------------------------------------------------------------------------

# autotune shape key → impl that served it ("pallas" | "jax" | "ref"),
# recorded at trace time. Serving prints these lines so CI can assert the
# fused path actually dispatched for the decode loop (DESIGN.md §9).
# DispatchLog dedup-counts per key (bounded by distinct cache shapes, not
# by decode steps) and ``.count(key)`` says how often each was served.
# Named: hits mirror into the obs registry so the report CLI can rebuild
# the ``calls=N`` lines from metrics.json alone
ATTN_DECODE_DISPATCH = health.DispatchLog("attn_decode")


def attention_decode(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    lengths: jax.Array,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    impl: str | None = None,
    block_s: int | None = None,
    h_block: int | None = None,
    interpret: bool | None = None,
    name: str = attn_dec.NAME,
) -> jax.Array:
    """Fused flash-style decode attention against the (possibly int8) KV
    cache — the dequant folds into the online softmax, so the cache's
    int8 codes stay resident and no float K/V view is materialized
    (DESIGN.md §9).

    q: (B, H, D) the new token's query heads; k/v: (B, S, KV, D) cache
    leaves — int8 codes with per-(position, head) f32 ``k_scale``/
    ``v_scale`` rows (B, S, KV, 1), or float rows without. ``lengths``:
    (B,) int32 valid-prefix per slot (decode: ``pos + 1``; cross-attention:
    ragged encoder lengths — a 0 length yields a zero output row). GQA is
    implicit: H = KV · G, grouped query layout, K/V broadcast per group.

    ``impl``: "pallas" (TPU kernel; interpret elsewhere), "jax" (compiled
    blocked scan — same algebra, the CPU serving path), "ref" (dequant-view
    oracle). None → pallas on TPU, jax otherwise. ``block_s``/``h_block``
    resolve explicit → ``attn_dec|…`` autotune cache entry → default.
    ``name`` names the Pallas kernel in the device trace. Returns (B, H, D)
    f32.
    """
    B, H, D = q.shape
    S, KV = k.shape[1], k.shape[2]
    if H % KV:
        raise ValueError(f"H={H} not divisible by KV={KV}")
    G = H // KV
    quantized = k.dtype == jnp.int8
    if quantized and (k_scale is None or v_scale is None):
        raise ValueError("int8 KV cache needs its k_scale/v_scale rows")
    kind = "int8" if quantized else k.dtype.name
    key = autotune.attn_dec_key(B, S, KV, G, D, kind)
    cfg = _tuned_fill(key, block_s=block_s, h_block=h_block)
    if impl is None:
        impl = "pallas" if jax.default_backend() == "tpu" else "jax"
    # untuned defaults: the Pallas kernel tiles kv_seq to bound VMEM and
    # takes all KV heads per step; the compiled CPU path defaults to ONE
    # block (the whole cache) — decode caches are cache-hierarchy-resident
    # there and the blocked scan only adds carry overhead (measured:
    # single-block 1.3× over block_s=128 at S=512). The ``attn_dec|…``
    # tuned entry overrides either way.
    h_block = cfg["h_block"]
    interpret = use_interpret() if interpret is None else interpret
    q4 = q.reshape(B, KV, G, D)

    def _run(im):
        # the impl that actually served this key — a demoted rung's
        # replacement overwrites the failed rung's entry
        ATTN_DECODE_DISPATCH[key] = im
        block_s = cfg["block_s"] or (
            attn_dec.DEFAULT_BLOCK_S if im == "pallas" else S
        )
        if im == "pallas":
            return attn_dec.decode_attention_pallas(
                q4, k, v, k_scale, v_scale, lengths,
                block_s=block_s, h_block=h_block, interpret=interpret,
                name=name,
            )
        if im == "jax":
            return attn_dec.attention_decode_jax(
                q4, k, v, k_scale, v_scale, lengths, block_s=block_s
            )
        return attn_dec.attention_decode_ref(
            q4, k, v, k_scale, v_scale, lengths
        )

    order = {
        "pallas": ("pallas", "jax", "ref"),
        "jax": ("jax", "ref"),
        "ref": ("ref",),
    }.get(impl)
    if order is None:
        raise ValueError(f"unknown attention_decode impl {impl!r}")
    out = _ladder(
        "attention_decode",
        [(im, functools.partial(_run, im)) for im in order],
        key=key,
    )
    return out.reshape(B, H, D)


def matmul(a: jax.Array, b: jax.Array, *, interpret: bool | None = None) -> jax.Array:
    interpret = use_interpret() if interpret is None else interpret
    return im2col_gemm.matmul_pallas(a, b, interpret=interpret)


# ---------------------------------------------------------------------------
# pool1d — custom VJP
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _pool1d_op(window: int, op: str, method: str, interpret: bool, x):
    return sliding_pool.sliding_pool_pallas(
        x, window=window, op=op, method=method, interpret=interpret
    )


def _pool1d_fwd(window, op, method, interpret, x):
    y = sliding_pool.sliding_pool_pallas(
        x, window=window, op=op, method=method, interpret=interpret
    )
    # sum/avg backward needs no residual; max needs (x, y) as argmax witness
    return y, ((x, y) if op == "max" else None)


def _pool1d_bwd(window, op, method, interpret, res, dy):
    if op == "max":
        x, y = res
        dx = sliding_pool.max_pool_bwd_pallas(
            x, y, dy, window=window, interpret=interpret
        )
        return (dx,)
    g = dy
    if op == "avg":
        g = (dy.astype(jnp.float32) / window).astype(dy.dtype)
    dx = sliding_pool.sum_pool_bwd(g, window=window, interpret=interpret)
    return (dx.astype(dy.dtype),)


_pool1d_op.defvjp(_pool1d_fwd, _pool1d_bwd)

# max-pool method crossover when the shape was never tuned: shift-and-max
# (lower constant) below, two-phase scan (O(n), window-independent) from
# here up — the measured BENCH crossover sits between w=16 and w=64
POOL_SHIFT_MAX_WINDOW = 32


def _pool_method(x, window: int, op: str, explicit: str | None) -> str:
    """explicit arg → tuned cache entry (``autotune_pool1d``) → heuristic."""
    if explicit is not None:
        return explicit
    if op != "max":
        return "scan"
    B, L, C = x.shape
    tuned = autotune.lookup(autotune.pool1d_key(B, L, C, window, op,
                                                x.dtype.name))
    if tuned and tuned.get("method") in ("scan", "shift"):
        return tuned["method"]
    return "shift" if window < POOL_SHIFT_MAX_WINDOW else "scan"


def pool1d(
    x: jax.Array,
    *,
    window: int,
    op: str = "sum",
    method: str | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """VALID sliding pooling along axis 1. x: (B,L,C). Differentiable:
    sum/avg backward reuses the two-phase scan kernel on the padded
    gradient; max backward is the shift-and-select Pallas kernel.

    ``method`` picks the max-pool forward evaluation ("scan" | "shift");
    None resolves it per shape from the autotune cache (falling back to the
    window-size crossover heuristic) instead of hardcoding one form."""
    interpret = use_interpret() if interpret is None else interpret
    resolved = _pool_method(x, window, op, method)
    pool_key = autotune.pool1d_key(*x.shape, window, op, x.dtype.name)
    return _ladder("pool1d", key=pool_key, rungs=[
        ("pallas", lambda: _pool1d_op(window, op, resolved, interpret, x)),
        ("jax", lambda: kernels_ref.pool_ref(x, window=window, op=op)),
    ])
