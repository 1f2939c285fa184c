"""Shared transformer building blocks (pure-functional JAX).

Conventions:
  * activations ``compute_dtype`` (bf16), reductions/softmax/norms in f32,
  * GQA attention with grouped einsums (no KV head repetition in memory),
  * flash-style chunked attention (online softmax over KV blocks inside a
    scan) for long sequences — O(L·chunk) score memory instead of O(L²),
  * decode path with a static pre-allocated KV cache,
  * sequence-chunked cross-entropy so the (B, L, vocab) logits tensor is
    never materialized (matters for the 152k/256k vocabs).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import ParamDef, Runtime

Array = jax.Array


# ---------------------------------------------------------------------------
# numerics
# ---------------------------------------------------------------------------

def cdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.compute_dtype)


def rms_norm(x: Array, scale: Array, eps: float = 1e-6) -> Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    return out.astype(x.dtype)


def layer_norm(x: Array, scale: Array, bias: Array, eps: float) -> Array:
    """LayerNorm with a bias, in f32 (whisper's blocks)."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    return (out + bias.astype(jnp.float32)).astype(x.dtype)


def act_fn(name: str):
    return {
        "silu": jax.nn.silu,
        "gelu": functools.partial(jax.nn.gelu, approximate=True),
        # whisper's ungated MLP: the exact (erf) GELU
        "gelu_plain": functools.partial(jax.nn.gelu, approximate=False),
        "relu_sq": lambda x: jnp.square(jax.nn.relu(x)),
    }[name]


# ---------------------------------------------------------------------------
# Fused conv→bias→activation building blocks
# ---------------------------------------------------------------------------
# Every model conv (whisper's frontend, llava's patch_embed, mamba's
# depthwise conv, the edge CNN) goes through ``conv_bias_act``. On the
# Pallas path (backend "sliding_pallas") it calls ``repro.kernels.ops``,
# where the bias add and activation run inside the conv kernel's epilogue
# — one launch, no extra HBM round trips. Pure-JAX / XLA backends apply
# them unfused with identical semantics (activations are the
# kernel-epilogue set: none/relu/gelu/gelu_erf/silu).
# Every backend is differentiable: the Pallas ops carry a custom VJP with
# sliding-window backward kernels (DESIGN.md §6).
#
# Quantized inference (DESIGN.md §7): ``w`` may be a
# ``repro.quant.QuantizedWeight`` (int8 + scales, from quant.apply) and/or
# ``precision`` ∈ {"w8a8", "w8a16"} may be set (float weights quantize on
# the fly). The Pallas backend then runs the fused int8 kernels; pure-JAX
# backends run ``repro.quant.qconv`` with the same int32 arithmetic. Every
# conv is a calibration site: under ``quant.calibrate.collecting`` the
# input activation is observed (eagerly) under ``site``.


def _quant_mode(w, precision: str) -> str | None:
    from repro.quant.qconv import QuantizedWeight

    if precision in ("w8a8", "w8a16"):
        return precision
    if isinstance(w, QuantizedWeight):  # quantized leaf, default weight-only
        return "w8a16"
    return None


def conv_bias_act(
    op: str,
    x: Array,
    w: Any,
    b: Array | None,
    *,
    mode: str | None,
    activation: str = "none",
    stride=1,
    padding="VALID",
    backend: str = "sliding",
    site: str | None = None,
) -> Array:
    """conv→bias→activation for ``op`` ∈ {"conv1d", "conv2d",
    "conv1d_depthwise"} (the ``repro.kernels.ops`` names).

    ``mode`` is the caller's int8 choice ("w8a8"/"w8a16"; None for
    float): a float ``w`` quantizes here, a ``QuantizedWeight`` is used
    as stored — and with no mode it dequantizes in registers. ``site``
    defaults to the shape-derived calibration site."""
    from repro.core import conv as C
    from repro.kernels import ops
    from repro.kernels.sliding_conv1d import apply_activation
    from repro.quant import calibrate, qconv

    quantized = isinstance(w, qconv.QuantizedWeight)
    shape = (w.q if quantized else w).shape
    taps = shape[:1] if op == "conv1d_depthwise" else shape[:-2]
    site = site or calibrate.conv_site(
        {"conv1d_depthwise": "conv1d_dw"}.get(op, op), x.shape[-1],
        shape[-1], "x".join(map(str, taps)),
    )
    calibrate.observe(site, x)
    kernel = {"conv1d": ops.conv1d, "conv2d": ops.conv2d,
              "conv1d_depthwise": ops.conv1d_depthwise}[op]
    if mode is not None:
        qw = w if quantized else qconv.quantize_weight(w)
        # requant chaining (DESIGN.md §8): a leaf carrying out_scale emits
        # int8 on the consumer's grid — only meaningful in w8a8, where the
        # consumer quantizes its input anyway. An int8 INPUT here is the
        # other end of a chain: its scale is this site's calibrated x_scale.
        out_scale = qw.out_scale if mode == "w8a8" else None
        if out_scale is None:
            calibrate.note_dequant(site)
        if backend == "sliding_pallas":
            return kernel(
                x, qw.q, stride=stride, padding=padding, bias=b,
                activation=activation, precision=mode, w_scale=qw.scale,
                x_scale=qw.x_scale, out_scale=out_scale,
            )
        # accumulate="fast": the compiled CPU evaluation (int8 storage,
        # f32 GEMMs) — the exact-int32 default is the test oracle, ~4×
        # slower than f32 through XLA CPU's integer matmul
        q = {"conv1d": qconv.conv1d_q, "conv2d": qconv.conv2d_q,
             "conv1d_depthwise": qconv.conv1d_depthwise_q}[op]
        return q(
            x, qw, b, mode=mode, stride=stride, padding=padding,
            x_scale=qw.x_scale, out_scale=out_scale, activation=activation,
            out_dtype=jnp.float32 if x.dtype == jnp.int8 else x.dtype,
            accumulate="fast",
        )
    w = w.dequant(x.dtype) if quantized else w.astype(x.dtype)
    if backend == "sliding_pallas":
        return kernel(
            x, w, stride=stride, padding=padding, bias=b,
            activation=activation,
        )
    if op != "conv1d_depthwise":
        conv = C.conv1d if op == "conv1d" else C.conv2d
        y = conv(x, w, stride=stride, padding=padding, backend=backend)
        return ops.epilogue_unfused(y, b, activation)
    # mamba's depthwise conv adds the bias and activates in its own dtype
    if backend == "sliding":
        y = C.conv1d_depthwise_sliding(x, w, stride=stride, padding=padding)
    elif backend == "xla":
        y = C.conv1d_xla(x, w[:, None, :], stride=stride, padding=padding,
                         groups=w.shape[1])
    else:
        raise ValueError(backend)
    return apply_activation(y + b.astype(y.dtype), activation)


def conv1d_bias_act(
    x: Array,
    w: Array,
    b: Array | None,
    *,
    activation: str = "none",
    stride: int = 1,
    padding="VALID",
    backend: str = "sliding",
    precision: str = "fp",
    site: str | None = None,
) -> Array:
    """Multi-channel conv1d + bias + activation. x: (B,L,Cin), w: (K,Cin,Cout)
    float or ``QuantizedWeight``."""
    return conv_bias_act(
        "conv1d", x, w, b, mode=_quant_mode(w, precision),
        activation=activation, stride=stride, padding=padding,
        backend=backend, site=site,
    )


def conv2d_bias_act(
    x: Array,
    w: Array,
    b: Array | None,
    *,
    activation: str = "none",
    stride: tuple[int, int] = (1, 1),
    padding="VALID",
    backend: str = "sliding",
    precision: str = "fp",
    site: str | None = None,
) -> Array:
    """Multi-channel conv2d + bias + activation. x: (B,H,W,Cin), w: HWIO
    float or ``QuantizedWeight``."""
    return conv_bias_act(
        "conv2d", x, w, b, mode=_quant_mode(w, precision),
        activation=activation, stride=stride, padding=padding,
        backend=backend, site=site,
    )


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> Array:
    return 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )


def apply_rope(x: Array, positions: Array, theta: float) -> Array:
    """x: (..., L, H, D); positions: (..., L) int32."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)  # (D/2,)
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # (..., L, D/2)
    cos = jnp.cos(ang)[..., :, None, :]
    sin = jnp.sin(ang)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention parameter defs
# ---------------------------------------------------------------------------

def attention_defs(cfg: ModelConfig) -> dict[str, ParamDef]:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    h, kv = cfg.num_heads, cfg.num_kv_heads
    defs = {
        "wq": ParamDef((d, h, hd), ("embed", "heads", "head_dim"), init="fan_in"),
        "wk": ParamDef((d, kv, hd), ("embed", "kv_heads", "head_dim"), init="fan_in"),
        "wv": ParamDef((d, kv, hd), ("embed", "kv_heads", "head_dim"), init="fan_in"),
        "wo": ParamDef((h, hd, d), ("heads", "head_dim", "embed"), init="fan_in"),
    }
    if cfg.qk_norm:
        defs["q_norm"] = ParamDef((hd,), (None,), init="ones")
        defs["k_norm"] = ParamDef((hd,), (None,), init="ones")
    return defs


def mlp_defs(cfg: ModelConfig, d_ff: int | None = None) -> dict[str, ParamDef]:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    if cfg.activation == "gelu_plain":  # ungated (whisper)
        return {
            "wi": ParamDef((d, f), ("embed", "mlp"), init="fan_in"),
            "wo": ParamDef((f, d), ("mlp", "embed"), init="fan_in"),
        }
    return {
        "wg": ParamDef((d, f), ("embed", "mlp"), init="fan_in"),
        "wu": ParamDef((d, f), ("embed", "mlp"), init="fan_in"),
        "wd": ParamDef((f, d), ("mlp", "embed"), init="fan_in"),
    }


def add_bias(y: Array, p, name: str) -> Array:
    """``y`` (B, L, ...) plus the parameter tree's flat bias ``name`` laid
    out over ``y``'s trailing axes, where the tree holds one (whisper's
    projections). A tree without it (qwen3's) adds no operation."""
    b = p.get(name)
    if b is None:
        return y
    return y + b.reshape(y.shape[2:]).astype(y.dtype)


def mlp_apply(p, x: Array, cfg: ModelConfig) -> Array:
    f = act_fn(cfg.activation)
    if cfg.activation == "gelu_plain":
        h = f(add_bias(jnp.einsum("bld,df->blf", x, p["wi"].astype(x.dtype)),
                       p, "bi"))
        return add_bias(jnp.einsum("blf,fd->bld", h, p["wo"].astype(x.dtype)),
                        p, "bo")
    g = jnp.einsum("bld,df->blf", x, p["wg"].astype(x.dtype))
    u = jnp.einsum("bld,df->blf", x, p["wu"].astype(x.dtype))
    return jnp.einsum("blf,fd->bld", f(g) * u, p["wd"].astype(x.dtype))


# ---------------------------------------------------------------------------
# Attention forward paths
# ---------------------------------------------------------------------------

def _qkv(p, x: Array, cfg: ModelConfig, positions: Array, rope: bool = True):
    dt = x.dtype
    q = add_bias(jnp.einsum("bld,dhk->blhk", x, p["wq"].astype(dt)), p, "bq")
    k = add_bias(jnp.einsum("bld,dhk->blhk", x, p["wk"].astype(dt)), p, "bk")
    v = add_bias(jnp.einsum("bld,dhk->blhk", x, p["wv"].astype(dt)), p, "bv")
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _group(q: Array, kv_heads: int):
    """(B, L, H, D) -> (B, L, KV, G, D) grouped query layout."""
    B, L, H, D = q.shape
    return q.reshape(B, L, kv_heads, H // kv_heads, D)


def full_attention(
    q: Array, k: Array, v: Array, *, causal: bool, q_offset: int = 0,
    kv_mask: Array | None = None,
) -> Array:
    """Direct attention (short sequences / decode). q: (B,Lq,H,D), k/v:
    (B,Lk,KV,D). ``kv_mask`` (B, Lk) bool gates invalid key positions
    (e.g. zero-padded cache rows — a zero key scores logit 0, NOT -inf,
    so padding would otherwise leak softmax mass)."""
    B, Lq, H, D = q.shape
    KV = k.shape[2]
    qg = _group(q, KV)
    scale = D ** -0.5
    scores = jnp.einsum("blkgd,bmkd->bkglm", qg, k).astype(jnp.float32) * scale
    if causal:
        qpos = jnp.arange(Lq) + q_offset
        kpos = jnp.arange(k.shape[1])
        mask = qpos[:, None] >= kpos[None, :]
        scores = jnp.where(mask[None, None, None], scores, -jnp.inf)
    if kv_mask is not None:
        scores = jnp.where(
            kv_mask[:, None, None, None, :], scores, -jnp.inf
        )
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkglm,bmkd->blkgd", w, v)
    return out.reshape(B, Lq, H, D)


def chunked_attention(
    q: Array,
    k: Array,
    v: Array,
    *,
    causal: bool,
    chunk: int,
    kv_mask: Array | None = None,
) -> Array:
    """Flash-style attention: scan over KV chunks with online softmax.

    q: (B, Lq, H, D); k/v: (B, Lk, KV, D); non-chunk-divisible lengths are
    padded internally (padded KV masked, padded Q rows trimmed).
    Score memory: O(cq*ck) per step instead of O(Lq*Lk).
    Causal masking is applied per block pair; fully-masked pairs still cost
    FLOPs in this baseline (the §Perf log addresses recovering them).
    """
    B, Lq0, H, D = q.shape
    Lk0 = k.shape[1]
    pad_q = (-Lq0) % min(chunk, Lq0)
    pad_k = (-Lk0) % min(chunk, Lk0)
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        base = jnp.arange(Lk0 + pad_k)[None, :] < Lk0
        if kv_mask is not None:
            kv_mask = jnp.pad(kv_mask, ((0, 0), (0, pad_k))) & base
        else:
            kv_mask = jnp.broadcast_to(base, (B, Lk0 + pad_k))
    B, Lq, H, D = q.shape
    Lk, KV = k.shape[1], k.shape[2]
    G = H // KV
    cq = min(chunk, Lq)
    ck = min(chunk, Lk)
    nq, nk = Lq // cq, Lk // ck
    scale = D ** -0.5
    qg = _group(q, KV).reshape(B, nq, cq, KV, G, D)
    kc = k.reshape(B, nk, ck, KV, D)
    vc = v.reshape(B, nk, ck, KV, D)

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def q_block(qi, q_chunk):
        # remat per q-chunk: backward recomputes score blocks instead of
        # saving the O(L²/chunk²) stack of (cq, ck) probability tiles.
        # q_chunk: (B, cq, KV, G, D)
        m0 = jnp.full((B, KV, G, cq), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, KV, G, cq), jnp.float32)
        a0 = jnp.zeros((B, cq, KV, G, D), jnp.float32)

        def kv_block(carry, inp):
            m, l, acc = carry
            kj, k_chunk, v_chunk = inp
            s = (
                jnp.einsum("blkgd,bmkd->bkglm", q_chunk, k_chunk).astype(
                    jnp.float32
                )
                * scale
            )  # (B, KV, G, cq, ck)
            if causal:
                qpos = qi * cq + jnp.arange(cq)
                kpos = kj * ck + jnp.arange(ck)
                s = jnp.where(
                    qpos[:, None] >= kpos[None, :], s, -jnp.inf
                )
            if kv_mask is not None:
                mblk = jax.lax.dynamic_slice_in_dim(kv_mask, kj * ck, ck, axis=1)
                s = jnp.where(mblk[:, None, None, None, :], s, -jnp.inf)
            m_new = jnp.maximum(m, s.max(axis=-1))
            # guard fully-masked rows (m_new = -inf)
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(s - m_safe[..., None])
            p = jnp.where(jnp.isfinite(s), p, 0.0)
            corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
            l_new = l * corr + p.sum(axis=-1)
            pv = jnp.einsum("bkglm,bmkd->blkgd", p.astype(q.dtype), v_chunk)
            acc_new = acc * jnp.moveaxis(corr, 3, 1)[..., None] + pv
            return (m_new, l_new, acc_new), None

        ks = jnp.moveaxis(kc, 1, 0)
        vs = jnp.moveaxis(vc, 1, 0)
        (m, l, acc), _ = jax.lax.scan(
            kv_block, (m0, l0, a0), (jnp.arange(nk), ks, vs)
        )
        l_safe = jnp.where(l > 0, l, 1.0)
        out = acc / jnp.moveaxis(l_safe, 3, 1)[..., None]
        return out.astype(q.dtype)

    qs = jnp.moveaxis(qg, 1, 0)  # (nq, B, cq, KV, G, D)
    out = jax.lax.map(lambda args: q_block(*args), (jnp.arange(nq), qs))
    out = jnp.moveaxis(out, 0, 1).reshape(B, Lq, KV, G, D)
    return out.reshape(B, Lq, H, D)[:, :Lq0]


def attention_train(
    p, x: Array, cfg: ModelConfig, rt: Runtime, positions: Array | None = None,
    rope: bool = True,
) -> Array:
    """Causal self-attention over a full sequence (train / prefill)."""
    B, L, _ = x.shape
    if positions is None:
        positions = jnp.arange(L)[None, :]
    q, k, v = _qkv(p, x, cfg, positions, rope=rope)
    q = rt.constrain(q, "batch", None, "heads", "head_dim")
    k = rt.constrain(k, "batch", None, "kv_heads", "head_dim")
    v = rt.constrain(v, "batch", None, "kv_heads", "head_dim")
    if L > cfg.attn_chunk:
        out = chunked_attention(q, k, v, causal=True, chunk=cfg.attn_chunk)
    else:
        out = full_attention(q, k, v, causal=True)
    out = rt.constrain(out, "batch", None, "heads", "head_dim")
    return attn_out(p, out)


def attn_out(p, o: Array) -> Array:
    """The attention output projection of ``o`` (B, L, H, hd), with its
    bias where the tree holds one."""
    return add_bias(jnp.einsum("blhk,hkd->bld", o, p["wo"].astype(o.dtype)),
                    p, "bo")


def dequant_cache_leaf(cache: dict, name: str, dtype) -> Array:
    """Read a cache leaf, dequantizing int8 storage (``<name>_scale``
    per-row f32 sibling, see ``common.kv_scale_defs``) when present."""
    leaf = cache[name]
    scale = cache.get(f"{name}_scale")
    if scale is not None:
        return (leaf.astype(jnp.float32) * scale).astype(dtype)
    return leaf.astype(dtype)


def attention_decode(
    p,
    x: Array,
    cache: dict[str, Array],
    pos: Array,
    cfg: ModelConfig,
    rt: Runtime,
    rope: bool = True,
) -> tuple[Array, dict[str, Array]]:
    """Single-token decode step against a static KV cache.

    x: (B, 1, D); cache: {"k","v": (B, S, KV, hd)}; pos: () int32.

    int8 KV cache (``cfg.kv_quant``, detected from ``k_scale``/``v_scale``
    leaves): storage is int8 with a per-(position, head) f32 scale over the
    head_dim row. The new token's rows update through
    ``common.store_kv_token`` — the one helper that writes the (q, scale)
    pair, shared with the prefill-cache quantization.

    The cache READ is ``cfg.attn_decode``-selected: "fused" (default)
    streams the codes through ``ops.attention_decode`` — the flash-style
    kernel with the dequant folded into the online softmax, no float K/V
    view (DESIGN.md §9); "view" keeps the PR-4 dequantize-whole-cache
    baseline for A/B comparison.
    """
    from repro.models import common

    B, _, _ = x.shape
    positions = jnp.full((B, 1), pos, jnp.int32)
    q, k_new, v_new = _qkv(p, x, cfg, positions, rope=rope)
    new = dict(cache)
    for name, fresh in (("k", k_new), ("v", v_new)):
        new.update(common.store_kv_token(new, name, fresh, pos))
    if cfg.attn_decode == "fused":
        from repro.kernels import ops

        lengths = jnp.full((B,), pos + 1, jnp.int32)
        out = ops.attention_decode(
            q[:, 0], new["k"], new["v"], lengths=lengths,
            k_scale=new.get("k_scale"), v_scale=new.get("v_scale"),
        ).astype(x.dtype)[:, None]  # (B, 1, H, D)
    else:  # "view": dequantize the whole cache, direct softmax
        k = dequant_cache_leaf(new, "k", x.dtype)
        v = dequant_cache_leaf(new, "v", x.dtype)
        S = k.shape[1]
        KV = k.shape[2]
        qg = _group(q, KV)
        scale = q.shape[-1] ** -0.5
        s = jnp.einsum("blkgd,bmkd->bkglm", qg, k).astype(jnp.float32) * scale
        mask = jnp.arange(S)[None, :] <= pos
        s = jnp.where(mask[None, None, None], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1).astype(x.dtype)
        out = jnp.einsum("bkglm,bmkd->blkgd", w, v).reshape(*q.shape)
    return attn_out(p, out), new


#: the decode kernel's name on cross-attention reads, so the device trace
#: tells them from self-attention reads
CROSS_DECODE_KERNEL = "cross_attention_decode_pallas"


def cross_attention_defs(cfg: ModelConfig) -> dict[str, ParamDef]:
    return attention_defs(cfg.replace(qk_norm=False))


def cross_attention(
    p, x: Array, enc_kv: tuple[Array, Array], cfg: ModelConfig, rt: Runtime
) -> Array:
    """Decoder cross-attention; enc_kv = precomputed (k, v) of encoder output."""
    dt = x.dtype
    q = add_bias(jnp.einsum("bld,dhk->blhk", x, p["wq"].astype(dt)), p, "bq")
    k, v = enc_kv
    if x.shape[1] > cfg.attn_chunk:
        out = chunked_attention(q, k, v, causal=False, chunk=cfg.attn_chunk)
    else:
        out = full_attention(q, k, v, causal=False)
    return attn_out(p, out.astype(dt))


def cross_attention_decode(
    p, x: Array, cache: dict[str, Array], cfg: ModelConfig
) -> Array:
    """Single-token decoder cross-attention against the cached (padded,
    possibly int8) encoder K/V. x: (B, 1, D); cache holds ``xk``/``xv``,
    (B, S, KV·hd) as the serving cache stores them or (B, S, KV, hd) as
    prefill emits them (+ per-(row, head) ``_scale`` siblings (B, S, KV,
    1) in int8 mode), and ``enc_len`` — the per-slot REAL encoder length,
    written once at prefill.

    The cross cache is padded past ``enc_len`` with zero rows (zero codes
    AND zero scales in int8 mode); a zero key scores logit 0, not -inf,
    so unmasked padding would leak softmax mass. ``enc_len`` is the
    **ragged per-slot length** set the fused read masks on. A fully-zero
    cache (structural smoke tests, enc_len 0) attends nothing and returns
    0 — the same result as softmax over zero values.
    """
    dt = x.dtype
    q = add_bias(jnp.einsum("bld,dhk->blhk", x, p["wq"].astype(dt)), p, "bq")
    # per-head view of the lane-dense leaves: the kernel wrapper merges
    # the heads back, so the two reshapes cancel and the compiled read
    # takes the stored slice as it is
    B, S = cache["xk"].shape[:2]
    heads = {n: cache[n].reshape(B, S, cfg.num_kv_heads, -1)
             for n in ("xk", "xv")}
    if cfg.attn_decode == "fused":
        from repro.kernels import ops

        # the per-slot encoder length was written into the cache at
        # prefill — no per-step cache scan to recover a static number
        lengths = cache["enc_len"].astype(jnp.int32)
        out = ops.attention_decode(
            q[:, 0], heads["xk"], heads["xv"], lengths=lengths,
            k_scale=cache.get("xk_scale"), v_scale=cache.get("xv_scale"),
            name=CROSS_DECODE_KERNEL,
        ).astype(dt)[:, None]
    else:
        xk = dequant_cache_leaf({**cache, **heads}, "xk", dt)
        xv = dequant_cache_leaf({**cache, **heads}, "xv", dt)
        # same validity definition as the fused path: positions past the
        # prefill-recorded encoder length are padding (an any-nonzero scan
        # heuristic here could diverge from the fused read on a real
        # all-zero K row)
        valid = jnp.arange(S)[None, :] < cache["enc_len"][:, None]
        # enc_len 0 (structural zero cache): attend every (zero) row so the
        # softmax stays finite — output 0, same as the fused path's guard
        valid = valid | ~valid.any(axis=1, keepdims=True)
        out = full_attention(q, xk, xv, causal=False, kv_mask=valid)
    return attn_out(p, out)


def encode_kv(p, enc_out: Array, cfg: ModelConfig) -> tuple[Array, Array]:
    dt = enc_out.dtype
    k = add_bias(jnp.einsum("bld,dhk->blhk", enc_out, p["wk"].astype(dt)),
                 p, "bk")
    v = add_bias(jnp.einsum("bld,dhk->blhk", enc_out, p["wv"].astype(dt)),
                 p, "bv")
    return k, v


# ---------------------------------------------------------------------------
# Embedding / loss
# ---------------------------------------------------------------------------

def embed_defs(cfg: ModelConfig) -> dict[str, ParamDef]:
    defs = {
        "tok": ParamDef(
            (cfg.vocab_size, cfg.d_model), ("vocab", "embed"), init="normal"
        )
    }
    if not cfg.tie_embeddings:
        defs["unembed"] = ParamDef(
            (cfg.d_model, cfg.vocab_size), ("embed", "vocab"), init="normal"
        )
    return defs


def embed_tokens(p, tokens: Array, cfg: ModelConfig) -> Array:
    e = p["tok"].astype(cdtype(cfg))[tokens]
    if cfg.name.startswith("gemma"):
        e = e * jnp.asarray(cfg.d_model ** 0.5, e.dtype)
    return e


def unembed_matrix(p, cfg: ModelConfig) -> Array:
    if cfg.tie_embeddings:
        return p["tok"].T
    return p["unembed"]


def lm_logits(p, h: Array, cfg: ModelConfig) -> Array:
    w = unembed_matrix(p, cfg).astype(h.dtype)
    return jnp.einsum("bld,dv->blv", h, w, preferred_element_type=jnp.float32)


def chunked_ce_loss(
    p_embed, h: Array, labels: Array, cfg: ModelConfig, rt: Runtime
) -> Array:
    """Mean next-token CE, scanning over sequence chunks so full (B, L, V)
    logits never exist. h: (B, L, D); labels: (B, L) (-1 = masked)."""
    B, L, D = h.shape
    c = min(cfg.loss_chunk, L)
    n = L // c
    w = unembed_matrix(p_embed, cfg)
    hc = jnp.moveaxis(h[:, : n * c].reshape(B, n, c, D), 1, 0)
    yc = jnp.moveaxis(labels[:, : n * c].reshape(B, n, c), 1, 0)

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def body(carry, inp):
        # remat: the (B, c, V) logits chunk is recomputed in backward rather
        # than stacked across chunks (matters at 152k/256k vocab).
        tot, cnt = carry
        hb, yb = inp
        logits = jnp.einsum(
            "bld,dv->blv", hb, w.astype(hb.dtype),
            preferred_element_type=jnp.float32,
        )
        logits = rt.constrain(logits, "batch", None, "vocab")
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, jnp.maximum(yb, 0)[..., None], axis=-1
        )[..., 0]
        mask = (yb >= 0).astype(jnp.float32)
        tot = tot + ((logz - gold) * mask).sum()
        cnt = cnt + mask.sum()
        return (tot, cnt), None

    (tot, cnt), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)), (hc, yc)
    )
    return tot / jnp.maximum(cnt, 1.0)
