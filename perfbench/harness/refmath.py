"""Plain float32 building blocks for the configurations' references.

Straightforward ``jax.numpy`` at ``Precision.HIGHEST``: no kernels, no
cache, no batching. Weights arrive in the dtype they are served in and
are upcast here.

``lower`` names one of the output check's controls: one stated precision
of the served model taken one step down, the other kept. The
configurations serve bf16 weights and activations over an int8 K/V cache,
so ``"int8_weights"`` runs every weight product in int8 (weights per
output channel, activations per row) and ``"int4_kv"`` has attention read
its keys and values rounded to int4 per (position, head) row, both
symmetric absmax.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
f32 = jnp.float32


INT8, INT4 = 127.0, 7.0
#: the controls, each one precision step down
CONTROLS = ("int8_weights", "int4_kv")


def qgrid(a, axis, levels: float):
    """Round ``a`` to a symmetric grid of ``levels`` steps each side of 0
    along ``axis`` (absmax scale)."""
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / levels
    s = jnp.where(s > 0, s, 1.0)
    return jnp.round(a / s) * s


def mm(x, w, lower: str | None = None):
    """``x`` (..., din) times ``w`` (din..., dout...) flattened to 2-D;
    the result keeps ``w``'s trailing output axes."""
    x = x.astype(f32)
    din = x.shape[-1]
    n_in = 1
    for i, n in enumerate(w.shape):
        n_in *= n
        if n_in == din:
            out_shape = w.shape[i + 1:]
            break
    else:
        raise ValueError(f"{w.shape} does not take {din} inputs")
    w2 = w.astype(f32).reshape(din, -1)
    if lower == "int8_weights":
        x, w2 = qgrid(x, -1, INT8), qgrid(w2, 0, INT8)
    return jnp.matmul(x, w2, precision=HIGHEST).reshape(
        *x.shape[:-1], *out_shape)


def rms_norm(x, g, eps: float):
    x = x.astype(f32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(f32)


def gelu_tanh(x):
    return 0.5 * x * (1 + jnp.tanh(0.7978845608028654 * (x + 0.044715 * x ** 3)))


def silu(x):
    return x / (1 + jnp.exp(-x))


def sinusoid(n: int, d: int):
    """Position p, channel pair i: sin and cos of p / 10000^(2i/d)."""
    pos = jnp.arange(n, dtype=f32)[:, None]
    ang = pos / jnp.power(10_000.0, jnp.arange(0, d, 2, dtype=f32) / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1)


def rope(x, positions, theta: float):
    """Rotate-half rotary embedding. x: (L, H, D); positions: (L,)."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=f32) / d)
    ang = positions.astype(f32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, *, causal: bool, lower: str | None = None,
              block: int = 512):
    """Softmax attention, one sequence. q: (Lq, H, D); k, v: (Lk, KV, D),
    H a multiple of KV (grouped queries share their K/V head). Causal
    masks key j > query i, the queries being the last Lq positions of the
    Lk. Computed in query blocks of ``block`` rows to bound memory."""
    if lower == "int4_kv":
        k, v = qgrid(k, -1, INT4), qgrid(v, -1, INT4)
    lq, h, d = q.shape
    lk, kv = k.shape[:2]
    g = h // kv
    qg = q.reshape(lq, kv, g, d)
    outs = []
    for s in range(0, lq, block):
        qb = qg[s:s + block]
        sc = jnp.einsum("qkgd,mkd->kgqm", qb, k, precision=HIGHEST) * d ** -0.5
        if causal:
            qpos = lk - lq + s + jnp.arange(qb.shape[0])
            sc = jnp.where(qpos[:, None] >= jnp.arange(lk)[None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(jnp.einsum("kgqm,mkd->qkgd", p, v, precision=HIGHEST))
    return jnp.concatenate(outs, 0).reshape(lq, h, d)
