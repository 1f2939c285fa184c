"""Pallas TPU kernel: fused single-query decode attention over the KV cache.

The decode hot loop reads the entire static KV cache every step. With the
int8 cache (DESIGN.md §8) the PR-4 path dequantized the whole cache to a
float *view* first — f32-sized HBM traffic plus a cache-sized intermediate,
exactly the materialize-then-reduce shape the paper's sliding kernels
exist to avoid. This kernel fuses the dequant into a flash-style online
softmax over kv_seq blocks (Dao et al., 2022) and keeps the int8 codes
resident (Dettmers et al., 2022):

  * scores fold the per-(position, head) K scale AFTER the q·k dot —
    ``q·(k_q·s_k) == (q·k_q)·s_k`` because ``s_k`` is constant along the
    head_dim reduction — so the MXU consumes int8 codes directly;
  * the V scale folds into the probability row before the p·v dot —
    ``p·(v_q·s_v) == (p·s_v)·v_q`` for the same reason;
  * masking is ragged per slot: ``lengths[b]`` valid cache rows (decode:
    ``pos + 1`` broadcast; whisper cross-attention: per-slot encoder
    lengths), applied blockwise inside the online softmax.

No float K/V view is ever materialized: per grid step one ``(block_s,
h_block·D)`` cache block lives in VMEM, the f32 running state is
``(h_block, G)`` + a ``(h_block, G, D)`` accumulator in scratch.

The **fp-cache variant is the same kernel** with the scale operands absent
— both paths share the grid/block structure, so the fused path serves
``kv_quant ∈ {fp, int8}`` uniformly (acceptance: identical greedy tokens).

GQA is handled by the grouped query layout ``(B, KV, G, D)``: each grid
step attends one (batch, kv-head-block) pair, broadcasting the K/V block
over the ``G`` grouped queries — no KV head repetition in memory.

``attention_decode_jax`` is the compiled pure-JAX evaluation of the SAME
blocked algorithm (``lax.scan`` over kv blocks, identical scale-fold
algebra) — the serving path on CPU, where interpret-mode Pallas would be
Python-speed. ``attention_decode_ref`` is the obviously-correct dequant-
view oracle the other two are tested against. Dispatch between them lives
in ``repro.kernels.ops.attention_decode``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_S = 128
# kv-block candidates the autotuner searches (``autotune_attention_decode``)
BLOCK_S_CANDIDATES = (32, 64, 128, 256, 512)


def _pad_seq(a: jax.Array | None, to: int) -> jax.Array | None:
    """Zero-pad axis 1 (kv_seq) up to ``to`` rows. Zero codes AND zero
    scales on the pad — masked out by ``lengths`` anyway."""
    if a is None or a.shape[1] >= to:
        return a
    pads = [(0, 0)] * a.ndim
    pads[1] = (0, to - a.shape[1])
    return jnp.pad(a, pads)


def _softmax_step(s, m_prev, l_prev, *, axis):
    """THE online-softmax update (one copy for the kernel, the blocked
    scan, the single-block pass, and the oracle — they must never diverge
    on edge inputs): new running max, masked probabilities, carry
    correction, new denominator, reducing scores over ``axis``. Guards
    fully-masked blocks: all -inf scores leave the carry untouched when it
    holds data (corr 1, p 0) and contribute nothing when it doesn't
    (m_prev -inf → corr 0)."""
    m_new = jnp.maximum(m_prev, s.max(axis=axis))
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - jnp.expand_dims(m_safe, axis))
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    corr = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
    return m_new, p, corr, l_prev * corr + p.sum(axis=axis)


def _online_update(s, p_scale, v, m_prev, l_prev, acc_prev):
    """One flash step in the kernel body: fold ``p_scale`` (per-position V
    scale row, or None) into the probability row, then accumulate p·v."""
    m_new, p, corr, l_new = _softmax_step(s, m_prev, l_prev, axis=-1)
    pw = p if p_scale is None else p * p_scale
    pv = jnp.dot(pw, v, preferred_element_type=jnp.float32)
    acc_new = acc_prev * corr[..., None] + pv
    return m_new, l_new, acc_new


def _finish(l, acc):
    """acc / l with the all-masked guard: l == 0 (no valid row — e.g. a
    zero-length cross-attention slot) yields 0, matching softmax-over-
    zero-values in the unfused paths."""
    l_safe = jnp.where(l > 0, l, 1.0)
    return acc / l_safe[..., None]


def _decode_kernel(
    len_ref, q_ref, k_ref, v_ref, *rest, bs, hb, d, n_s, quantized, sm_scale
):
    """Grid (B, KV/hb, n_s); the kv_seq dim (last, sequential) revisits one
    (batch, head-block) output with the online-softmax state in scratch.
    ``len_ref`` is the scalar-prefetched (B,) lengths vector in SMEM."""
    if quantized:
        ks_ref, vs_ref = rest[0], rest[1]
        rest = rest[2:]
    o_ref, m_ref, l_ref, acc_ref = rest
    h_idx = pl.program_id(1)
    s_idx = pl.program_id(2)
    q = q_ref[0].astype(jnp.float32)  # (hb, G, D)
    kblk = k_ref[0]  # (bs, hb·D) — int8 codes or float rows
    vblk = v_ref[0]
    length = len_ref[pl.program_id(0)]
    pos = s_idx * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    valid = pos < length  # (1, bs)

    @pl.when(s_idx == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    for i in range(hb):  # static head-block loop: one 2-D dot per head
        ki = kblk[:, i * d : (i + 1) * d].astype(jnp.float32)
        s = jax.lax.dot_general(  # q·kᵀ without a transpose: (G, bs)
            q[i], ki, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        s = s * sm_scale
        head = h_idx * hb + i
        if quantized:
            # scale-fold algebra: s_k is constant along head_dim, so it
            # commutes out of the q·k reduction — fold it AFTER the dot
            s = s * ks_ref[0, pl.ds(head, 1), :]
        s = jnp.where(valid, s, -jnp.inf)
        vs_row = vs_ref[0, pl.ds(head, 1), :] if quantized else None
        m_new, l_new, acc_new = _online_update(
            s, vs_row, vblk[:, i * d : (i + 1) * d].astype(jnp.float32),
            m_ref[i], l_ref[i], acc_ref[i],
        )
        m_ref[i], l_ref[i], acc_ref[i] = m_new, l_new, acc_new

    @pl.when(s_idx == n_s - 1)
    def _done():
        o_ref[0] = _finish(l_ref[...], acc_ref[...]).astype(o_ref.dtype)


def head_block(h_block: int | None, KV: int, D: int) -> int:
    """KV heads per grid step. A K/V block is ``hb·D`` lanes wide, which
    the TPU tiles in 128s unless it spans all ``KV·D``: an ``h_block``
    that does not divide KV or breaks that tiling (and None) means all KV
    heads."""
    if h_block and KV % h_block == 0 and (h_block * D) % 128 == 0:
        return h_block
    return KV


@functools.partial(
    jax.jit, static_argnames=("block_s", "h_block", "interpret")
)
def decode_attention_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    lengths: jax.Array | None = None,
    *,
    block_s: int = DEFAULT_BLOCK_S,
    h_block: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Fused decode attention. q: (B, KV, G, D) grouped queries (any float
    dtype); k/v: (B, S, KV, D) cache leaves — int8 codes WITH their
    per-(position, head) f32 ``k_scale``/``v_scale`` rows (B, S, KV, 1), or
    float rows without; lengths: (B,) int32 valid-prefix per slot (None →
    all S rows valid). Returns (B, KV, G, D) f32.

    ``block_s`` tiles kv_seq (the reduction grid dim); ``h_block`` groups
    KV heads per grid step (``head_block``). Both are tuned under the
    ``attn_dec|…`` autotune key.

    Layout: k/v travel as (B, S, KV·D) — a free reshape — so each grid
    step reads one (block_s, hb·D) tile whose last two dims meet the
    TPU's (8, 128) tiling; the scale rows travel as (B, KV, S) so a head's
    row is a (1, block_s) slice; ``lengths`` is scalar-prefetched to SMEM.
    """
    B, KV, G, D = q.shape
    S = k.shape[1]
    quantized = k.dtype == jnp.int8
    if quantized and (k_scale is None or v_scale is None):
        raise ValueError("int8 K/V codes need their k_scale/v_scale rows")
    if lengths is None:
        lengths = jnp.full((B,), S, jnp.int32)
    bs = min(block_s, S)
    n_s = pl.cdiv(S, bs)
    Sp = n_s * bs
    k = _pad_seq(k, Sp).reshape(B, Sp, KV * D)
    v = _pad_seq(v, Sp).reshape(B, Sp, KV * D)
    hb = head_block(h_block, KV, D)
    n_h = KV // hb
    kernel = functools.partial(
        _decode_kernel, bs=bs, hb=hb, d=D, n_s=n_s, quantized=quantized,
        sm_scale=D ** -0.5,
    )
    in_specs = [
        pl.BlockSpec((1, hb, G, D), lambda b, h, s, lens: (b, h, 0, 0)),
        pl.BlockSpec((1, bs, hb * D), lambda b, h, s, lens: (b, s, h)),
        pl.BlockSpec((1, bs, hb * D), lambda b, h, s, lens: (b, s, h)),
    ]
    args = [q, k, v]
    if quantized:
        # scale rows travel as (B, KV, Sp) — head_dim collapsed, kv_seq on
        # the lanes — and every step sees all KV heads' rows of its block
        ks3 = _pad_seq(k_scale, Sp)[..., 0].astype(jnp.float32)
        vs3 = _pad_seq(v_scale, Sp)[..., 0].astype(jnp.float32)
        in_specs += [
            pl.BlockSpec((1, KV, bs), lambda b, h, s, lens: (b, 0, s)),
            pl.BlockSpec((1, KV, bs), lambda b, h, s, lens: (b, 0, s)),
        ]
        args += [ks3.transpose(0, 2, 1), vs3.transpose(0, 2, 1)]
    return pl.pallas_call(
        kernel,
        name="decode_attention_pallas",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, n_h, n_s),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (1, hb, G, D), lambda b, h, s, lens: (b, h, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((hb, G), jnp.float32),  # running max
                pltpu.VMEM((hb, G), jnp.float32),  # running denominator
                pltpu.VMEM((hb, G, D), jnp.float32),  # output accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, D), jnp.float32),
        interpret=interpret,
    )(lengths.astype(jnp.int32), *args)


# ---------------------------------------------------------------------------
# pure-JAX evaluations
# ---------------------------------------------------------------------------

def _block_pass(qf, kc, ksc, valid, sm):
    """One kv block in the codes-resident CPU formulation: the score pass
    is a broadcast multiply-reduce over the **contiguous** head_dim axis in
    the cache's own (B, s, KV, D) layout — XLA fuses the int8→f32 convert,
    the q multiply, and the d-reduction into a single pass over the codes,
    so no f32 copy of the block's K ever exists (a GEMM here forces a
    convert+transpose materialization instead; measured 1.3–1.65× slower
    at the serving shapes). G is small in decode (≤ heads), so the extra
    broadcast FLOPs are noise. The p·v pass keeps the GEMM — its reduction
    runs over kv_seq, which is strided in this layout, exactly where the
    broadcast form loses locality.

    Returns (s_masked (B, s, KV, G), pw_row maker) pieces: the caller owns
    the online-softmax state."""
    s = jnp.sum(
        qf[:, None] * kc[:, :, :, None, :].astype(jnp.float32), axis=-1
    )  # (B, s, KV, G)
    if ksc is not None:
        s = s * (ksc * sm)  # (B, s, KV, 1) row scale folds AFTER the dot
    else:
        s = s * sm
    return jnp.where(valid[:, :, None, None], s, -jnp.inf)


def _block_pv(p, vsc, vc):
    """p·(v_q·s_v) as (p·s_v)·v_q: fold the V scale into the probability
    row, then one GEMM against the int8 codes."""
    pw = p if vsc is None else p * vsc
    pw = pw.transpose(0, 2, 3, 1)  # (B, KV, G, s) — small
    return jnp.einsum(
        "bkgs,bskd->bkgd", pw, vc.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )


@functools.partial(jax.jit, static_argnames=("block_s",))
def attention_decode_jax(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    lengths: jax.Array | None = None,
    *,
    block_s: int = DEFAULT_BLOCK_S,
) -> jax.Array:
    """Compiled pure-JAX fused path — the CPU serving evaluation. Same
    blocked online-softmax structure and scale-fold algebra as the Pallas
    kernel (``lax.scan`` over kv_seq blocks), with the score pass written
    so XLA keeps the int8 codes resident (see ``_block_pass``). Only
    block-sized f32 intermediates exist. Shapes as
    :func:`decode_attention_pallas`; returns (B, KV, G, D) f32.
    """
    B, KV, G, D = q.shape
    S = k.shape[1]
    quantized = k_scale is not None
    if lengths is None:
        lengths = jnp.full((B,), S, jnp.int32)
    bs = min(block_s, S)
    n_s = pl.cdiv(S, bs)
    Sp = n_s * bs
    qf = q.astype(jnp.float32)
    sm = D ** -0.5

    def blocks(a):  # (B, Sp, KV, ...) -> (n_s, B, bs, KV, ...)
        a = _pad_seq(a, Sp)
        return jnp.moveaxis(
            a.reshape(B, n_s, bs, *a.shape[2:]), 1, 0
        )

    m0 = jnp.full((B, KV, G), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, KV, G), jnp.float32)

    if n_s == 1:
        # single-block shapes (short caches): one pass, no scan carry —
        # cheaper to compile inside the decode jit and the CPU default
        valid = jnp.arange(S)[None, :] < lengths[:, None]
        s = _block_pass(qf, k, k_scale if quantized else None, valid, sm)
        _m, p, _corr, l = _softmax_step(s, m0, l0, axis=1)
        pv = _block_pv(p, v_scale if quantized else None, v)
        return _finish(l, pv)

    kb, vb = blocks(k), blocks(v)
    xs = (jnp.arange(n_s), kb, vb)
    if quantized:
        xs += (blocks(k_scale), blocks(v_scale))

    def step(carry, inp):
        m, l, acc = carry  # (B, KV, G)[, D]
        if quantized:
            i, kc, vc, ksc, vsc = inp
        else:
            i, kc, vc = inp
            ksc = vsc = None
        pos = i * bs + jnp.arange(bs)
        valid = pos[None, :] < lengths[:, None]  # (B, bs)
        s = _block_pass(qf, kc, ksc, valid, sm)
        m_new, p, corr, l_new = _softmax_step(s, m, l, axis=1)
        pv = _block_pv(p, vsc, vc)
        acc_new = acc * corr[..., None] + pv
        return (m_new, l_new, acc_new), None

    a0 = jnp.zeros((B, KV, G, D), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), xs)
    return _finish(l, acc)


def attention_decode_ref(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    lengths: jax.Array | None = None,
) -> jax.Array:
    """Dequant-view oracle: materialize float K/V, one full softmax — the
    obviously-correct reference the fused paths are validated against
    (and the ``impl="ref"`` dispatch fallback)."""
    B, KV, G, D = q.shape
    S = k.shape[1]
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale/v_scale travel as a pair")
    if lengths is None:
        lengths = jnp.full((B,), S, jnp.int32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    if k_scale is not None:
        kf = kf * k_scale
        vf = vf * v_scale
    s = jnp.einsum("bkgd,bskd->bkgs", q.astype(jnp.float32), kf)
    s = s * D ** -0.5
    valid = jnp.arange(S)[None, :] < lengths[:, None]
    s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
    m0 = jnp.full((B, KV, G), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, KV, G), jnp.float32)
    _m, p, _corr, l = _softmax_step(s, m0, l0, axis=-1)
    return _finish(l, jnp.einsum("bkgs,bskd->bkgd", p, vf))
