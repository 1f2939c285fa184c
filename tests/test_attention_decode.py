"""Fused decode attention (`repro.kernels.attention_decode` + ops dispatch).

The contract (DESIGN.md §9): a flash-style single-query attention over the
KV cache whose int8 dequant folds into the online softmax — scores fold
the per-(position, head) K scale AFTER the q·k dot, the V scale folds into
the probability row — so the cache's int8 codes stay resident and no float
K/V view is materialized. The fp-cache variant is the same kernel with the
scale operands absent. Validated here against the dequant-view oracle
across GQA ratios, ragged per-slot lengths (pos 0 / mid / full), bf16
queries, kv-block tilings (incl. non-divisible), and head grouping, in
Pallas interpret mode AND via the compiled blocked-scan CPU path.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import attention_decode as A
from repro.kernels import autotune, ops
from repro.optim.compress import quantize_int8


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def _case(rng, B=2, S=24, KV=2, G=4, D=32, quant=True, qdtype=np.float32):
    q = jnp.asarray(rng.normal(size=(B, KV, G, D)).astype(qdtype))
    k = jnp.asarray(rng.normal(size=(B, S, KV, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, S, KV, D)).astype(np.float32))
    if not quant:
        return q, k, v, None, None
    kq, ks = quantize_int8(k)
    vq, vs = quantize_int8(v)
    return q, kq, vq, ks, vs


def _check(got, want, tol=2e-5):
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=tol, rtol=tol
    )


# -- kernel vs oracle ---------------------------------------------------------

@pytest.mark.parametrize("G", [1, 4, 8])
def test_pallas_int8_matches_oracle_gqa(rng, G):
    q, k, v, ks, vs = _case(rng, G=G)
    lengths = jnp.asarray([5, 24], jnp.int32)
    ref = A.attention_decode_ref(q, k, v, ks, vs, lengths)
    out = A.decode_attention_pallas(
        q, k, v, ks, vs, lengths, block_s=8, interpret=True
    )
    _check(out, ref)


@pytest.mark.parametrize("length", [1, 13, 24])  # pos 0 / mid / full cache
def test_pallas_int8_ragged_lengths(rng, length):
    q, k, v, ks, vs = _case(rng)
    lengths = jnp.asarray([length, 24 - length + 1], jnp.int32)
    ref = A.attention_decode_ref(q, k, v, ks, vs, lengths)
    out = A.decode_attention_pallas(
        q, k, v, ks, vs, lengths, block_s=8, interpret=True
    )
    _check(out, ref)


def test_pallas_fp_cache_same_kernel(rng):
    """The fp-cache variant shares the block structure (no scale rows)."""
    q, k, v, _, _ = _case(rng, quant=False)
    lengths = jnp.asarray([7, 20], jnp.int32)
    ref = A.attention_decode_ref(q, k, v, lengths=lengths)
    out = A.decode_attention_pallas(
        q, k, v, lengths=lengths, block_s=8, interpret=True
    )
    _check(out, ref)


def test_pallas_bf16_query(rng):
    q, k, v, ks, vs = _case(rng)
    ref = A.attention_decode_ref(q, k, v, ks, vs)
    out = A.decode_attention_pallas(
        q.astype(jnp.bfloat16), k, v, ks, vs, block_s=8, interpret=True
    )
    _check(out, ref, tol=2e-2)  # bf16 q: 8-bit mantissa


def test_pallas_nondivisible_block_and_head_grouping(rng):
    """S=24 with block_s=7 (pad + mask) and h_block=KV (grouped heads)."""
    q, k, v, ks, vs = _case(rng)
    lengths = jnp.asarray([24, 11], jnp.int32)
    ref = A.attention_decode_ref(q, k, v, ks, vs, lengths)
    for bs, hb in ((7, 1), (8, 2), (24, 2)):
        out = A.decode_attention_pallas(
            q, k, v, ks, vs, lengths, block_s=bs, h_block=hb,
            interpret=True,
        )
        _check(out, ref)


def test_pallas_zero_length_slot_is_zero(rng):
    """length 0 (whisper cross-attention on an all-padded slot) attends
    nothing: the all-masked guard returns 0, like softmax over zeros."""
    q, k, v, ks, vs = _case(rng)
    out = A.decode_attention_pallas(
        q, k, v, ks, vs, jnp.asarray([0, 9], jnp.int32),
        block_s=8, interpret=True,
    )
    assert float(jnp.abs(out[0]).max()) == 0.0
    assert float(jnp.abs(out[1]).max()) > 0.0


@pytest.mark.parametrize("rows", [1, 16, 32, 33, 200, 1500, 1536])
def test_block_rows_leave_no_remainder_for_any_block(rows):
    """A cache allocated at ``block_rows`` rows divides into whole blocks
    of the default and of every tuned candidate, so ``_pad_seq`` has
    nothing to copy; at most one block of the largest candidate is added,
    and a cache no longer than the smallest block is read as one block as
    it stands."""
    got = A.block_rows(rows)
    if rows <= min(A.BLOCK_S_CANDIDATES):
        assert got == rows
        return
    assert rows <= got < rows + max(A.BLOCK_S_CANDIDATES)
    for block in (A.DEFAULT_BLOCK_S, *A.BLOCK_S_CANDIDATES):
        assert got % block == 0, block


# -- compiled blocked-scan path (the CPU serving evaluation) ------------------

@pytest.mark.parametrize("quant", [True, False])
def test_jax_fast_path_matches_oracle(rng, quant):
    q, k, v, ks, vs = _case(rng, S=40, quant=quant)
    lengths = jnp.asarray([1, 33], jnp.int32)
    ref = A.attention_decode_ref(q, k, v, ks, vs, lengths)
    for bs in (8, 16, 64):  # multi-block, non-divisible, single-block
        out = A.attention_decode_jax(
            q, k, v, ks, vs, lengths, block_s=bs
        )
        _check(out, ref)


def test_jax_fast_path_scale_fold_algebra(rng):
    """(q·k_q)·s_k == q·(k_q·s_k): folding after the dot is exact in f32
    up to reassociation — the fused path must track the view read."""
    q, k, v, ks, vs = _case(rng, S=17)
    fused = A.attention_decode_jax(q, k, v, ks, vs, block_s=4)
    view = A.attention_decode_ref(q, k, v, ks, vs)
    _check(fused, view)


# -- ops dispatch + autotune --------------------------------------------------

def test_ops_dispatch_shapes_and_log(rng):
    B, S, KV, G, D = 2, 24, 2, 4, 32
    q = jnp.asarray(rng.normal(size=(B, KV * G, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, S, KV, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, S, KV, D)).astype(np.float32))
    kq, ks = quantize_int8(k)
    vq, vs = quantize_int8(v)
    lengths = jnp.full((B,), S, jnp.int32)
    out = ops.attention_decode(
        q, kq, vq, lengths=lengths, k_scale=ks, v_scale=vs
    )
    assert out.shape == (B, KV * G, D)
    key = autotune.attn_dec_key(B, S, KV, G, D, "int8")
    assert ops.ATTN_DECODE_DISPATCH.get(key) in ("jax", "pallas")
    ref = A.attention_decode_ref(
        q.reshape(B, KV, G, D), kq, vq, ks, vs, lengths
    ).reshape(B, KV * G, D)
    _check(out, ref)
    # every impl agrees
    for impl in ("jax", "ref", "pallas"):
        got = ops.attention_decode(
            q, kq, vq, lengths=lengths, k_scale=ks, v_scale=vs, impl=impl
        )
        _check(got, ref)


def test_ops_dispatch_requires_scales_for_int8(rng):
    q, k, v, ks, vs = _case(rng)
    with pytest.raises(ValueError, match="k_scale"):
        ops.attention_decode(
            q.reshape(2, -1, 32), k, v,
            lengths=jnp.full((2,), 24, jnp.int32),
        )


def test_autotune_attention_decode_records_key(rng, tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "tune.json"))
    autotune.invalidate()
    B, S, KV, G, D = 1, 32, 2, 2, 32
    q = jnp.asarray(rng.normal(size=(B, KV * G, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, S, KV, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, S, KV, D)).astype(np.float32))
    kq, ks = quantize_int8(k)
    vq, vs = quantize_int8(v)
    r = autotune.autotune_attention_decode(
        q, kq, vq, k_scale=ks, v_scale=vs,
        block_candidates=(8, 16, 32),
    )
    key = autotune.attn_dec_key(B, S, KV, G, D, "int8")
    assert r.key == key
    tuned = autotune.lookup(key)
    assert tuned is not None and tuned["block_s"] in (8, 16, 32)
    assert tuned["h_block"] in (1, KV)
    assert "us" in tuned and "default_us" in tuned
    # dispatch consults the tuned entry (explicit args still win)
    out = ops.attention_decode(
        q, kq, vq, lengths=jnp.full((B,), S, jnp.int32),
        k_scale=ks, v_scale=vs,
    )
    assert out.shape == (B, KV * G, D)
    autotune.invalidate()
