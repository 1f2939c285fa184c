"""qwen3-1.7b: plain float32 reference of the served model, and its work.

Dense decoder: 28 layers, hidden 2048, 16 query heads over 8 K/V heads of
128, RMSNorm on each head's queries and keys before rotary embedding
(theta 1e6), SwiGLU MLP of 6144, a 151936-token vocabulary whose
embedding is also the output head. It takes the weights the benchmark
made (the program's parameter layout) and nothing else from the program,
and runs one request at a time, teacher-forced over the prompt and the
served tokens.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from harness import counts as C
from harness import refmath as R

KV_BYTES, SCALE_BYTES = 1, 4  # int8 cache codes, one f32 scale per row


def program_fields(c: dict) -> dict:
    """The program's model configuration fields this file fixes."""
    return dict(
        d_model=c["hidden_size"], num_layers=c["num_hidden_layers"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        tie_embeddings=c["tie_word_embeddings"], qk_norm=True,
        rope_theta=float(c["rope_theta"]), norm_eps=c["rms_norm_eps"],
        param_dtype=c["torch_dtype"], compute_dtype=c["torch_dtype"],
    )


def request_inputs(c: dict, rng, clients: int) -> dict:
    """Each request's inputs besides its prompt tokens: none."""
    return {}


@functools.partial(jax.jit,
                   static_argnames=("n_out", "eps", "theta", "lower"))
def _logits(w, tokens, n_out, *, eps, theta, lower=None):
    pos = jnp.arange(tokens.shape[0])
    x = w["embed"]["tok"][tokens].astype(R.f32)

    def layer(x, lp):
        a = lp["attn"]
        h = R.rms_norm(x, lp["attn_norm"], eps)
        q = R.rms_norm(R.mm(h, a["wq"], lower), a["q_norm"], eps)
        k = R.rms_norm(R.mm(h, a["wk"], lower), a["k_norm"], eps)
        v = R.mm(h, a["wv"], lower)
        o = R.attention(R.rope(q, pos, theta), R.rope(k, pos, theta), v,
                        causal=True, lower=lower)
        x = x + R.mm(o.reshape(o.shape[0], -1), a["wo"], lower)
        h = R.rms_norm(x, lp["mlp_norm"], eps)
        m = lp["mlp"]
        g = R.silu(R.mm(h, m["wg"], lower)) * R.mm(h, m["wu"], lower)
        return x + R.mm(g, m["wd"], lower), None

    x, _ = jax.lax.scan(layer, x, w["blocks"])
    h = R.rms_norm(x[-n_out:], w["final_norm"], eps)
    return R.mm(h, w["embed"]["tok"].T, lower)


def logits(w, c: dict, extra, prompt, served, *, lower: str | None = None):
    """Reference logits (len(served), vocab) at the positions that chose
    each served token: teacher-forced over ``prompt`` + ``served[:-1]``;
    with ``lower``, one of the controls (``refmath.CONTROLS``)."""
    tokens = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    out = _logits(w, jnp.asarray(tokens), len(served),
                  eps=float(c["rms_norm_eps"]), theta=float(c["rope_theta"]),
                  lower=lower)
    return np.asarray(out)


def work(c: dict, batch: int, prompt: int, gen: int) -> dict:
    """Operations and bytes of one batch, counted from the shapes."""
    d, L = c["hidden_size"], c["num_hidden_layers"]
    H, KV, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    ff, V = c["intermediate_size"], c["vocab_size"]
    prefill = batch * (L * (C.attn_proj_flops(prompt, d, H, KV, hd)
                            + C.attn_core_flops(C.causal_pairs(prompt), H, hd)
                            + C.mlp_flops(prompt, d, ff, True))
                       + C.proj_flops(1, d, V))
    row = C.kv_row_bytes(hd, KV_BYTES, SCALE_BYTES)
    weights = 2 * (L * (2 * d * (H + KV) * hd + 3 * d * ff + 2 * d + 2 * hd)
                   + V * d + d)
    steps, attn_flops, attn_bytes = [], 0, 0
    for i in range(gen - 1):
        live = prompt + i + 1
        f = batch * (L * (C.attn_proj_flops(1, d, H, KV, hd)
                          + C.attn_core_flops(live, H, hd)
                          + C.mlp_flops(1, d, ff, True))
                     + C.proj_flops(1, d, V))
        kv_read = L * batch * KV * row * 2 * live
        qo = L * batch * 2 * H * hd * 4  # q in, out, f32
        b = weights + batch * d * 2 + kv_read + L * batch * KV * row * 2
        steps.append((f, b))
        attn_flops += batch * L * C.attn_core_flops(live, H, hd)
        attn_bytes += kv_read + qo
    return {
        "prefill_flops": prefill,
        "decode_steps": steps,
        "kernels": {"attention_decode": (attn_flops, attn_bytes)},
    }
