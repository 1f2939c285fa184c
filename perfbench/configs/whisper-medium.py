"""whisper-medium: plain float32 reference of the served model, and its work.

Encoder-decoder over 80-bin log-mels (arXiv:2212.04356; the published
config.json): two k=3 convolutions padded 1, the second at stride 2, each
with a bias and the exact (erf) GELU, so 3000 frames give 1500 positions;
whisper's fixed sinusoids; 24 pre-LayerNorm encoder blocks of
bidirectional self-attention and a GELU MLP of 4096; a final LayerNorm.
The decoder: token embedding plus 448 learned positions, 24 pre-LayerNorm
blocks of causal self-attention, cross-attention over the encoder output
and the MLP, a final LayerNorm and the output head tied to the token
embedding. 16 heads of 64; LayerNorms carry a bias, eps 1e-5; q, v and
output projections and both MLP projections carry biases, k none.

It takes the weights the benchmark made (the program's parameter layout)
and nothing else from the program, and runs one request at a time: its
own audio row encoded, then the decoder teacher-forced over the prompt and
the served tokens. Both controls act in every attention, the encoder's and
the cross-attention among them.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from harness import counts as C
from harness import refmath as R

KV_BYTES, SCALE_BYTES = 1, 4  # int8 cache codes, one f32 scale per row
W_BYTES = 2  # bf16 serving weights and activations


def _serving_dtype(c: dict) -> str:
    return c["assumed"]["serving_dtype"]


def program_fields(c: dict) -> dict:
    """The program's model configuration fields this file fixes."""
    d, h = c["d_model"], c["decoder_attention_heads"]
    return dict(
        d_model=d, num_layers=c["decoder_layers"],
        encoder_layers=c["encoder_layers"], num_heads=h, num_kv_heads=h,
        head_dim=d // h, d_ff=c["decoder_ffn_dim"],
        vocab_size=c["vocab_size"], tie_embeddings=c["tie_word_embeddings"],
        norm_eps=c["layer_norm_eps"],
        max_target_positions=c["max_target_positions"],
        param_dtype=_serving_dtype(c), compute_dtype=_serving_dtype(c),
    )


def frames(c: dict) -> int:
    """Mel frames of one request: a whole 30 s window."""
    return 2 * c["max_source_positions"]


def request_inputs(c: dict, rng, clients: int) -> dict:
    """Each request's own 30 s of mels, uniform in [-1, 1): the range of
    whisper's normalised log-mel."""
    shape = (clients, frames(c), c["num_mel_bins"])
    return {"audio": rng.random(shape, dtype=np.float32) * 2 - 1}


def layer_norm(x, p, eps: float):
    x = x.astype(R.f32)
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + eps) * p["scale"].astype(R.f32)
            + p["bias"].astype(R.f32))


def gelu(x):
    return 0.5 * x * (1 + jax.scipy.special.erf(x / math.sqrt(2)))


def linear(x, w, b, lower):
    """``x`` times ``w``, plus the flat bias ``b`` (None: no bias)."""
    y = R.mm(x, w, lower)
    return y if b is None else y + b.astype(R.f32).reshape(y.shape[1:])


def conv(x, w, b, stride: int, lower):
    """k=3 convolution padded 1 each side: x (T, Cin), w (3, Cin, Cout),
    as one product of the three shifted rows with the flattened taps."""
    t = (x.shape[0] - 1) // stride + 1
    xp = jnp.pad(x.astype(R.f32), ((1, 1), (0, 0)))
    cols = jnp.concatenate(
        [xp[k: k + stride * (t - 1) + 1: stride] for k in range(3)], -1)
    return linear(cols, w, b, lower)


def sinusoids(n: int, d: int):
    """Whisper's encoder positions: sin | cos of p·e^(-i·ln(1e4)/(d/2-1))."""
    inv = jnp.exp(-math.log(10_000.0) / (d // 2 - 1)
                  * jnp.arange(d // 2, dtype=R.f32))
    ang = jnp.arange(n, dtype=R.f32)[:, None] * inv[None]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1)


def attend(a, h, kv_src, *, causal, lower):
    """One attention block's output: queries of ``h``, keys and values of
    ``kv_src`` (itself for self-attention)."""
    q = linear(h, a["wq"], a["bq"], lower)
    k = linear(kv_src, a["wk"], None, lower)
    v = linear(kv_src, a["wv"], a["bv"], lower)
    o = R.attention(q, k, v, causal=causal, lower=lower)
    return linear(o.reshape(o.shape[0], -1), a["wo"], a["bo"], lower)


def mlp(m, h, lower):
    return linear(gelu(linear(h, m["wi"], m["bi"], lower)), m["wo"], m["bo"],
                  lower)


@functools.partial(jax.jit, static_argnames=("n_out", "eps", "lower"))
def _logits(w, audio, tokens, n_out, *, eps, lower=None):
    f = w["frontend"]
    x = gelu(conv(audio, f["conv1_w"], f["conv1_b"], 1, lower))
    x = gelu(conv(x, f["conv2_w"], f["conv2_b"], 2, lower))
    x = x + sinusoids(*x.shape)

    def enc_layer(x, lp):
        h = layer_norm(x, lp["attn_norm"], eps)
        x = x + attend(lp["attn"], h, h, causal=False, lower=lower)
        h = layer_norm(x, lp["mlp_norm"], eps)
        return x + mlp(lp["mlp"], h, lower), None

    x, _ = jax.lax.scan(enc_layer, x, w["encoder"])
    enc = layer_norm(x, w["enc_norm"], eps)

    tok = w["embed"]["tok"]
    y = tok[tokens].astype(R.f32) + w["dec_pos"][: tokens.shape[0]].astype(
        R.f32)

    def dec_layer(y, lp):
        h = layer_norm(y, lp["attn_norm"], eps)
        y = y + attend(lp["attn"], h, h, causal=True, lower=lower)
        h = layer_norm(y, lp["xattn_norm"], eps)
        y = y + attend(lp["xattn"], h, enc, causal=False, lower=lower)
        h = layer_norm(y, lp["mlp_norm"], eps)
        return y + mlp(lp["mlp"], h, lower), None

    y, _ = jax.lax.scan(dec_layer, y, w["decoder"])
    h = layer_norm(y[-n_out:], w["final_norm"], eps)
    return R.mm(h, tok.T, lower)


def logits(w, c: dict, extra, prompt, served, *, lower: str | None = None):
    """Reference logits (len(served), vocab) at the positions that chose
    each served token: the request's own ``extra["audio"]`` encoded, the
    decoder teacher-forced over ``prompt`` + ``served[:-1]``; with
    ``lower``, one of the controls (``refmath.CONTROLS``)."""
    tokens = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    out = _logits(w, jnp.asarray(extra["audio"]), jnp.asarray(tokens),
                  len(served), eps=float(c["layer_norm_eps"]), lower=lower)
    return np.asarray(out)


def conv_work(c: dict, batch: int) -> list[tuple[int, int]]:
    """(operations, bytes) of each frontend convolution over one batch:
    bf16 input, taps, bias and output each moved once."""
    t, m, d = frames(c), c["num_mel_bins"], c["d_model"]
    s = c["max_source_positions"]
    return [
        (C.proj_flops(batch * t, 3 * m, d),
         W_BYTES * (batch * t * m + 3 * m * d + d + batch * t * d)),
        (C.proj_flops(batch * s, 3 * d, d),
         W_BYTES * (batch * t * d + 3 * d * d + d + batch * s * d)),
    ]


def work(c: dict, batch: int, prompt: int, gen: int) -> dict:
    """Operations and bytes of one batch, counted from the shapes."""
    d, H = c["d_model"], c["decoder_attention_heads"]
    hd, ff, V = d // H, c["decoder_ffn_dim"], c["vocab_size"]
    Le, Ld, S = c["encoder_layers"], c["decoder_layers"], \
        c["max_source_positions"]
    encoder = (sum(f for f, _ in conv_work(c, 1))
               + Le * (C.attn_proj_flops(S, d, H, H, hd)
                       + C.attn_core_flops(S * S, H, hd)
                       + C.mlp_flops(S, d, ff, False))
               + Ld * 2 * C.proj_flops(S, d, d))  # each layer's cross K/V

    def decoder(tokens: int, self_pairs: int) -> int:
        """The decoder over ``tokens`` new positions."""
        return Ld * (C.attn_proj_flops(tokens, d, H, H, hd)
                     + C.attn_core_flops(self_pairs, H, hd)
                     + 2 * C.proj_flops(tokens, d, d)  # cross q, out
                     + C.attn_core_flops(tokens * S, H, hd)
                     + C.mlp_flops(tokens, d, ff, False))

    prefill = batch * (encoder + decoder(prompt, C.causal_pairs(prompt))
                       + C.proj_flops(1, d, V))
    row = C.kv_row_bytes(hd, KV_BYTES, SCALE_BYTES)
    # what a decode step reads of the weights: per layer the self-attention
    # and the cross q/out projections, the MLP, their biases and three
    # LayerNorms (the cross K/V projections ran in the encoder program);
    # the tied embedding as the head, one position row and the final norm
    layer = 6 * d * d + 2 * d * ff + (5 * d + ff + d) + 3 * 2 * d
    weights = W_BYTES * (Ld * layer + V * d + d + 2 * d)
    cross_read = Ld * batch * H * row * 2 * S
    qo = Ld * batch * 2 * H * hd * 4  # q in, out, f32
    steps, self_flops, self_bytes = [], 0, 0
    for i in range(gen - 1):
        live = prompt + i + 1
        f = batch * (decoder(1, live) + C.proj_flops(1, d, V))
        self_read = Ld * batch * H * row * 2 * live
        b = (weights + batch * d * W_BYTES + self_read + cross_read
             + Ld * batch * H * row * 2)
        steps.append((f, b))
        self_flops += batch * Ld * C.attn_core_flops(live, H, hd)
        self_bytes += self_read + qo
    n = gen - 1
    return {
        "prefill_flops": prefill,
        "decode_steps": steps,
        "kernels": {
            "sliding_conv1d": conv_work(c, batch),
            "cross_attention_decode": (
                n * batch * Ld * C.attn_core_flops(S, H, hd),
                n * (cross_read + qo)),
            "attention_decode": (self_flops, self_bytes),
        },
    }
