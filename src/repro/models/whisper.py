"""Whisper (arXiv:2212.04356; huggingface.co/openai/whisper-medium): an
encoder-decoder transformer over 80-bin log-mel frames.

Encoder: the conv frontend — conv1d k=3, then conv1d k=3 at stride 2, each
padded 1, with a bias and the exact (erf) GELU, through the paper's
sliding conv path (``cfg.conv_backend``) — so 3000 mel frames (30 s) give
1500 positions; whisper's fixed sinusoids; pre-LayerNorm blocks of
bidirectional self-attention and a GELU MLP; a final LayerNorm.

Decoder: the token embedding plus learned positions
(``cfg.max_target_positions`` of them), pre-LayerNorm blocks of causal
self-attention, cross-attention over the encoder output and a GELU MLP, a
final LayerNorm, and an output head tied to the token embedding.

Every LayerNorm has a bias (eps ``cfg.norm_eps``). The q, v and output
projections of each attention and both MLP projections have biases; the
k projections have none. Biases are flat vectors (``layers.add_bias``).

Serving (``launch/serve.py``) runs :meth:`Whisper.encode_cross` — the
frontend, the encoder and each decoder layer's cross K/V — as a program of
its own, once per batch, then :meth:`Whisper.prefill` over the decoder prompt
against those cross K/V (``launch/steps.make_prefill_step`` chains the
two for callers that want one step), then :meth:`Whisper.decode_step`,
which reads them from the cache and returns only the self-attention
leaves it wrote. The decoder prompt's length does not
depend on the audio's. Training (``loss``) takes mels, or precomputed
frame embeddings (B, S, d_model) in the frontend's place
(``train --audio-frontend stub``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import ParamDef, Runtime, abstract_params, init_params
from repro.models import layers as L
from repro.models.common import kv_cache_defs, scan_blocks, stack_defs

Array = jax.Array

N_MELS = 80
#: encoder positions of a 30 s window: 3000 mel frames after the stride-2 conv
N_AUDIO_CTX = 1500
#: the cache leaves a decode step writes: the decoder's self-attention K/V
SELF_LEAVES = ("k", "v", "k_scale", "v_scale")


def sinusoids(length: int, channels: int) -> Array:
    """Whisper's encoder positions: position p, channel i < channels/2
    gets sin(p·e^(-i·ln(10000)/(channels/2 - 1))), the second half the
    cos of the same angles."""
    inc = math.log(10_000.0) / (channels // 2 - 1)
    inv = jnp.exp(-inc * jnp.arange(channels // 2, dtype=jnp.float32))
    ang = jnp.arange(length, dtype=jnp.float32)[:, None] * inv[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def frontend_defs(cfg: ModelConfig) -> dict[str, ParamDef]:
    d = cfg.d_model
    return {
        "conv1_w": ParamDef((3, N_MELS, d), (None, None, "embed"), init="fan_in"),
        "conv1_b": ParamDef((d,), ("embed",), init="zeros"),
        "conv2_w": ParamDef((3, d, d), (None, "embed", "embed"), init="fan_in"),
        "conv2_b": ParamDef((d,), ("embed",), init="zeros"),
    }


def conv_frontend(p, mels: Array, cfg: ModelConfig) -> Array:
    """mels: (B, T, 80) -> (B, T//2, d_model). Sliding conv, custom k=3.

    conv→bias→gelu is one fused kernel launch on the Pallas path
    (``conv_backend="sliding_pallas"``). With ``cfg.conv_precision`` set
    (and int8 weights swapped in by ``repro.quant.apply``) the convs run
    the quantized kernels; the site names here key the calibration spec.
    When the calibration spec chained conv1→conv2 (``quant.apply.CHAINS``),
    conv1's leaf carries ``out_scale`` = conv2's input scale: conv1
    requantizes in its epilogue and hands conv2 int8 activations directly —
    no f32 tensor is materialized between the two convs (DESIGN.md §8)."""
    precision = cfg.conv_precision
    x = L.conv1d_bias_act(
        mels, p["conv1_w"], p["conv1_b"],
        activation="gelu_erf", padding="SAME", backend=cfg.conv_backend,
        precision=precision, site="whisper/conv1",
    )
    x = L.conv1d_bias_act(
        x, p["conv2_w"], p["conv2_b"],
        activation="gelu_erf", stride=2, padding="SAME",
        backend=cfg.conv_backend, precision=precision, site="whisper/conv2",
    )
    return x


def _norm_defs(d: int) -> dict[str, ParamDef]:
    return {"scale": ParamDef((d,), ("embed",), init="ones"),
            "bias": ParamDef((d,), ("embed",), init="zeros")}


def _norm(x: Array, p, cfg: ModelConfig) -> Array:
    return L.layer_norm(x, p["scale"], p["bias"], cfg.norm_eps)


def _attn_defs(cfg: ModelConfig) -> dict[str, ParamDef]:
    """Attention projections with whisper's biases: q, v and out, no k."""
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    defs = L.cross_attention_defs(cfg)
    defs["bq"] = ParamDef((h * hd,), (None,), init="zeros")
    defs["bv"] = ParamDef((kv * hd,), (None,), init="zeros")
    defs["bo"] = ParamDef((cfg.d_model,), ("embed",), init="zeros")
    return defs


def _mlp_defs(cfg: ModelConfig) -> dict[str, ParamDef]:
    defs = L.mlp_defs(cfg)
    defs["bi"] = ParamDef((cfg.d_ff,), ("mlp",), init="zeros")
    defs["bo"] = ParamDef((cfg.d_model,), ("embed",), init="zeros")
    return defs


def _attention(q, k, v, cfg: ModelConfig, *, causal: bool) -> Array:
    if k.shape[1] > cfg.attn_chunk:
        return L.chunked_attention(q, k, v, causal=causal,
                                   chunk=cfg.attn_chunk)
    return L.full_attention(q, k, v, causal=causal)


class Whisper:
    def __init__(self, cfg: ModelConfig, rt: Runtime | None = None):
        self.cfg = cfg
        self.rt = rt or Runtime()

    # -- parameters -----------------------------------------------------------
    def _enc_block_defs(self):
        cfg = self.cfg
        return {
            "attn_norm": _norm_defs(cfg.d_model),
            "attn": _attn_defs(cfg),
            "mlp_norm": _norm_defs(cfg.d_model),
            "mlp": _mlp_defs(cfg),
        }

    def _dec_block_defs(self):
        cfg = self.cfg
        return {
            "attn_norm": _norm_defs(cfg.d_model),
            "attn": _attn_defs(cfg),
            "xattn_norm": _norm_defs(cfg.d_model),
            "xattn": _attn_defs(cfg),
            "mlp_norm": _norm_defs(cfg.d_model),
            "mlp": _mlp_defs(cfg),
        }

    def param_defs(self):
        cfg = self.cfg
        return {
            "embed": L.embed_defs(cfg),
            "dec_pos": ParamDef((cfg.max_target_positions, cfg.d_model),
                                (None, "embed"), init="normal"),
            "frontend": frontend_defs(cfg),
            "encoder": stack_defs(self._enc_block_defs(), cfg.encoder_layers),
            "enc_norm": _norm_defs(cfg.d_model),
            "decoder": stack_defs(self._dec_block_defs(), cfg.num_layers),
            "final_norm": _norm_defs(cfg.d_model),
        }

    def init(self, rng):
        return init_params(self.param_defs(), rng, self.cfg.param_dtype)

    def abstract(self):
        return abstract_params(self.param_defs(), self.cfg.param_dtype)

    # -- encoder --------------------------------------------------------------
    def encode(self, params, frames: Array) -> Array:
        """The encoder's output (B, S, d) of mels (B, 2S, 80), through the
        conv frontend, or of precomputed frame embeddings (B, S, d)."""
        cfg, rt = self.cfg, self.rt
        x = frames.astype(L.cdtype(cfg))
        if x.shape[-1] == N_MELS:
            x = conv_frontend(params["frontend"], x, cfg)
        x = x + sinusoids(x.shape[1], cfg.d_model).astype(x.dtype)
        x = rt.constrain(x, "batch", "seq", None)

        def body(carry, lp):
            xc, aux = carry
            xc = rt.constrain(xc, "batch", "seq", None)
            h = _norm(xc, lp["attn_norm"], cfg)
            q, k, v = L._qkv(lp["attn"], h, cfg, None, rope=False)
            o = _attention(q, k, v, cfg, causal=False)
            xc = xc + L.attn_out(lp["attn"], o)
            h = _norm(xc, lp["mlp_norm"], cfg)
            xc = rt.constrain(xc + L.mlp_apply(lp["mlp"], h, cfg),
                              "batch", "seq", None)
            return (xc, aux)

        x, _ = scan_blocks(
            (x, jnp.zeros((), jnp.float32)), params["encoder"], body,
            remat=cfg.remat != "none",
        )
        return _norm(x, params["enc_norm"], cfg)

    def encode_cross(self, params, frames: Array) -> dict[str, Array]:
        """The serving encoder program: :meth:`encode`, then each decoder
        layer's cross-attention K/V of its output, stacked over the layers
        as ``xk``/``xv`` (layers, B, S, KV, hd) in the parameter dtype."""
        enc = self.encode(params, frames)
        pd = jnp.dtype(self.cfg.param_dtype)

        def layer(_, lp):
            k, v = L.encode_kv(lp["xattn"], enc, self.cfg)
            return None, {"xk": k.astype(pd), "xv": v.astype(pd)}

        return jax.lax.scan(layer, None, params["decoder"])[1]

    # -- decoder --------------------------------------------------------------
    def _embed(self, params, tokens: Array, pos) -> Array:
        """Token embeddings plus the learned positions from ``pos`` on."""
        cfg, L_ = self.cfg, tokens.shape[1]
        if L_ > cfg.max_target_positions:
            raise ValueError(f"{cfg.name}'s decoder takes at most "
                             f"{cfg.max_target_positions} positions, not {L_}")
        x = L.embed_tokens(params["embed"], tokens, cfg)
        pe = jax.lax.dynamic_slice_in_dim(params["dec_pos"], pos, L_, axis=0)
        x = x + pe[None].astype(x.dtype)
        return self.rt.constrain(x, "batch", "seq", None)

    def _dec_block(self, carry, lp, enc_out):
        cfg, rt = self.cfg, self.rt
        x, aux = carry
        x = rt.constrain(x, "batch", "seq", None)
        h = _norm(x, lp["attn_norm"], cfg)
        x = x + L.attention_train(lp["attn"], h, cfg, rt, rope=False)
        h = _norm(x, lp["xattn_norm"], cfg)
        kv = L.encode_kv(lp["xattn"], enc_out, cfg)
        x = x + L.cross_attention(lp["xattn"], h, kv, cfg, rt)
        h = _norm(x, lp["mlp_norm"], cfg)
        x = rt.constrain(x + L.mlp_apply(lp["mlp"], h, cfg),
                         "batch", "seq", None)
        return (x, aux)

    def loss(self, params, batch):
        cfg, rt = self.cfg, self.rt
        enc_out = self.encode(params, batch["frames"])
        x = self._embed(params, batch["tokens"], 0)
        body = functools.partial(self._dec_block, enc_out=enc_out)
        x, _ = scan_blocks(
            (x, jnp.zeros((), jnp.float32)), params["decoder"], body,
            remat=cfg.remat != "none",
        )
        x = _norm(x, params["final_norm"], cfg)
        return L.chunked_ce_loss(params["embed"], x, batch["labels"], cfg, rt)

    # -- serving ----------------------------------------------------------------
    def cache_defs(self, batch: int, seq: int, enc_len: int = N_AUDIO_CTX):
        """Decoder self-attention K/V of ``seq`` positions, and the cross
        K/V of ``enc_len`` encoder positions (a 30 s window unless told),
        allocated at the decode kernel's block multiple
        (``attention_decode.block_rows``: 1500 → 1536) so that prefill pads
        them once and no decode step pads them again; ``enc_len`` masks
        the rows past the real length. The cross K/V are stored
        lane-dense, (layers, B, rows, KV·hd), the layout the decode kernel
        reads: stored as (…, KV, hd) the TPU keeps the rows minor-most (so
        64-wide heads are not padded to 128 lanes), and each layer's slice
        would be relaid out on every step. With ``cfg.kv_quant ==
        "int8"`` every sequence-proportional leaf (self-attn k/v AND the
        cross xk/xv) stores int8 + a per-(row, head) scale."""
        from repro.kernels.attention_decode import block_rows
        from repro.models.common import kv_scale_defs

        cfg = self.cfg
        d = kv_cache_defs(cfg, cfg.num_layers, batch, seq)
        kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
        dt = "int8" if cfg.kv_quant == "int8" else None
        rows = (cfg.num_layers, batch, block_rows(enc_len))
        heads = ParamDef((*rows, kv, hd),
                         ("layers", "batch", "kv_seq", "kv_heads", None),
                         init="zeros", dtype=dt)
        for name in ("xk", "xv"):
            d[name] = ParamDef((*rows, kv * hd),
                               ("layers", "batch", "kv_seq", "kv_heads"),
                               init="zeros", dtype=dt)
        # per-slot REAL encoder length (the cross cache is zero-padded past
        # it): written once at prefill, read by the fused ragged attention
        # every decode step — recomputing it would re-scan the whole cache
        d["enc_len"] = ParamDef(
            (cfg.num_layers, batch), ("layers", "batch"), init="zeros",
            dtype="int32")
        if dt:
            d.update(kv_scale_defs({"xk": heads, "xv": heads}))
        return d

    def prefill(self, params, batch):
        """The decoder prompt's forward against the encoder's cross K/V,
        ``batch["cross"]`` (:meth:`encode_cross`'s output). Returns the last
        token's logits and the decode cache's pieces: each layer's self K/V
        of the prompt, the cross K/V, and each slot's encoder length."""
        cfg, rt = self.cfg, self.rt
        cross = batch["cross"]
        x = self._embed(params, batch["tokens"], 0)
        pd = jnp.dtype(cfg.param_dtype)

        def body(carry, inp):
            xc, aux = carry
            lp, xkv = inp
            h = _norm(xc, lp["attn_norm"], cfg)
            q, k, v = L._qkv(lp["attn"], h, cfg, None, rope=False)
            o = _attention(q, k, v, cfg, causal=True)
            xc = xc + L.attn_out(lp["attn"], o)
            h = _norm(xc, lp["xattn_norm"], cfg)
            xc = xc + L.cross_attention(lp["xattn"], h,
                                        (xkv["xk"], xkv["xv"]), cfg, rt)
            h = _norm(xc, lp["mlp_norm"], cfg)
            xc = xc + L.mlp_apply(lp["mlp"], h, cfg)
            return (xc, aux), {"k": k.astype(pd), "v": v.astype(pd)}

        (x, _), cache = scan_blocks(
            (x, jnp.zeros((), jnp.float32)), (params["decoder"], cross), body,
            remat=cfg.remat != "none", collect=True,
        )
        x = _norm(x, params["final_norm"], cfg)
        logits = L.lm_logits(params["embed"], x[:, -1:], cfg)
        B, S = cross["xk"].shape[1:3]
        cache.update(cross,
                     enc_len=jnp.full((cfg.num_layers, B), S, jnp.int32))
        return logits, cache

    def decode_step(self, params, cache, tokens, pos):
        """One token for every slot: its logits, and the cache leaves the
        step wrote, the self-attention K/V (and their int8 scales). The
        cross K/V and ``enc_len``, written once at prefill, reach each
        layer as read-only scan inputs and are not returned, so a compiled
        step neither restacks nor outputs (copies) them; the caller merges
        what is returned into the cache it holds."""
        cfg, rt = self.cfg, self.rt
        x = self._embed(params, tokens, pos)
        own = {n: cache[n] for n in SELF_LEAVES if n in cache}
        held = {n: a for n, a in cache.items() if n not in own}

        def body(carry, inp):
            xc, _ = carry
            lp, sub, cl = inp
            h = _norm(xc, lp["attn_norm"], cfg)
            y, kv_new = L.attention_decode(
                lp["attn"], h, sub, pos, cfg, rt, rope=False)
            xc = xc + y
            h = _norm(xc, lp["xattn_norm"], cfg)
            # ragged fused read over the padded (possibly int8) encoder
            # cache: the valid-prefix masking, zero-cache fallback, and
            # int8 code handling live in cross_attention_decode
            xc = xc + L.cross_attention_decode(lp["xattn"], h, cl, cfg)
            h = _norm(xc, lp["mlp_norm"], cfg)
            xc = xc + L.mlp_apply(lp["mlp"], h, cfg)
            return (xc, jnp.zeros((), jnp.float32)), kv_new

        (x, _), written = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)),
            (params["decoder"], own, held))
        x = _norm(x, params["final_norm"], cfg)
        return L.lm_logits(params["embed"], x, cfg), written
