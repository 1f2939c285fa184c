"""Jamba hybrid (arXiv:2403.19887): Mamba + attention 1:7 interleave, MoE.

Layer schedule (period = ``attn_every`` = 8): position 4 is attention, the
other 7 are Mamba; every other layer (odd positions) swaps the dense FFN for
a 16-expert top-2 MoE. Params are stacked per *period* and scanned over the
9 periods, keeping trace size ≈ one period.

Serving state per period: 1 attention KV cache + 7 Mamba (conv, ssm) states.
The attention KV is the only sequence-length-proportional state — that plus
the SSM recurrence is what makes long_500k feasible (DESIGN.md §4).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from jax.lax import optimization_barrier
from repro.configs.base import ModelConfig
from repro.distributed.sharding import ParamDef, Runtime, abstract_params, init_params
from repro.models import layers as L
from repro.models import moe as moe_lib
from repro.models.common import add_kv_prefix, stack_defs, strip_kv_prefix
from repro.models.mamba import mamba_apply, mamba_defs, mamba_state_defs

Array = jax.Array


def _attn_pos(cfg: ModelConfig) -> int:
    return cfg.attn_every // 2  # attention sits mid-period (jamba: idx 4)


class Jamba:
    def __init__(self, cfg: ModelConfig, rt: Runtime | None = None):
        assert cfg.attn_every > 0 and cfg.num_layers % cfg.attn_every == 0
        self.cfg = cfg
        self.rt = rt or Runtime()
        self.period = cfg.attn_every
        self.n_periods = cfg.num_layers // cfg.attn_every

    # -- parameters ----------------------------------------------------------
    def _pos_defs(self, pos: int) -> dict[str, Any]:
        cfg = self.cfg
        d = {"norm": ParamDef((cfg.d_model,), ("embed",), init="ones"),
             "ffn_norm": ParamDef((cfg.d_model,), ("embed",), init="ones")}
        if pos == _attn_pos(cfg):
            d["attn"] = L.attention_defs(cfg)
        else:
            d["mamba"] = mamba_defs(cfg)
        if cfg.num_experts and pos % cfg.moe_every == 1:
            d["moe"] = moe_lib.moe_defs(cfg)
        else:
            d["mlp"] = L.mlp_defs(cfg)
        return d

    def param_defs(self):
        cfg = self.cfg
        period = {
            f"pos{j}": stack_defs(self._pos_defs(j), self.n_periods)
            for j in range(self.period)
        }
        return {
            "embed": L.embed_defs(cfg),
            "periods": period,
            "final_norm": ParamDef((cfg.d_model,), ("embed",), init="ones"),
        }

    def init(self, rng):
        return init_params(self.param_defs(), rng, self.cfg.param_dtype)

    def abstract(self):
        return abstract_params(self.param_defs(), self.cfg.param_dtype)

    # -- blocks ---------------------------------------------------------------
    def _pos_block(self, x_aux, lp, pos: int):
        cfg, rt = self.cfg, self.rt
        x, aux = x_aux
        x = rt.constrain(x, "batch", "seq", None)
        h = L.rms_norm(x, lp["norm"], cfg.norm_eps)
        if pos == _attn_pos(cfg):
            x = x + L.attention_train(lp["attn"], h, cfg, rt)
        else:
            y, _ = mamba_apply(lp["mamba"], h, cfg, rt)
            x = x + y
        h = L.rms_norm(x, lp["ffn_norm"], cfg.norm_eps)
        if "moe" in lp:
            y, a = moe_lib.moe_apply(lp["moe"], h, cfg, rt)
            aux = aux + a
        else:
            y = L.mlp_apply(lp["mlp"], h, cfg)
        # output constraint: the next checkpoint's saved residual (SP)
        return (rt.constrain(x + y, "batch", "seq", None), aux)

    def hidden(self, params, embeds):
        cfg = self.cfg

        def period_body(carry, period_params):
            carry = optimization_barrier(carry)  # see common.scan_blocks
            for j in range(self.period):
                body = functools.partial(self._pos_block, pos=j)
                if cfg.remat != "none":
                    body = jax.checkpoint(body)
                carry = body(carry, period_params[f"pos{j}"])
            return carry, None

        (x, aux), _ = jax.lax.scan(
            period_body,
            (embeds, jnp.zeros((), jnp.float32)),
            params["periods"],
        )
        return L.rms_norm(x, params["final_norm"], cfg.norm_eps), aux

    def loss(self, params, batch):
        cfg, rt = self.cfg, self.rt
        x = L.embed_tokens(params["embed"], batch["tokens"], cfg)
        x = rt.constrain(x, "batch", "seq", None)
        h, aux = self.hidden(params, x)
        ce = L.chunked_ce_loss(params["embed"], h, batch["labels"], cfg, rt)
        return ce + 0.01 * aux / max(cfg.num_layers, 1)

    # -- serving ---------------------------------------------------------------
    def cache_defs(self, batch: int, seq: int):
        cfg = self.cfg
        kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
        np_ = self.n_periods
        dt = "int8" if cfg.kv_quant == "int8" else None
        d = {
            "attn_k": ParamDef(
                (np_, batch, seq, kv, hd),
                ("layers", "batch", "kv_seq", "kv_heads", None), init="zeros",
                dtype=dt),
            "attn_v": ParamDef(
                (np_, batch, seq, kv, hd),
                ("layers", "batch", "kv_seq", "kv_heads", None), init="zeros",
                dtype=dt),
        }
        if dt:
            from repro.models.common import kv_scale_defs

            d.update(kv_scale_defs(dict(d)))
        ms = mamba_state_defs(cfg, np_, batch)
        for j in range(self.period):
            if j == _attn_pos(cfg):
                continue
            d[f"mamba{j}"] = ms
        return d

    def prefill(self, params, batch):
        """Prompt forward emitting last-token logits + serving state: attn KV
        per period + final Mamba (conv, ssm) states."""
        cfg, rt = self.cfg, self.rt
        x = L.embed_tokens(params["embed"], batch["tokens"], cfg)
        x = rt.constrain(x, "batch", "seq", None)
        Ltot = x.shape[1]

        def period_body(carry, pp):
            xc, aux = carry
            out_cache = {}
            for j in range(self.period):
                lp = pp[f"pos{j}"]
                h = L.rms_norm(xc, lp["norm"], cfg.norm_eps)
                if j == _attn_pos(cfg):
                    positions = jnp.arange(Ltot)[None, :]
                    q, k, v = L._qkv(lp["attn"], h, cfg, positions)
                    if Ltot > cfg.attn_chunk:
                        o = L.chunked_attention(q, k, v, causal=True,
                                                chunk=cfg.attn_chunk)
                    else:
                        o = L.full_attention(q, k, v, causal=True)
                    y = jnp.einsum("blhk,hkd->bld", o,
                                   lp["attn"]["wo"].astype(xc.dtype))
                    out_cache["attn_k"] = k.astype(jnp.dtype(cfg.param_dtype))
                    out_cache["attn_v"] = v.astype(jnp.dtype(cfg.param_dtype))
                else:
                    y, st = mamba_apply(lp["mamba"], h, cfg, rt,
                                        return_state=True)
                    out_cache[f"mamba{j}"] = st
                xc = xc + y
                h = L.rms_norm(xc, lp["ffn_norm"], cfg.norm_eps)
                if "moe" in lp:
                    y, a = moe_lib.moe_apply(lp["moe"], h, cfg, rt)
                    aux = aux + a
                else:
                    y = L.mlp_apply(lp["mlp"], h, cfg)
                xc = xc + y
            return (xc, aux), out_cache

        body = period_body
        if cfg.remat != "none":
            body = jax.checkpoint(period_body)
        (x, _), cache = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)), params["periods"])
        x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = L.lm_logits(params["embed"], x[:, -1:], cfg)
        return logits, cache

    def decode_step(self, params, cache, tokens, pos):
        cfg, rt = self.cfg, self.rt
        x = L.embed_tokens(params["embed"], tokens, cfg)
        x = rt.constrain(x, "batch", "seq", None)

        def period_body(carry, inp):
            xc, _ = carry
            pp, cl = inp
            new_cache = dict(cl)
            for j in range(self.period):
                lp = pp[f"pos{j}"]
                h = L.rms_norm(xc, lp["norm"], cfg.norm_eps)
                if j == _attn_pos(cfg):
                    # strip/add the attn_ prefix as a set: the int8 cache's
                    # (q, scale) pair leaves travel together, never sliced
                    # by hand (common.store_kv_token owns the pair update)
                    sub = strip_kv_prefix(cl, "attn_")
                    y, kv_new = L.attention_decode(lp["attn"], h, sub, pos,
                                                   cfg, rt)
                    new_cache.update(add_kv_prefix(kv_new, "attn_"))
                else:
                    y, st = mamba_apply(lp["mamba"], h, cfg, rt,
                                        state=cl[f"mamba{j}"])
                    new_cache[f"mamba{j}"] = st
                xc = xc + y
                h = L.rms_norm(xc, lp["ffn_norm"], cfg.norm_eps)
                if "moe" in lp:
                    y, _a = moe_lib.moe_apply(lp["moe"], h, cfg, rt)
                else:
                    y = L.mlp_apply(lp["mlp"], h, cfg)
                xc = xc + y
            return (xc, jnp.zeros((), jnp.float32)), new_cache

        (x, _), new_cache = jax.lax.scan(
            period_body, (x, jnp.zeros((), jnp.float32)),
            (params["periods"], cache),
        )
        x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
        return L.lm_logits(params["embed"], x, cfg), new_cache
