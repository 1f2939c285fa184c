"""Shared model plumbing: scan-over-layers, stacked ParamDefs, cache defs."""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from jax.lax import optimization_barrier
from repro.configs.base import ModelConfig
from repro.distributed.sharding import ParamDef, Runtime

Array = jax.Array


def stack_defs(defs: Any, n: int) -> Any:
    """Add a leading `layers` dim to every ParamDef (scan-over-layers)."""
    return jax.tree.map(
        lambda d: dataclasses.replace(
            d, shape=(n, *d.shape), axes=("layers", *d.axes)
        ),
        defs,
        is_leaf=lambda x: isinstance(x, ParamDef),
    )


def scan_blocks(
    x: Array,
    stacked: Any,
    body: Callable[[Array, Any], Array],
    *,
    remat: bool = True,
    collect: bool = False,
):
    """Run `body` over the leading (layers) dim of `stacked` params.

    collect=True also stacks per-layer auxiliary outputs (body must return
    (x, aux) pairs) — used by prefill to emit KV caches.

    The carry passes through an optimization barrier each step: without it
    XLA hoists dtype converts of the *entire* stacked residual (layers, B,
    L, D) out of the backward while-loop, materializing an f32 copy of all
    per-layer activations at once (observed: +9 GiB/device on gemma-2b).
    """

    def barrier_body(carry, lp):
        return body(optimization_barrier(carry), lp)

    if collect:
        fn = jax.checkpoint(barrier_body) if remat else barrier_body

        def step(carry, lp):
            new, aux = fn(carry, lp)
            return new, aux

        return jax.lax.scan(step, x, stacked)
    fn = jax.checkpoint(barrier_body) if remat else barrier_body

    def step(carry, lp):
        return fn(carry, lp), None

    out, _ = jax.lax.scan(step, x, stacked)
    return out


def unrolled_blocks(x, layer_list, body, *, remat=True):
    fn = jax.checkpoint(body) if remat else body
    for lp in layer_list:
        x = fn(x, lp)
    return x


def kv_cache_defs(cfg: ModelConfig, layers: int, batch: int, seq: int):
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    quant = cfg.kv_quant == "int8"
    dt = "int8" if quant else None  # None → param_dtype
    d = dict(
        k=ParamDef(
            (layers, batch, seq, kv, hd),
            ("layers", "batch", "kv_seq", "kv_heads", "head_dim"),
            init="zeros", dtype=dt,
        ),
        v=ParamDef(
            (layers, batch, seq, kv, hd),
            ("layers", "batch", "kv_seq", "kv_heads", "head_dim"),
            init="zeros", dtype=dt,
        ),
    )
    if quant:
        d.update(kv_scale_defs(d))
    return d


def quantize_kv_leaf(value: Array) -> tuple[Array, Array]:
    """THE int8 KV quantizer: per-(…, position, head) absmax over the last
    (head_dim) axis via the ``optim/compress`` per-row primitive. Every
    producer of the (q, scale) pair — prefill-cache quantization
    (``serve.quantize_cache_to_defs``) and the per-token decode update
    (:func:`store_kv_token`) — goes through this one function so the pair
    layout and grid can never drift apart."""
    from repro.optim.compress import quantize_int8

    q, s = quantize_int8(value)
    return q.astype(jnp.int8), s


def store_kv_token(
    cache: dict[str, Array], name: str, fresh: Array, pos: Array, *,
    axis: int = 1,
) -> dict[str, Array]:
    """Write one new token's rows for cache leaf ``name`` at ``pos`` along
    ``axis`` (the kv_seq axis of a per-layer decode leaf). When the cache
    stores int8 (a ``<name>_scale`` sibling exists) the fresh rows
    quantize through :func:`quantize_kv_leaf` and BOTH pair leaves update
    together — callers never slice the (q, scale) pair by hand. Returns
    only the updated leaves."""
    import functools

    upd = functools.partial(jax.lax.dynamic_update_slice_in_dim, axis=axis)
    if f"{name}_scale" in cache:
        qrow, srow = quantize_kv_leaf(fresh)
        return {
            name: upd(cache[name], qrow, pos),
            f"{name}_scale": upd(cache[f"{name}_scale"], srow, pos),
        }
    return {name: upd(cache[name], fresh.astype(cache[name].dtype), pos)}


def strip_kv_prefix(cache: dict[str, Array], prefix: str) -> dict[str, Array]:
    """View of the ``prefix``-named K/V leaves under their bare names
    (``attn_k`` → ``k``), carrying the ``_scale`` siblings along — so
    model code hands ``attention_decode`` a complete (q, scale) pair set
    without naming the scale leaves by hand."""
    return {
        name[len(prefix):]: leaf
        for name, leaf in cache.items()
        if name.startswith(prefix)
    }


def add_kv_prefix(leaves: dict[str, Array], prefix: str) -> dict[str, Array]:
    """Inverse of :func:`strip_kv_prefix` for writing updates back."""
    return {f"{prefix}{name}": leaf for name, leaf in leaves.items()}


def kv_scale_defs(defs: dict) -> dict:
    """Per-row f32 scale leaves pairing int8 cache leaves: each ``name``
    whose rows (last axis) are absmax-quantized gets ``<name>_scale`` of
    the same shape with the row axis collapsed to 1. The scale leaf keeps
    the ``kv_seq`` axis name so ``serve.pad_cache_to_defs`` pads the
    (q, scale) pair coherently."""
    return {
        f"{name}_scale": ParamDef(
            (*d.shape[:-1], 1), (*d.axes[:-1], None),
            init="zeros", dtype="float32",
        )
        for name, d in defs.items()
    }
