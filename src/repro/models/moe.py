"""Mixture-of-Experts FFN with expert parallelism.

Production path (``rt.mesh`` present): experts are sharded over the
``model`` mesh axis. Because activations are replicated over ``model``
between blocks (Megatron layout), every model-axis device already holds all
tokens — dispatch is a *local* capacity-bounded scatter to the device's own
expert shard, and the combine is the row-parallel ``psum`` the block needs
anyway. No all-to-all is required; EP communication folds into the existing
TP collective. (An a2a variant is a known alternative when activations are
sequence-sharded; see EXPERIMENTS.md §Perf.)

Fallback path (no mesh — CPU smoke tests): same routing math evaluated with
a dense one-hot dispatch einsum.

FLOPs are top-k-active only in both paths: 2·T·K·(3·D·F) + router.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.distributed.sharding import ParamDef, Runtime
from repro.models.layers import act_fn

Array = jax.Array


def moe_defs(cfg: ModelConfig) -> dict[str, ParamDef]:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    return {
        "router": ParamDef((d, e), ("embed", None), init="normal", dtype="float32"),
        "wg": ParamDef((e, d, f), ("experts", "embed", "mlp"), init="fan_in"),
        "wu": ParamDef((e, d, f), ("experts", "embed", "mlp"), init="fan_in"),
        "wd": ParamDef((e, f, d), ("experts", "mlp", "embed"), init="fan_in"),
    }


def _route(xt: Array, router: Array, k: int):
    """Top-k routing with renormalized gates. xt: (T, D)."""
    logits = jnp.einsum(
        "td,de->te", xt.astype(jnp.float32), router.astype(jnp.float32)
    )
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, k)  # (T, K)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # load-balancing auxiliary (Switch-style): mean router prob * mean load
    load = jnp.mean(
        jax.nn.one_hot(ids[:, 0], router.shape[1], dtype=jnp.float32), axis=0
    )
    imp = probs.mean(axis=0)
    aux = router.shape[1] * jnp.sum(load * imp)
    return gates, ids, aux


def _expert_ffn(buf: Array, wg, wu, wd, activation: str) -> Array:
    """buf: (E_loc, C, D) -> (E_loc, C, D)."""
    f = act_fn(activation)
    dt = buf.dtype
    g = jnp.einsum("ecd,edf->ecf", buf, wg.astype(dt))
    u = jnp.einsum("ecd,edf->ecf", buf, wu.astype(dt))
    return jnp.einsum("ecf,efd->ecd", f(g) * u, wd.astype(dt))


TOKEN_GROUP = 8192  # tokens dispatched per scanned group (bounds liveness)


def _ep_local(xt, router, wg, wu, wd, *, cfg: ModelConfig, n_model: int,
              model_axis: str | None, psum_axes: tuple = ()):
    """Per-device EP body with token-group scanning.

    xt: (T_loc, D) tokens replicated over the model axis; wg/wu/wd:
    (E_loc, D, F) local expert shard. Tokens are processed in groups of
    ``TOKEN_GROUP`` inside a ``lax.scan`` (capacity enforced per group, as
    in grouped-capacity MoE systems): the (group·K, D) dispatch/combine
    gathers exist for one group at a time, so XLA cannot schedule every MoE
    layer's gather transients concurrently (observed 140 GB/device on
    jamba-398b without grouping)."""
    T, D = xt.shape
    if T > TOKEN_GROUP and T % TOKEN_GROUP == 0:
        ng = T // TOKEN_GROUP
        groups = xt.reshape(ng, TOKEN_GROUP, D)

        @jax.checkpoint
        def gstep(carry, xg):
            out, aux = _ep_group(xg, router, wg, wu, wd, cfg=cfg,
                                 n_model=n_model, model_axis=model_axis,
                                 psum_axes=psum_axes)
            return carry + aux, out

        aux_sum, outs = jax.lax.scan(
            gstep, jnp.zeros((), jnp.float32), groups
        )
        return outs.reshape(T, D), aux_sum / ng
    return _ep_group(xt, router, wg, wu, wd, cfg=cfg, n_model=n_model,
                     model_axis=model_axis, psum_axes=psum_axes)


def _ep_group(xt, router, wg, wu, wd, *, cfg: ModelConfig, n_model: int,
              model_axis: str | None, psum_axes: tuple = ()):
    T, D = xt.shape
    E_loc = wg.shape[0]
    K = cfg.experts_per_token
    E = E_loc * n_model
    gates, ids, aux = _route(xt, router, K)
    cap = int(max(1, (T * K / E) * cfg.capacity_factor))
    base = (
        jax.lax.axis_index(model_axis) * E_loc if model_axis is not None else 0
    )
    flat_ids = ids.reshape(T * K)
    flat_gates = gates.reshape(T * K)
    local = (flat_ids >= base) & (flat_ids < base + E_loc)
    lid = jnp.where(local, flat_ids - base, 0)
    onehot = jax.nn.one_hot(lid, E_loc, dtype=jnp.int32) * local[:, None]
    pos = jnp.cumsum(onehot, axis=0) - onehot  # position BEFORE this entry
    pos_in_e = jnp.take_along_axis(pos, lid[:, None], axis=1)[:, 0]
    keep = local & (pos_in_e < cap)
    slot = jnp.where(keep, lid * cap + pos_in_e, E_loc * cap)  # OOB -> dropped
    # dispatch: scatter tokens into (E_loc*cap, D). Token replication over K
    # is a regular pattern -> broadcast+reshape, NOT a gather.
    xt_rep = jnp.broadcast_to(xt[:, None], (T, K, D)).reshape(T * K, D)
    buf = jnp.zeros((E_loc * cap + 1, D), xt.dtype)
    buf = buf.at[slot].set(xt_rep, mode="drop")
    buf = buf[:-1].reshape(E_loc, cap, D)
    out_buf = _expert_ffn(buf, wg, wu, wd, cfg.activation).reshape(
        E_loc * cap, D
    )
    # combine: gather expert outputs back to (T*K) slots (bf16), weight, and
    # sum the K expert choices per token via reshape (regular pattern — no
    # scatter-add, whose u32 index broadcast cost 4 GB/layer at jamba scale).
    vals = jnp.where(
        keep[:, None], out_buf[jnp.minimum(slot, E_loc * cap - 1)], 0.0
    ) * flat_gates[:, None].astype(xt.dtype)
    out = vals.reshape(T, K, D).sum(axis=1).astype(xt.dtype)
    axes = psum_axes or ((model_axis,) if model_axis is not None else ())
    if axes:
        out = jax.lax.psum(out, axes)
        aux = jax.lax.pmean(aux, axes)
    return out, aux


def moe_apply(
    p, x: Array, cfg: ModelConfig, rt: Runtime
) -> tuple[Array, Array]:
    """x: (B, L, D) -> (out, aux_loss)."""
    B, L, D = x.shape
    model_ax = rt.axis_for("experts", cfg.num_experts)
    if rt.mesh is None or model_ax is None:
        out, aux = _ep_local(
            x.reshape(B * L, D), p["router"], p["wg"], p["wu"], p["wd"],
            cfg=cfg, n_model=1, model_axis=None,
        )
        return out.reshape(B, L, D), aux

    n_model = rt.axis_size("experts")
    dp_axes = rt.dp_axes()
    x_spec = P(
        dp_axes if (dp_axes and B % rt.dp_size == 0) else None, None, None
    )
    # Expert-weight specs follow the rule table. Train/prefill: experts on
    # `model`, D/F unsharded inside the shard_map (the FSDP gather happens at
    # the boundary). Serving 2-D TP rules additionally shard the per-expert
    # F dim over `data` — the FFN then emits a partial sum and the combine
    # psums over both axes instead of all-gathering weights every step.
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    wg_spec = rt.pspec(("experts", "embed_act", "mlp"), (e, d, f))
    wd_spec = rt.pspec(("experts", "mlp", "embed_act"), (e, f, d))

    def _axes(entry):
        return [] if entry is None else (
            [entry] if isinstance(entry, str) else list(entry)
        )

    psum_axes = tuple(dict.fromkeys(_axes(wg_spec[0]) + _axes(wg_spec[2])))
    expert_axis = wg_spec[0] if isinstance(wg_spec[0], str) else None

    def fn(xb, router, wg, wu, wd):
        Bl = xb.shape[0]
        out, aux = _ep_local(
            xb.reshape(Bl * L, D), router, wg, wu, wd,
            cfg=cfg, n_model=n_model, model_axis=expert_axis,
            psum_axes=psum_axes,
        )
        # aux already pmean'd over model; mean over dp happens via loss mean
        return out.reshape(Bl, L, D), aux

    out, aux = shard_map(
        fn,
        mesh=rt.mesh,
        in_specs=(x_spec, P(None, None), wg_spec, wg_spec, wd_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(x, p["router"], p["wg"], p["wu"], p["wd"])
    return out, aux
