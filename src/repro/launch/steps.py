"""Step factories shared by train.py / serve.py / dryrun.py."""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.optim import OptConfig, apply_updates


def make_train_step(model, opt_cfg: OptConfig, accum_steps: int = 1,
                    accum_dtype: str = "float32") -> Callable:
    """Train step with optional gradient accumulation.

    ``accum_steps > 1`` splits the global batch into microbatches evaluated
    in a ``lax.scan`` (f32 grad accumulator, mean over steps). Besides the
    usual batch-scaling role, the scan is a hard scheduling barrier: XLA
    cannot co-schedule different microbatches' backward transients, which
    bounds peak activation memory (jamba-398b needs this to fit v5e HBM).
    """

    def loss_grads(params, batch):
        return jax.value_and_grad(model.loss)(params, batch)

    def train_step(state, batch):
        if accum_steps == 1:
            loss, grads = loss_grads(state["params"], batch)
        else:
            def split(x):
                b = x.shape[0]
                assert b % accum_steps == 0, (b, accum_steps)
                return x.reshape(accum_steps, b // accum_steps, *x.shape[1:])

            mb = {
                k: (split(v) if getattr(v, "ndim", 0) >= 1 else v)
                for k, v in batch.items()
            }

            def mstep(carry, mbatch):
                tot, acc = carry
                loss, grads = loss_grads(state["params"], mbatch)
                acc = jax.tree.map(
                    lambda a, g: a + g.astype(a.dtype), acc, grads
                )
                return (tot + loss, acc), None

            adt = jnp.dtype(accum_dtype)
            acc0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, adt), state["params"]
            )
            (loss_sum, gsum), _ = jax.lax.scan(
                mstep, (jnp.zeros((), jnp.float32), acc0), mb
            )
            loss = loss_sum / accum_steps
            grads = jax.tree.map(lambda g: g / accum_steps, gsum)
        new_p, new_opt, info = apply_updates(
            state["params"], grads, state["opt"], opt_cfg
        )
        metrics = {"loss": loss, **info}
        return {"params": new_p, "opt": new_opt}, metrics

    return train_step


def make_eval_step(model) -> Callable:
    def eval_step(params, batch):
        return model.loss(params, batch)

    return eval_step


def make_prefill_step(model) -> Callable:
    """The whole prefill as one step: an encoder-decoder's encoder program
    over ``batch["frames"]``, then its prefill over the cross K/V."""
    def prefill_step(params, batch):
        if "frames" in batch:
            batch = dict(batch)
            batch["cross"] = model.encode_cross(params, batch.pop("frames"))
        return model.prefill(params, batch)

    return prefill_step


def make_serve_step(model) -> Callable:
    """One decode step over the whole cache: the leaves ``decode_step``
    wrote, merged into the cache it was given."""
    def serve_step(params, cache, batch):
        logits, written = model.decode_step(
            params, cache, batch["tokens"], batch["pos"]
        )
        return logits, {**cache, **written}

    return serve_step
