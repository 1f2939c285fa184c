"""Seeded random weights, made on the device in one jitted call.

The benchmark makes the weights itself and hands the same tree to the
program and to the reference, so the program's initialiser plays no part
in what is compared. The tree follows the program's parameter layout
(``model.param_defs()``: names, shapes, dtypes); the values follow one
rule, so every seed gives the same sizes:

* matrices: normal with standard deviation ``fan_in ** -0.5``, where the
  fan-in is every axis but the output one (Q/K/V: the model width); the
  projections back into the residual stream (``wo``, ``wd``) are scaled by
  a further ``(2 * layers) ** -0.5`` so the stream stays bounded in depth;
* token embeddings: standard deviation 1, or ``d_model ** -0.5`` when the
  output head is tied to them; a separate output head ``d_model ** -0.5``;
  so the logits have a standard deviation near 1;
* norm scales: ``1 + 0.1 * normal``; biases: ``0.1 * normal``.

A configuration's JSON may adjust the rule with an optional
``"seeded_weights"`` object, whose keys are ``make``'s keywords; without
it the weights are the rule's. ``qk_gain`` multiplies the standard
deviation of every query and key projection (``wq``, ``wk``: self- and
cross-attention alike), which sharpens attention. It is there for models
that attend over long contexts of fixed length, such as whisper's 1500
encoder positions: with the rule's weights the attention there is near
uniform, averages the K/V rows' errors away, and the logit-gap check
cannot tell a K/V cache one precision step down from the served one (on a
TPU v5e, whisper-medium needed ``qk_gain`` 2 before its control failed).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A JAX key from any whole-number seed (more than 32 bits too)."""
    words = np.random.SeedSequence(int(seed)).generate_state(1, np.uint32)
    return jax.random.key(int(words[0]))


def _std(path: tuple[str, ...], shape, axes, tied: bool, d_model: int,
         qk_gain: float):
    name = path[-1]
    if name == "tok":
        return d_model ** -0.5 if tied else 1.0
    if name == "unembed":
        return d_model ** -0.5
    if axes and axes[0] == "layers":
        layers, shape, axes = shape[0], shape[1:], axes[1:]
    else:
        layers = 1
    if len(shape) == 1:
        return None  # vector: norm scale or bias
    fan_in = shape[0] if axes[-1] == "head_dim" else math.prod(shape[:-1])
    std = fan_in ** -0.5
    if name in ("wo", "wd"):
        std *= (2 * layers) ** -0.5
    if name in ("wq", "wk"):
        std *= qk_gain
    return std


def make(model, seed: int, *, qk_gain: float = 1.0):
    """The model's parameters from ``seed``, on the default device, with
    the query and key projections' standard deviation times ``qk_gain``."""
    from repro.distributed.sharding import ParamDef

    defs = model.param_defs()
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        defs, is_leaf=lambda x: isinstance(x, ParamDef))
    cfg = model.cfg
    specs = []
    for path, d in flat:
        names = tuple(getattr(p, "key", str(p)) for p in path)
        dtype = jnp.dtype(d.dtype or cfg.param_dtype)
        std = _std(names, d.shape, d.axes, cfg.tie_embeddings, cfg.d_model,
                   qk_gain)
        specs.append((d.shape, dtype, std, d.init))

    def build(key):
        leaves = []
        for i, (shape, dtype, std, init) in enumerate(specs):
            k = jax.random.fold_in(key, i)
            z = jax.random.normal(k, shape, jnp.float32 if std is None
                                  else dtype)
            if std is not None:
                leaves.append(z * jnp.asarray(std, dtype))
            elif init == "ones":
                leaves.append((1.0 + 0.1 * z).astype(dtype))
            else:
                leaves.append((0.1 * z).astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build)(seed_key(seed))
