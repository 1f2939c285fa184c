"""Pipeline parallelism: GPipe-style microbatch schedule over a `stage` mesh
axis, built from ``shard_map`` + ``ppermute``.

The production meshes in this assignment are (pod, data, model) — no stage
axis — so PP is an *optional* extra axis for deployments that want it (e.g.
cross-slice pipelining where DCN bandwidth favours activation passing over
gradient all-reduce). The implementation is nevertheless real and tested on
virtual devices: S stages × M microbatches, bubble fraction
(S−1)/(M+S−1), activations handed stage→stage by ``collective_permute``.

``pipeline_apply(stage_fn, stage_params, x, mesh)``:
  * ``stage_params`` — pytree whose leaves have a leading stage dim S,
    sharded P('stage', ...) so each device holds its stage's weights;
  * ``x`` — (M, mb, ...) microbatched input (replicated over 'stage');
  * returns (M, mb, ...) outputs of the full S-stage composition.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

Array = jax.Array


def pipeline_apply(
    stage_fn: Callable[[Any, Array], Array],
    stage_params: Any,
    x: Array,
    mesh: Mesh,
    *,
    stage_axis: str = "stage",
) -> Array:
    """Run the S-stage pipeline over M microbatches (forward)."""
    n_stages = mesh.shape[stage_axis]
    M = x.shape[0]
    steps = M + n_stages - 1  # schedule length incl. fill/drain bubble

    params_spec = jax.tree.map(lambda _: P(stage_axis), stage_params)
    perm = [(i, i + 1) for i in range(n_stages - 1)]

    def body(params_local, x_all):
        # params_local leaves: (1, ...) — this device's stage
        p_stage = jax.tree.map(lambda a: a[0], params_local)
        stage_id = jax.lax.axis_index(stage_axis)
        buf = jnp.zeros(x_all.shape[1:], x_all.dtype)  # incoming activation
        outs = jnp.zeros_like(x_all)
        for t in range(steps):
            # stage 0 injects microbatch t (while t < M)
            inject = x_all[min(t, M - 1)]
            cur = jnp.where((stage_id == 0) & (t < M), inject, buf)
            y = stage_fn(p_stage, cur)
            # last stage emits microbatch (t - S + 1) when in range
            m_out = t - (n_stages - 1)
            if 0 <= m_out < M:
                emit = jnp.where(stage_id == n_stages - 1, y, outs[m_out])
                outs = outs.at[m_out].set(emit)
            # hand activations to the next stage
            buf = jax.lax.ppermute(y, stage_axis, perm)
        # keep only the last stage's collected outputs everywhere
        last = jnp.equal(stage_id, n_stages - 1)
        outs = jnp.where(last, outs, 0.0)
        return jax.lax.psum(outs, stage_axis)

    return shard_map(
        body,
        mesh=mesh,
        in_specs=(params_spec, P()),
        out_specs=P(),
        check_vma=False,
    )(stage_params, x)


def pipeline_bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    return (n_stages - 1) / (n_microbatches + n_stages - 1)
