"""Production mesh definition (assignment §MULTI-POD DRY-RUN).

``make_production_mesh`` is a FUNCTION so importing this module never
touches jax device state. Single pod: 16×16 = 256 chips (data, model).
Multi-pod: 2×16×16 = 512 chips (pod, data, model) — the ``pod`` axis is
pure data parallelism whose gradient all-reduce crosses the DCN.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """A mesh whose axes are ``Auto``: the models place arrays with
    ``with_sharding_constraint`` and leave the rest to the partitioner,
    which explicit axes (``jax.make_mesh``'s default) do not allow."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(n_data: int = 1, n_model: int = 1):
    """Small mesh over however many (virtual) devices exist — for tests."""
    return _auto_mesh((n_data, n_model), ("data", "model"))


# TPU v5e hardware constants (roofline denominators, assignment §ROOFLINE)
PEAK_FLOPS_BF16 = 197e12  # per chip
HBM_BW = 819e9  # bytes/s per chip
ICI_BW = 50e9  # bytes/s per link
