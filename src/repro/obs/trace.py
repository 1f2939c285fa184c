"""Tracing: named spans on the JAX profiler's timeline (DESIGN.md §12).

While a JAX profiler records, :func:`span` enters
``jax.profiler.TraceAnnotation(name, **attrs)``: the span lands in the
``/host:CPU`` plane of the same ``.xplane.pb`` as the device's ops, on one
clock, under its bare name with the attributes as stats. Any profiler
arms it — ``--trace`` on serve/train (``jax.profiler.trace``), a
benchmark's traced run, or an operator's ``jax.profiler`` session or
profile server. Otherwise :func:`span` returns a shared null context
manager: one ``is_enabled()`` check, no allocation.

JAX is imported on the first :func:`span` call, so ``repro.obs`` imports
without it.
"""
from __future__ import annotations

from repro.obs import names

#: ``jax.profiler.TraceAnnotation``, imported on first use
_ANNOTATION = None


class _NullSpan:
    """Shared no-op context manager returned while no profiler records."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _NullSpan()


def _load_annotation():
    global _ANNOTATION
    from jax.profiler import TraceAnnotation

    _ANNOTATION = TraceAnnotation
    return TraceAnnotation


def span(name: str, **attrs):
    """Context manager naming one region of host time on the profiler's
    timeline; a no-op when no profiler records.

        with obs.span("serve.prefill", arch=cfg.name):
            ...

    The name must come from the frozen ``obs.names.SPANS`` vocabulary.
    """
    ann = _ANNOTATION or _load_annotation()
    if not ann.is_enabled():
        return _NULL
    if name not in names.SPANS:
        raise ValueError(
            f"unknown span name {name!r}: add it to obs.names.SPANS "
            f"(frozen vocabulary, DESIGN.md §12)"
        )
    return ann(name, **attrs)
