"""``repro.obs`` — observability (DESIGN.md §12).

Two surfaces behind frozen name vocabularies (``obs.names``):

  * **tracing** (``obs.span``): named ``jax.profiler.TraceAnnotation``
    spans, live only while a JAX profiler records (``--trace`` on
    serve/train writes ``<run_dir>/profile``); one ``is_enabled()`` check
    and a shared null context manager otherwise.
  * **metrics** (``obs.metrics.REGISTRY``: counters, gauges,
    fixed-bucket latency histograms with deterministic quantiles, facts
    tables) snapshotted to ``metrics.json`` + a Prometheus text
    exposition under ``run_dir``.

``python -m repro.obs report <run_dir>`` renders the human summary from
the persisted artifacts. The package imports nothing from the rest of
``repro``, and JAX only when a span is first asked for — ``health``
(itself import-light) mirrors into it without cycles.
"""
from __future__ import annotations

from repro.obs import logs, metrics, names, trace
from repro.obs.logs import debug, info, log, set_level, warn
from repro.obs.metrics import BOUNDS, REGISTRY, DispatchLog, hist_quantile
from repro.obs.trace import span

__all__ = [
    "BOUNDS", "REGISTRY", "DispatchLog", "debug", "hist_quantile", "info",
    "log", "logs", "metrics", "names", "set_level", "span", "trace",
    "warn", "write_artifacts",
]


def write_artifacts(run_dir) -> list[str]:
    """Persist the run's metrics under ``run_dir``: ``metrics.json`` +
    ``metrics.prom``. Returns the written paths."""
    import os

    paths = [REGISTRY.write(run_dir)]
    paths.append(os.path.join(os.fspath(run_dir), "metrics.prom"))
    return paths
