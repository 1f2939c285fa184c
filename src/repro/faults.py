"""Deterministic, seedable fault injection for the robustness layer.

The graceful-degradation machinery (DESIGN.md §10) is only trustworthy if
every failure class it claims to survive can be *produced on demand*. This
module is the single switchboard: production code calls tiny hooks at its
failure points (``maybe_fail``, ``sleep_point``, ``poison_rows``,
``corrupt_scale``, ``take``) which are no-ops unless an injection is armed
— either programmatically::

    with faults.inject("pallas_compile", site="conv1d", times=1):
        ops.conv1d(x, w)          # pallas rung raises; ladder demotes

or via the environment for CI / subprocess chaos runs::

    REPRO_FAULTS=pallas_compile                      # every site
    REPRO_FAULTS=pallas_compile:conv1d,quant_scale_zero:whisper/conv1
    REPRO_FAULTS=slow_step*2                         # fire at most twice

Spec grammar: ``kind[:site][*times]`` joined by commas.

Fault kinds (each consumed by a specific hook site):

  ====================  =====================================================
  kind                  hook / effect
  ====================  =====================================================
  pallas_compile        ops dispatch ladder, pallas rung — raises FaultError
                        at TRACE time (the ladder demotes in place)
  pallas_runtime        ``guest_trap``: raises *inside the compiled call*
                        (jax.debug.callback) on the pallas rung — the
                        failure surfaces at RUN time to serve/train's
                        runtime catch layer (DESIGN.md §15)
  jax_runtime           ops dispatch ladder, compiled-JAX rung — raises
  nan_activations       ``poison_rows``: NaNs every batch row of the serve
                        logits, or one row (slot);
                        ``guest_trap``: a kernel emitting NaN at run time
  quant_scale_zero      ``corrupt_scale``: calibration emits a 0.0 scale
  quant_scale_nan       ``corrupt_scale``: calibration emits a NaN scale
  autotune_corrupt      autotune ``_load``: treats the cache file as corrupt
  ckpt_corrupt          CheckpointManager: truncates a leaf after commit
  ckpt_write_stall      CheckpointManager._write: sleeps between leaves
  heartbeat_stale       ft.beat: skips the heartbeat write (dead host)
  slow_step             train/serve loops: sleeps ``delay_s`` (straggler)
  ====================  =====================================================

Determinism: an injection fires on every matching call (up to ``times``)
unless given a probability ``p < 1``, in which case draws come from a
``numpy`` generator seeded with ``seed`` — the fire/skip sequence is a
pure function of the call order, so chaos tests replay exactly.

Sites match hierarchically: an injection armed for ``site="conv1d"`` also
hits ``"conv1d.w8a8"`` (prefix up to a ``.``); ``site=None`` hits every
site. Hooks are thread-safe and O(1) when nothing is armed.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import threading
import time
from typing import Iterator

import numpy as np

ENV_VAR = "REPRO_FAULTS"


class FaultError(RuntimeError):
    """Raised by an armed ``maybe_fail`` hook; carries the reason code."""

    def __init__(self, kind: str, site: str | None):
        super().__init__(f"injected fault {kind!r} at site {site!r}")
        self.kind = kind
        self.site = site


@dataclasses.dataclass
class Injection:
    kind: str
    site: str | None = None  # None → every site
    times: int | None = None  # None → unlimited
    p: float = 1.0  # fire probability per matching call
    seed: int = 0
    delay_s: float = 0.05  # for sleep hooks (slow_step, ckpt_write_stall)
    fired: int = 0
    _rng: np.random.Generator | None = None

    def matches(self, site: str | None) -> bool:
        if self.site is None or site is None:
            return True
        return site == self.site or site.startswith(self.site + ".")

    def take(self) -> bool:
        """Consume one firing opportunity; True if the fault fires now."""
        if self.times is not None and self.fired >= self.times:
            return False
        if self.p < 1.0:
            if self._rng is None:
                self._rng = np.random.default_rng(self.seed)
            if self._rng.random() >= self.p:
                return False
        self.fired += 1
        return True


_LOCK = threading.Lock()
_ACTIVE: list[Injection] = []
_ENV_LOADED = False


def _parse_env(spec: str) -> list[Injection]:
    """``kind[:site][*times]`` entries joined by commas."""
    out = []
    for entry in spec.split(","):
        entry = entry.strip()
        if not entry:
            continue
        times = None
        if "*" in entry:
            entry, _, n = entry.rpartition("*")
            times = int(n)
        kind, _, site = entry.partition(":")
        out.append(Injection(kind=kind, site=site or None, times=times))
    return out


def _ensure_env() -> None:
    global _ENV_LOADED
    if not _ENV_LOADED:
        _ENV_LOADED = True
        spec = os.environ.get(ENV_VAR, "")
        if spec:
            _ACTIVE.extend(_parse_env(spec))


def reload_env() -> None:
    """Re-read ``REPRO_FAULTS`` (tests that monkeypatch the env)."""
    global _ENV_LOADED
    with _LOCK:
        _ACTIVE.clear()
        _ENV_LOADED = False
        _ensure_env()


def reset() -> None:
    """Disarm everything, including env-armed injections (tests)."""
    global _ENV_LOADED
    with _LOCK:
        _ACTIVE.clear()
        _ENV_LOADED = True  # do not re-arm from the env until reload_env()


def active(kind: str, site: str | None = None) -> Injection | None:
    """The first armed injection matching (kind, site), else None."""
    with _LOCK:
        _ensure_env()
        for inj in _ACTIVE:
            if inj.kind == kind and inj.matches(site):
                return inj
    return None


def take(kind: str, site: str | None = None) -> bool:
    """True exactly when an armed matching injection fires (and consumes
    one of its ``times``). The universal boolean hook."""
    inj = active(kind, site)
    return inj.take() if inj is not None else False


def maybe_fail(kind: str, site: str | None = None) -> None:
    """Raise ``FaultError(kind, site)`` when armed — the kernel-failure
    hook the ops dispatch ladder places at the top of each rung."""
    if take(kind, site):
        raise FaultError(kind, site)


# rung name → the fault kinds that fire at TRACE time at that rung of the
# ops ladder (``pallas_runtime`` moved to the guest trap below: it fires
# inside the compiled call, which is the class it names)
RUNG_KINDS = {
    "pallas": ("pallas_compile",),
    "jax": ("jax_runtime",),
}


def maybe_fail_rung(rung: str, site: str) -> None:
    """Ladder hook: check every fault kind registered for this rung."""
    for kind in RUNG_KINDS.get(rung, ()):
        maybe_fail(kind, site)


# -- runtime fault domain (DESIGN.md §15) -------------------------------------
#
# A kernel that traces/compiles fine but dies *on device at run time* never
# reaches the dispatch ladder — dispatch already returned. The guest trap
# closes that gap: ``ops._ladder`` wraps the winning rung's output in a
# ``jax.debug.callback`` which executes on the host INSIDE every run of the
# compiled function. When an armed runtime fault fires (or the env-gated
# non-finite sentinel sees a poisoned output), the callback records a
# ``Trip`` carrying the dispatch key and raises — XLA surfaces it as an
# ``XlaRuntimeError`` at the jit call, where serve/train's catch layer
# consumes the trip to map the failure back to its (site, rung).

#: rung name → fault kinds the guest trap fires inside the compiled call
RUNTIME_RUNG_KINDS = {
    "pallas": ("pallas_runtime",),
}

#: arm the non-finite output sentinel at every ladder site (cheap: one
#: ``isfinite`` reduction per dispatch output, only when enabled)
SENTINEL_ENV = "REPRO_RUNTIME_SENTINEL"


@dataclasses.dataclass(frozen=True)
class Trip:
    """Host-side record of one runtime trap firing: the (site, rung) the
    failure maps back to, the autotune dispatch key, and the fault kind."""

    site: str
    rung: str
    key: str | None
    kind: str


_TRIP: list[Trip] = []  # single-slot mailbox, guarded by _LOCK


def _record_trip(trip: Trip) -> None:
    with _LOCK:
        _TRIP[:] = [trip]


def consume_trip(site: str | None = None) -> Trip | None:
    """Pop the pending runtime trip (the catch layer's attribution read).
    Returns None when the failure was not a trapped kernel fault. With
    ``site`` given, pops only a trip recorded for that site — the eager
    ladder filters so it never steals another site's attribution from
    the serve/train catch layers."""
    with _LOCK:
        if not _TRIP:
            return None
        if site is not None and _TRIP[0].site != site:
            return None
        return _TRIP.pop()


def sentinel_on() -> bool:
    return os.environ.get(SENTINEL_ENV, "") not in ("", "0")


def trap_armed(rung: str, site: str) -> bool:
    """Trace-time gate: compile the guest trap into this rung's output?
    True when a runtime-kind injection matches the site, a NaN injection
    targets the kernel site, or the sentinel env is set. O(1) when clean
    — the hot path pays one env read and an empty-list scan."""
    if sentinel_on():
        return True
    for kind in RUNTIME_RUNG_KINDS.get(rung, ()):
        if active(kind, site) is not None:
            return True
    return active("nan_activations", site) is not None


def guest_trap(site: str, rung: str, key: str | None, out):
    """Wrap a rung's output with the in-compiled-call runtime hooks.

    Inserted at trace time only when :func:`trap_armed`; the callback then
    runs on the host inside EVERY execution of the compiled function:

      * an armed ``pallas_runtime``-class injection fires → Trip + raise
        (the "kernel dies on device" drill);
      * an armed ``nan_activations`` injection at the kernel site fires →
        Trip + raise (a kernel emitting NaN at run time);
      * with the sentinel armed, a genuinely non-finite output → same.

    In eager dispatch the callback executes immediately, so the ladder's
    own try/except demotes in place; under jit the raise surfaces as an
    ``XlaRuntimeError`` from the compiled call and serve/train's runtime
    catch layer attributes it via :func:`consume_trip`."""
    if not trap_armed(rung, site):
        return out
    import jax
    import jax.numpy as jnp

    leaves = jax.tree_util.tree_leaves(out)
    flag = jnp.bool_(False)
    if sentinel_on():
        for leaf in leaves:
            if jnp.issubdtype(leaf.dtype, jnp.inexact):
                flag = flag | ~jnp.isfinite(leaf).all()
    kinds = RUNTIME_RUNG_KINDS.get(rung, ()) + ("nan_activations",)

    def _trap(bad):
        for kind in kinds:
            if take(kind, site):
                _record_trip(Trip(site, rung, key, kind))
                raise FaultError(kind, site)
        if bool(bad):
            _record_trip(Trip(site, rung, key, "nan_activations"))
            raise FaultError("nan_activations", site)

    jax.debug.callback(_trap, flag)
    return out


def poison_rows(kind: str, site: str, row_prefix: str, n: int):
    """Host-side NaN-poison decision for ``n`` batch rows (slots): an
    injection armed at ``site`` poisons every row, one armed at
    ``{row_prefix}.{i}`` poisons row ``i`` (armed at ``row_prefix`` itself,
    every row, one firing each). Returns the (n,) bool mask, or None when
    nothing fires. The serve loop hands the mask to the compiled decode
    step, which NaNs those rows of the logits, so chaos runs can poison ONE
    request slot without touching siblings; with nothing of ``kind`` armed
    this is one scan of an empty list."""
    if active(kind) is None:
        return None
    every = take(kind, site)
    rows = np.array([take(kind, f"{row_prefix}.{i}") for i in range(n)])
    if not (every or rows.any()):
        return None
    return rows | every


def sleep_point(kind: str, site: str | None = None) -> float:
    """Sleep ``delay_s`` when armed (straggler / stalled-write injection);
    returns the seconds slept (0.0 when disarmed)."""
    inj = active(kind, site)
    if inj is not None and inj.take():
        time.sleep(inj.delay_s)
        return inj.delay_s
    return 0.0


def corrupt_scale(site: str, scale):
    """Calibration hook: override a site's emitted activation scale with
    0.0 / NaN when ``quant_scale_zero`` / ``quant_scale_nan`` is armed."""
    import jax.numpy as jnp

    if take("quant_scale_zero", site):
        return jnp.zeros_like(scale)
    if take("quant_scale_nan", site):
        return jnp.full_like(scale, jnp.nan)
    return scale


def truncate_file(path, keep_bytes: int = 16) -> None:
    """Torn-write simulator for tests: chop a file to ``keep_bytes``."""
    data = open(path, "rb").read()[:keep_bytes]
    with open(path, "wb") as f:
        f.write(data)


@contextlib.contextmanager
def inject(
    kind: str,
    site: str | None = None,
    *,
    times: int | None = None,
    p: float = 1.0,
    seed: int = 0,
    delay_s: float = 0.05,
) -> Iterator[Injection]:
    """Arm one injection for the duration of the block (programmatic form;
    the env form stays armed for the whole process)."""
    inj = Injection(
        kind=kind, site=site, times=times, p=p, seed=seed, delay_s=delay_s
    )
    with _LOCK:
        _ensure_env()
        _ACTIVE.append(inj)
    try:
        yield inj
    finally:
        with _LOCK:
            if inj in _ACTIVE:
                _ACTIVE.remove(inj)
