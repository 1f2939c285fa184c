"""Central health registry: reason-coded degradation events + demotions.

The robustness layer (DESIGN.md §10) never silently falls back: every time
a dispatch site degrades — a Pallas kernel demoted to its compiled-JAX
twin, a quantized site served in float because its scale was unusable, a
corrupt autotune cache quarantined, a torn checkpoint skipped — the event
lands here with a machine-checkable reason code. Serving prints the
registry at exit and CI asserts the *expected* events appear (and, in
clean runs, that none do).

Reason codes are a closed vocabulary (:class:`Reason`, DESIGN.md §11):
``record`` rejects anything outside it, and the ``repro.analysis`` lint
pass enforces the same at every call site, so a typo'd reason fails fast
instead of silently forking the event taxonomy that CI greps against.
Exception-derived reasons go through :func:`canon_reason`, which maps a
fault kind or exception class onto the vocabulary.

Two kinds of state:

  * **events** — append-only ``HealthEvent`` log. ``record`` deduplicates
    by (site, reason, action): repeats bump ``count`` instead of spamming,
    and only the first occurrence prints to stderr.
  * **demotions** — a circuit breaker per ``(site, impl)``. The ``ops``
    dispatch ladder consults this so a kernel that failed once is not
    retried on every call (and, under ``jax.jit``, so a re-trace at a new
    shape skips the failed rung). A demotion is NOT process-lifetime
    (DESIGN.md §15): after a cooldown — a clean-call count and/or a
    wall-clock interval, both env-tunable and growing exponentially with
    repeated trips — the rung re-enters through a single *probation*
    call. A probe that serves cleanly repromotes the rung (reason-coded
    ``repromote`` event + ``health.repromote`` counter); a probe that
    fails re-demotes with doubled cooldown.

Cooldown knobs (read at check time so tests can tune them):

  ``REPRO_HEALTH_COOLDOWN_CALLS``  clean dispatches at the site before a
                                   probe (default 64; ``0`` disables the
                                   call-based path)
  ``REPRO_HEALTH_COOLDOWN_S``      wall-clock cooldown in seconds
                                   (measured with ``perf_counter``;
                                   unset → call-based only)
  ``REPRO_HEALTH_COOLDOWN_GROWTH`` per-trip multiplier (default 2.0)

The registry is process-global and import-light (stdlib only): any layer
— kernels, checkpointing, serving, autotuner — can report without import
cycles. ``repro.kernels.ops`` re-exports the singleton as ``ops.HEALTH``.
"""
from __future__ import annotations

import dataclasses
import enum
import os
import sys
import threading
import time

# obs imports nothing else of repro (JAX only at the first span) — no
# cycle, and every health event mirrors into the obs metrics/trace
# surfaces (DESIGN.md §12)
from repro.obs import metrics as _obs_metrics
from repro.obs import trace as _obs_trace
from repro.obs.metrics import DispatchLog  # noqa: F401 — canonical home
                                           # moved to repro.obs.metrics;
                                           # re-exported for existing users


class Reason(str, enum.Enum):
    """Frozen vocabulary of health reason codes.

    Grouped by producer; a new degradation class means a new member HERE
    first (the analysis lint flags literal reasons outside this enum, and
    ``Health.record`` raises on them at runtime). Members are str-valued so
    existing ``ev.reason == "pallas_compile"`` comparisons keep working.
    """

    # fault-injection kinds (repro.faults) — these surface as ``e.kind``
    # on FaultError and flow into ladder/retry reasons verbatim
    PALLAS_COMPILE = "pallas_compile"
    PALLAS_RUNTIME = "pallas_runtime"
    JAX_RUNTIME = "jax_runtime"
    NAN_ACTIVATIONS = "nan_activations"
    QUANT_SCALE_ZERO = "quant_scale_zero"
    QUANT_SCALE_NAN = "quant_scale_nan"
    AUTOTUNE_CORRUPT = "autotune_corrupt"
    CKPT_CORRUPT = "ckpt_corrupt"
    CKPT_WRITE_STALL = "ckpt_write_stall"
    HEARTBEAT_STALE = "heartbeat_stale"
    SLOW_STEP = "slow_step"
    # degradation-ladder rung failures without a fault kind (ops._ladder)
    PALLAS_ERROR = "pallas_error"
    JAX_ERROR = "jax_error"
    REF_ERROR = "ref_error"
    # quant dispatch + calibration
    QUANT_SLOWER = "quant_slower"
    # autotune cache quarantine
    CACHE_CORRUPT = "cache_corrupt"
    CACHE_SCHEMA_MISMATCH = "cache_schema_mismatch"
    # checkpointing
    CKPT_INVALID = "ckpt_invalid"
    # serving
    DEADLINE_EXCEEDED = "deadline_exceeded"
    STRAGGLER = "straggler"
    NAN_LOGITS = "nan_logits"
    LOAD_SHED = "load_shed"
    # training restarts
    RESTARTS_EXHAUSTED = "restarts_exhausted"
    STEP_CRASH = "step_crash"
    # canonical catch-all for exceptions with no mapped kind — the class
    # name goes in ``detail``, not the reason (an open-ended reason set
    # would defeat the frozen vocabulary)
    RUNTIME_ERROR = "runtime_error"


def canon_reason(exc: BaseException, default: str | None = None) -> str:
    """Canonical :class:`Reason` value for an exception.

    Order: a valid ``exc.kind`` (fault-injected errors carry their kind),
    then ``FloatingPointError`` → ``nan_logits`` (the serve nan guard),
    then ``default`` if it names a valid reason, else ``runtime_error``
    with the class name left to the caller's ``detail``.
    """
    kind = getattr(exc, "kind", None)
    if kind is not None:
        try:
            return Reason(kind).value
        except ValueError:
            pass
    if isinstance(exc, FloatingPointError):
        return Reason.NAN_LOGITS.value
    if default is not None:
        try:
            return Reason(default).value
        except ValueError:
            pass
    return Reason.RUNTIME_ERROR.value


@dataclasses.dataclass
class HealthEvent:
    """One reason-coded degradation event.

    ``site``   — where: a dispatch site ("conv1d", "conv1d.w8a8"), a
                 calibration site ("whisper/conv1"), or a subsystem
                 ("autotune", "ckpt", "serve/generate").
    ``reason`` — machine-checkable code from the frozen :class:`Reason`
                 vocabulary: "pallas_compile", "pallas_error",
                 "quant_scale_zero", "quant_scale_nan", "quant_slower",
                 "cache_corrupt", "ckpt_invalid", "nan_logits",
                 "deadline_exceeded", "straggler", …
    ``action`` — what was done: "demote:pallas->jax", "fallback:fp",
                 "quarantine", "retry", "truncate", …
    ``detail`` — free-form context (exception repr, file path, timings).
    ``count``  — occurrences of this (site, reason, action) triple.
    """

    site: str
    reason: str
    action: str
    detail: str = ""
    count: int = 1

    def line(self) -> str:
        extra = f" x{self.count}" if self.count > 1 else ""
        det = f" ({self.detail})" if self.detail else ""
        return (
            f"site={self.site} reason={self.reason} "
            f"action={self.action}{extra}{det}"
        )


def _cooldown_calls() -> int:
    try:
        return int(os.environ.get("REPRO_HEALTH_COOLDOWN_CALLS", "64"))
    except ValueError:
        return 64


def _cooldown_s() -> float | None:
    raw = os.environ.get("REPRO_HEALTH_COOLDOWN_S")
    if not raw:
        return None
    try:
        return float(raw)
    except ValueError:
        return None


def _cooldown_growth() -> float:
    try:
        return float(os.environ.get("REPRO_HEALTH_COOLDOWN_GROWTH", "2.0"))
    except ValueError:
        return 2.0


@dataclasses.dataclass
class Breaker:
    """Circuit-breaker state for one demoted ``(site, impl)`` rung.

    ``open``    — demoted; the ladder skips the rung.
    ``probing`` — cooldown elapsed and exactly ONE dispatch was granted
                  the rung as a probe. The grant is synchronous: the same
                  ``_ladder`` call that received it either succeeds
                  (``note_success`` repromotes) or fails (``demote``
                  re-opens with ``trips + 1``), so ``probing`` can never
                  outlive the dispatch that holds it.
    """

    site: str
    impl: str
    reason: str = Reason.RUNTIME_ERROR.value
    trips: int = 1       # demotion count — drives exponential cooldown
    clean: int = 0       # clean calls at the site since this trip
    since: float = 0.0   # perf_counter at the trip (monotonic, not wall)
    state: str = "open"

    def _growth(self) -> float:
        # cap the exponent so repeated trips saturate instead of overflow
        return _cooldown_growth() ** min(self.trips - 1, 16)

    def ready(self, now: float) -> bool:
        """Cooldown elapsed — the rung may take its probation call."""
        cd_s = _cooldown_s()
        if cd_s is not None and now - self.since >= cd_s * self._growth():
            return True
        calls = _cooldown_calls()
        return calls > 0 and self.clean >= calls * self._growth()


class Health:
    """Process-global event log + per-(site, impl) circuit breakers."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.events: list[HealthEvent] = []
        self._breakers: dict[tuple[str, str], Breaker] = {}
        # trip counts survive repromotion so a rung that keeps flapping
        # keeps inheriting its grown cooldown instead of resetting it
        self._trip_history: dict[tuple[str, str], int] = {}

    # -- events ---------------------------------------------------------------
    def record(
        self, site: str, reason: str, action: str, detail: str = ""
    ) -> HealthEvent:
        """Log one event; duplicate (site, reason, action) bumps count.
        The first occurrence prints one ``[health]`` line to stderr.
        ``reason`` must come from the frozen :class:`Reason` vocabulary —
        an unknown code raises (route exceptions via :func:`canon_reason`).
        """
        try:
            reason = Reason(reason).value
        except ValueError:
            raise ValueError(
                f"unknown health reason {reason!r} at site {site!r}: "
                f"add it to health.Reason or canonicalize via canon_reason"
            ) from None
        # while a profiler records, the event is a span on the timeline
        # next to the kernel ops it explains
        with _obs_trace.span(
            "health.event", site=site, reason=reason, action=action
        ):
            return self._log(site, reason, action, detail)

    def _log(
        self, site: str, reason: str, action: str, detail: str
    ) -> HealthEvent:
        with self._lock:
            hit = None
            for ev in self.events:
                if (ev.site, ev.reason, ev.action) == (site, reason, action):
                    ev.count += 1
                    hit = ev
                    break
            if hit is None:
                hit = HealthEvent(site, reason, action, detail)
                self.events.append(hit)
                first = True
            else:
                first = False
        # mirror into obs: a counter series per (site, reason, action)
        _obs_metrics.REGISTRY.counter("health.events").inc(
            1.0, site=site, reason=reason, action=action
        )
        if first:
            print(f"[health] {hit.line()}", file=sys.stderr)
        return hit

    def events_for(
        self, site: str | None = None, reason: str | None = None
    ) -> list[HealthEvent]:
        return [
            ev
            for ev in self.events
            if (site is None or ev.site == site)
            and (reason is None or ev.reason == reason)
        ]

    # -- demotions (circuit breaker, DESIGN.md §15) ----------------------------
    def demote(self, site: str, impl: str,
               reason: str = Reason.RUNTIME_ERROR.value) -> None:
        """Open the breaker for ``impl`` at ``site``. A repeat trip (or a
        failed probation probe) re-opens it with ``trips + 1`` — the
        cooldown grows exponentially with the trip count."""
        key = (site, impl)
        try:
            reason = Reason(reason).value
        except ValueError:
            reason = Reason.RUNTIME_ERROR.value
        now = time.perf_counter()
        with self._lock:
            br = self._breakers.get(key)
            if br is None:
                br = Breaker(site, impl, reason=reason,
                             trips=self._trip_history.get(key, 0) + 1,
                             since=now)
                self._breakers[key] = br
            else:
                br.trips += 1
                br.clean = 0
                br.since = now
                br.state = "open"
                br.reason = reason
            self._trip_history[key] = br.trips

    def is_demoted(self, site: str, impl: str) -> bool:
        """Breaker check — also the probation gate: the first call after
        the cooldown elapses is granted the rung (returns False once,
        state → ``probing``); the grant resolves synchronously inside
        that dispatch via ``note_success`` or a repeat ``demote``."""
        with self._lock:
            br = self._breakers.get((site, impl))
            if br is None:
                return False
            if br.state == "probing":
                return True  # the single probe is already out
            if br.ready(time.perf_counter()):
                br.state = "probing"
                probe = br
            else:
                return True
        # outside the lock: record re-acquires it
        self.record(site, probe.reason, f"probe:{impl}",
                    detail=f"trip {probe.trips}, clean {probe.clean}")
        return False

    def note_success(self, site: str, impl: str) -> None:
        """A dispatch at ``site`` served cleanly by ``impl``: credit every
        open breaker at the site with a clean call, and resolve ``impl``'s
        probation — the probe passed, the rung repromotes."""
        repromoted = None
        with self._lock:
            for (s, i), br in list(self._breakers.items()):
                if s != site:
                    continue
                if i == impl and br.state == "probing":
                    del self._breakers[(s, i)]
                    repromoted = br
                elif br.state == "open":
                    br.clean += 1
        if repromoted is not None:
            self.record(site, repromoted.reason, f"repromote:{impl}",
                        detail=f"after trip {repromoted.trips}")
            _obs_metrics.REGISTRY.counter("health.repromote").inc(
                1.0, site=site, rung=impl
            )

    def tick(self, n: int = 1) -> None:
        """Clean-call credit from a serving/training loop step — lets a
        call-count cooldown progress while the demoted site itself is not
        re-dispatched (jitted hot loops dispatch only at trace time)."""
        with self._lock:
            for br in self._breakers.values():
                if br.state == "open":
                    br.clean += n

    def probation_ready(self) -> list[tuple[str, str]]:
        """(site, impl) pairs whose cooldown has elapsed but which no
        dispatch has probed yet — serve/train drop their jit caches for
        these so the next re-trace can take the probe."""
        now = time.perf_counter()
        with self._lock:
            return [
                (br.site, br.impl)
                for br in self._breakers.values()
                if br.state == "open" and br.ready(now)
            ]

    def demotions(self) -> dict[str, frozenset[str]]:
        with self._lock:
            out: dict[str, set[str]] = {}
            for (s, i) in self._breakers:
                out.setdefault(s, set()).add(i)
            return {s: frozenset(v) for s, v in out.items()}

    def breaker(self, site: str, impl: str) -> Breaker | None:
        """The live breaker for ``(site, impl)`` (introspection/tests)."""
        with self._lock:
            return self._breakers.get((site, impl))

    # -- lifecycle ------------------------------------------------------------
    def reset(self) -> None:
        """Clear events AND demotions (tests; never in production loops)."""
        with self._lock:
            self.events.clear()
            self._breakers.clear()
            self._trip_history.clear()

    def summary(self) -> list[str]:
        """One formatted line per distinct event (serve prints these)."""
        return [ev.line() for ev in self.events]


#: The process-global registry (re-exported as ``repro.kernels.ops.HEALTH``).
HEALTH = Health()
