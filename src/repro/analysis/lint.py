"""Convention lint: AST pass over ``src/`` for the repo's frozen registries.

Three rules, each backed by a registry that already exists at runtime —
the lint only moves the failure from "first hit in production" to "CI":

  * **lint_reason** — the ``reason`` argument of ``HEALTH.record`` must be
    a member of the frozen ``health.Reason`` vocabulary when written as a
    string literal, and must never be an f-string (open-ended reasons
    defeat the closed vocabulary CI greps against — canonicalize through
    ``health.canon_reason`` instead). Non-literal reasons (variables,
    calls) are allowed: ``Health.record`` validates them at runtime.
  * **lint_site** — any literal ``site=`` string (at ``HEALTH.record``,
    ``faults.inject``, the ``conv*_bias_act`` entry points, …) must name a
    site the rest of the system knows: a dispatch-ladder site, a
    calibration site from ``quant.apply`` (``CHAINS`` / ``SITE_FOR_KEY``),
    a static subsystem site, or the shape-derived ``calibrate.conv_site``
    pattern. A typo'd site silently forks the health/calibration
    namespace — events recorded under it match no CI assertion.
  * **lint_raw_indexing** — kernel files (``kernels/*.py``) must not call
    ``pl.load`` / ``pl.store``: every memory access in this repo's kernels
    goes through a declared BlockSpec so the contract checker
    (:mod:`repro.analysis.contracts`) can prove halo bounds. Raw
    element-offset loads are exactly the accesses it cannot see.
  * **lint_obs_name** — literal metric names at ``.counter(`` /
    ``.gauge(`` / ``.histogram(`` / ``.facts(`` call sites must come from
    the frozen ``obs.names.METRICS`` vocabulary, literal span names at
    ``span`` from ``obs.names.SPANS``, and
    neither may be an f-string — dynamic names fork the telemetry
    namespace the report CLI and CI assertions key on (the registry
    enforces the same at runtime; the lint moves the failure to CI).
  * **lint_ladder_key** — every ``_ladder(...)`` dispatch call must pass
    the ``key=`` dispatch-key kwarg. The runtime fault domain (DESIGN.md
    §15) attributes an in-compiled-call kernel failure back to its
    (site, rung) through that key; a ladder call without it would opt its
    kernel family out of runtime demotion silently.
  * **lint_walltime** — ``time.time()`` is banned in the repro package:
    every duration measured there (dispatch wall time, autotune
    candidate timing, serve TTFT/decode-step, train step time) must use
    the monotonic ``time.perf_counter()`` — wall-clock time jumps under
    NTP slew and produced the misleading timings PR 8 fixed. The few
    legitimate wall-clock uses (artifact timestamps compared across
    processes) are allowlisted per file in :data:`WALLCLOCK_ALLOWED`;
    ``from time import time`` is flagged too (it hides the call form
    the lint matches).
"""
from __future__ import annotations

import ast
import pathlib
import re

from repro.analysis.contracts import Violation
from repro.health import Reason
from repro.obs import names as obs_names

#: subsystem sites with no registry of their own
STATIC_SITES = {
    "autotune", "ckpt", "serve/generate", "serve/decode", "serve/slot",
    "serve/admission", "train",
}

#: dispatch-ladder sites (``ops._ladder`` callers); fault injection
#: matches hierarchically, so the bare family names are valid too
DISPATCH_SITES = {
    "conv1d", "conv2d", "conv1d_depthwise", "attention_decode", "pool1d",
    "conv1d.w8a8", "conv1d.w8a16",
    "conv2d.w8a8", "conv2d.w8a16",
    "conv1d_depthwise.w8a8", "conv1d_depthwise.w8a16",
}

#: shape-derived default sites (``calibrate.conv_site``)
CONV_SITE_RE = re.compile(r"^[a-z0-9_]+\|Cin\d+\|Cout\d+\|K[\dx]+$")

_REASON_VALUES = {r.value for r in Reason}

#: registry accessor methods whose literal first arg is a metric name
_METRIC_METHODS = {"counter", "gauge", "histogram", "facts"}

#: tracing entry points whose literal first arg is a span name
_SPAN_FUNCS = {"span"}

#: the explicit wall-clock registry: files (package-relative, posix)
#: allowed to call ``time.time()``, with the reason — these produce
#: *timestamps* (points in calendar time, compared across processes or
#: shown to operators), not durations. Everything else in the package
#: is measuring elapsed time and must use ``time.perf_counter()``.
WALLCLOCK_ALLOWED: dict[str, str] = {
    "repro/distributed/ft.py":
        "heartbeat files carry wall-clock timestamps whose staleness is "
        "compared across processes",
    "repro/checkpoint/manager.py":
        "the checkpoint manifest records an operator-facing save "
        "timestamp",
}


def known_sites() -> set[str]:
    """The full literal-site universe: static + dispatch + calibration."""
    from repro.quant import apply as qapply

    return (
        STATIC_SITES | DISPATCH_SITES
        | set(qapply.CHAINS) | set(qapply.CHAINS.values())
        | set(qapply.SITE_FOR_KEY.values())
    )


def _is_health_record(call: ast.Call) -> bool:
    f = call.func
    return (
        isinstance(f, ast.Attribute) and f.attr == "record"
        and (
            (isinstance(f.value, ast.Name) and f.value.id == "HEALTH")
            or (isinstance(f.value, ast.Attribute) and f.value.attr == "HEALTH")
        )
    )


def _str_const(node) -> str | None:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def _walltime_allowed(rel: str) -> bool:
    posix = rel.replace("\\", "/")
    return any(posix.endswith(k) for k in WALLCLOCK_ALLOWED)


class _Linter(ast.NodeVisitor):
    def __init__(
        self, rel: str, *, kernel_file: bool, sites: set[str],
        walltime_ok: bool = False,
    ):
        self.rel = rel
        self.kernel_file = kernel_file
        self.sites = sites
        self.walltime_ok = walltime_ok
        self.violations: list[Violation] = []

    def _flag(self, kind: str, node: ast.AST, detail: str) -> None:
        self.violations.append(Violation(
            kind, "lint", f"{self.rel}:{node.lineno}", detail
        ))

    def _check_site_literal(self, node: ast.AST, site: str) -> None:
        if site in self.sites or CONV_SITE_RE.match(site):
            return
        self._flag(
            "lint_site", node,
            f"site {site!r} is not in the site registry (dispatch sites, "
            f"quant.apply calibration sites, static subsystem sites, or "
            f"the calibrate.conv_site pattern) — a typo'd site forks the "
            f"health/calibration namespace",
        )

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        if (
            not self.walltime_ok and node.module == "time"
            and any(a.name == "time" for a in node.names)
        ):
            self._flag(
                "lint_walltime", node,
                "`from time import time` hides the wall-clock call from "
                "the lint — import the module and use time.perf_counter() "
                "for durations (wall clock is for allowlisted artifact "
                "timestamps only)",
            )
        self.generic_visit(node)

    def _lint_walltime(self, call: ast.Call) -> None:
        if self.walltime_ok:
            return
        f = call.func
        if (
            isinstance(f, ast.Attribute) and f.attr == "time"
            and isinstance(f.value, ast.Name) and f.value.id == "time"
        ):
            self._flag(
                "lint_walltime", call,
                "time.time() in the repro package — durations must use "
                "the monotonic time.perf_counter() (wall clock jumps "
                "under NTP slew; this is the regression class PR 8's "
                "perf_counter fix removed). Genuine timestamps belong in "
                "lint.WALLCLOCK_ALLOWED with a reason",
            )

    def _lint_ladder_key(self, call: ast.Call) -> None:
        f = call.func
        name = (
            f.id if isinstance(f, ast.Name)
            else f.attr if isinstance(f, ast.Attribute)
            else None
        )
        if name != "_ladder":
            return
        if not any(kw.arg == "key" for kw in call.keywords):
            self._flag(
                "lint_ladder_key", call,
                "_ladder(...) without key= — the dispatch key is how the "
                "runtime catch layer maps an in-compiled-call kernel "
                "failure back to its (site, rung); omitting it opts this "
                "kernel family out of runtime demotion (DESIGN.md §15)",
            )

    def visit_Call(self, call: ast.Call) -> None:
        self._lint_record(call)
        self._lint_obs_name(call)
        self._lint_walltime(call)
        self._lint_ladder_key(call)
        for kw in call.keywords:
            if kw.arg == "site":
                s = _str_const(kw.value)
                if s is not None:
                    self._check_site_literal(kw.value, s)
        if self.kernel_file and isinstance(call.func, ast.Attribute):
            f = call.func
            if (
                f.attr in ("load", "store")
                and isinstance(f.value, ast.Name) and f.value.id == "pl"
            ):
                self._flag(
                    "lint_raw_indexing", call,
                    f"pl.{f.attr}(...) bypasses the declared BlockSpecs — "
                    f"the contract checker cannot prove halo bounds for "
                    f"raw element offsets; express the access as an "
                    f"index-mapped block instead",
                )
        self.generic_visit(call)

    def _lint_obs_name(self, call: ast.Call) -> None:
        """Literal metric/span names must be in the frozen obs vocabularies
        (``obs.names``); f-string names are flagged outright. Non-literal
        names (variables, concatenation) pass — the registry validates
        those at runtime."""
        f = call.func
        vocab = kind = None
        if isinstance(f, ast.Attribute) and f.attr in _METRIC_METHODS:
            vocab, kind = obs_names.METRICS, "metric"
        elif (
            isinstance(f, ast.Name) and f.id in _SPAN_FUNCS
            or isinstance(f, ast.Attribute) and f.attr in _SPAN_FUNCS
        ):
            vocab, kind = obs_names.SPANS, "span"
        if vocab is None or not call.args:
            return
        node = call.args[0]
        if isinstance(node, ast.JoinedStr):
            self._flag(
                "lint_obs_name", node,
                f"f-string {kind} name — dynamic names fork the telemetry "
                f"namespace the obs report and CI key on; use a name from "
                f"obs.names and put the dynamic part in a label",
            )
            return
        s = _str_const(node)
        if s is not None and s not in vocab:
            self._flag(
                "lint_obs_name", node,
                f"{kind} name {s!r} is not in the frozen obs.names "
                f"vocabulary — add it there first (the obs registry "
                f"rejects it at runtime too)",
            )

    def _lint_record(self, call: ast.Call) -> None:
        if not _is_health_record(call):
            return None
        site_node = call.args[0] if call.args else None
        for kw in call.keywords:
            if kw.arg == "site":
                site_node = kw.value
        if site_node is not None:
            s = _str_const(site_node)
            if s is not None:
                self._check_site_literal(site_node, s)
        reason_node = call.args[1] if len(call.args) > 1 else None
        for kw in call.keywords:
            if kw.arg == "reason":
                reason_node = kw.value
        if reason_node is None:
            return None
        if isinstance(reason_node, ast.JoinedStr):
            self._flag(
                "lint_reason", reason_node,
                "f-string reason at HEALTH.record — open-ended reasons "
                "defeat the frozen health.Reason vocabulary; canonicalize "
                "via health.canon_reason and keep the dynamic part in "
                "detail=",
            )
            return None
        r = _str_const(reason_node)
        if r is not None and r not in _REASON_VALUES:
            self._flag(
                "lint_reason", reason_node,
                f"reason {r!r} is not in the frozen health.Reason "
                f"vocabulary — add a member there first (the runtime "
                f"check in Health.record will reject it too)",
            )
        return None


def lint_file(
    path: pathlib.Path, *, rel: str | None = None,
    sites: set[str] | None = None,
) -> list[Violation]:
    rel = rel or str(path)
    sites = known_sites() if sites is None else sites
    try:
        tree = ast.parse(path.read_text(), filename=rel)
    except SyntaxError as e:
        return [Violation("lint_syntax", "lint", rel, str(e))]
    linter = _Linter(
        rel, kernel_file="/kernels/" in path.as_posix(), sites=sites,
        walltime_ok=_walltime_allowed(rel),
    )
    linter.visit(tree)
    return linter.violations


def check_all(root: str | None = None) -> tuple[list[Violation], dict]:
    """Lint every ``.py`` under ``root`` (default: the ``repro`` package)."""
    if root is None:
        base = pathlib.Path(__file__).resolve().parent.parent
    else:
        base = pathlib.Path(root)
    sites = known_sites()
    violations: list[Violation] = []
    n = 0
    for path in sorted(base.rglob("*.py")):
        if "__pycache__" in path.parts:
            continue
        n += 1
        violations.extend(
            lint_file(path, rel=str(path.relative_to(base.parent)), sites=sites)
        )
    return violations, {"files": n, "sites": len(sites)}
