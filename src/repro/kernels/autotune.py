"""Shape-keyed autotuner for the sliding-conv Pallas kernels.

Per-layer primitive/tile selection is what dominates conv throughput (ZNNi,
Zlateski & Lee 2016): the best ``(tile, channel-block, regime)`` choice
depends on the layer shape, not just the filter size. This module measures
candidate configurations for a concrete call shape and persists the winner
in a JSON cache consulted by the ``repro.kernels.ops`` dispatch layer, so
tile/block choices are *measured*, not hard-coded.

Cache format (DESIGN.md §5): a JSON object mapping shape keys to config
dicts, e.g. ::

    {
      "conv1d|B1|L16384|Cin32|Cout32|K3|s1|float32": {
        "tile_l": 512, "cin_block": 0, "cout_block": 0,
        "regime": "custom", "us": 812.4, "default_us": 1103.0
      },
      "conv2d|B1|H128|W128|Cin32|Cout32|K3x3|s1x1|float32": {
        "tile_h": 16, "tile_w": 128, "cin_block": 0, "cout_block": 128,
        "regime": "custom", "us": 903.1, "default_us": 1201.7
      }
    }

``cin_block``/``cout_block`` of 0 mean "unblocked" (full channel axis).
``us``/``default_us`` record the measured winner vs the default config so
speedup trajectories survive across PRs. The cache path is
``$REPRO_AUTOTUNE_CACHE``, by default ``.cache/autotune-<device kind>.json``
under the current working directory (``default_cache_path``): timings are
only meaningful on the device that took them, so a chip run never reads
what a CPU run tuned. Writes go through a temp file + rename.

The file additionally carries a reserved ``"__schema__"`` version entry
(never returned by ``lookup``). A cache that fails to parse or was written
by an incompatible schema is **quarantined** — renamed to
``<name>.corrupt`` with a reason-coded health event — instead of silently
reset-then-overwritten, so a torn write never erases tuning history and
the operator can inspect what happened (DESIGN.md §10). A cache with no
``__schema__`` field is legacy-accepted (pre-versioning files are schema 1).
"""
from __future__ import annotations

import dataclasses
import json
import os
import re
import sys
import time
from pathlib import Path
from typing import Any, Callable, Iterable

import jax

from repro import faults
from repro.health import HEALTH
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace


# bump when the cache entry layout changes incompatibly; readers quarantine
# files stamped with a DIFFERENT version (missing field = legacy schema 1)
SCHEMA_VERSION = 1
SCHEMA_KEY = "__schema__"

# candidate axes — kept deliberately small: every candidate costs a
# recompile, and in interpret mode (CPU) a slow Python-level run.
TILE_L_CANDIDATES = (64, 128, 256, 512)
TILE_HW_CANDIDATES = ((8, 128), (16, 128), (16, 256), (32, 64))
CHANNEL_BLOCKS = (0, 64, 128)  # 0 = unblocked
# channel count above which the dispatch layer blocks channels even without
# a tuned entry (keeps the (K, Cin, Cout) weight tile VMEM-bounded)
AUTO_BLOCK_THRESHOLD = 256
AUTO_BLOCK = 128


def default_cache_path() -> Path:
    """``.cache/autotune-<device kind>.json``, e.g. ``autotune-cpu.json``
    or ``autotune-TPU_v5_lite.json``."""
    kind = re.sub(r"\W+", "_", jax.devices()[0].device_kind).strip("_")
    return Path(".cache") / f"autotune-{kind}.json"


def cache_path() -> Path:
    env = os.environ.get("REPRO_AUTOTUNE_CACHE")
    return Path(env) if env else default_cache_path()


_cache: dict[str, dict[str, Any]] | None = None
_cache_file: Path | None = None


def _quarantine(p: Path, reason: str, detail: str = "") -> None:
    """Move an unusable cache file aside (never delete: the operator may
    want the bytes) and record the event."""
    try:
        quarantined = p.with_name(p.name + ".corrupt")
        p.replace(quarantined)
        detail = detail or str(quarantined)
    except OSError:
        pass  # racing process already moved/removed it
    HEALTH.record("autotune", reason, "quarantine", detail=detail)


def _load() -> dict[str, dict[str, Any]]:
    global _cache, _cache_file
    p = cache_path()
    if _cache is None or _cache_file != p:
        _cache_file = p
        _cache = {}
        try:
            text = p.read_text()
        except OSError:
            return _cache  # no cache yet — nothing to validate
        try:
            if faults.take("autotune_corrupt"):
                raise ValueError("injected fault 'autotune_corrupt'")
            loaded = json.loads(text)
            if not isinstance(loaded, dict):
                raise ValueError(f"cache root is {type(loaded).__name__}")
        except ValueError as e:
            _quarantine(p, "cache_corrupt", detail=repr(e)[:200])
            return _cache
        schema = loaded.pop(SCHEMA_KEY, SCHEMA_VERSION)
        if schema != SCHEMA_VERSION:
            _quarantine(p, "cache_schema_mismatch",
                        detail=f"file schema {schema} != {SCHEMA_VERSION}")
            return _cache
        _cache = loaded
    return _cache


def _flush() -> None:
    p = cache_path()
    p.parent.mkdir(parents=True, exist_ok=True)
    # per-process temp name: concurrent tuners each write their own temp and
    # the atomic rename is last-writer-wins (a shared .tmp raced — one
    # process could rename a half-written file from another)
    tmp = p.parent / f".{p.name}.{os.getpid()}.tmp"
    tmp.write_text(
        json.dumps({SCHEMA_KEY: SCHEMA_VERSION, **_cache},
                   indent=1, sort_keys=True)
    )
    tmp.replace(p)


def invalidate() -> None:
    """Drop the in-memory cache (next lookup re-reads the file)."""
    global _cache
    _cache = None


def conv1d_key(B, L, Cin, Cout, K, stride, dtype, grad: bool = False) -> str:
    """Shape key; ``grad=True`` keys the backward (dw-kernel) entry so the
    cache tunes forward and backward tilings independently."""
    base = f"conv1d|B{B}|L{L}|Cin{Cin}|Cout{Cout}|K{K}|s{stride}|{dtype}"
    return base + "|grad" if grad else base


def conv2d_key(
    B, H, W, Cin, Cout, kh, kw, sh, sw, dtype, grad: bool = False
) -> str:
    base = (
        f"conv2d|B{B}|H{H}|W{W}|Cin{Cin}|Cout{Cout}"
        f"|K{kh}x{kw}|s{sh}x{sw}|{dtype}"
    )
    return base + "|grad" if grad else base


def conv1d_dw_key(B, L, C, K, stride, dtype) -> str:
    """Depthwise conv1d shape key (the mamba conv path; ``dtype`` is the
    precision name for the quantized kernels, e.g. "w8a8")."""
    return f"conv1ddw|B{B}|L{L}|C{C}|K{K}|s{stride}|{dtype}"


def attn_dec_key(B, S, KV, G, D, kind) -> str:
    """Fused decode-attention shape key (``ops.attention_decode``). ``kind``
    is "int8" for the quantized cache, else the float cache dtype name —
    the two tile very differently (int8 rows are 4× denser in VMEM)."""
    return f"attn_dec|B{B}|S{S}|KV{KV}|G{G}|D{D}|{kind}"


def pool1d_key(B, L, C, window, op, dtype) -> str:
    """Sliding-pool shape key; the tuned entry's ``method`` field selects
    the kernel evaluation (``scan`` two-phase vs ``shift`` O(n·w) loop —
    the crossover is shape-dependent, see ``autotune_pool1d``)."""
    return f"pool1d|B{B}|L{L}|C{C}|w{window}|{op}|{dtype}"


def lookup(key: str) -> dict[str, Any] | None:
    """Tuned config for a shape key, or None if never tuned."""
    return _load().get(key)


def record(key: str, config: dict[str, Any]) -> None:
    _load()[key] = config
    _flush()


# ---------------------------------------------------------------------------
# measurement
# ---------------------------------------------------------------------------

def _time_fn(fn: Callable[[], jax.Array], warmup: int = 1, iters: int = 3) -> float:
    """Median seconds per call (device-synchronized)."""
    for _ in range(warmup):
        jax.block_until_ready(fn())
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


def _blocks_for(c: int) -> list[int]:
    """Channel-block candidates that make sense for a channel count."""
    return [b for b in CHANNEL_BLOCKS if b == 0 or b < c]


@dataclasses.dataclass
class Result:
    key: str
    best: dict[str, Any]
    default_us: float
    best_us: float
    pruned: int = 0  # candidates skipped on a contract verdict, untimed
    timed: int = 0  # configs actually measured (incl. the default)
    cost_skipped: int = 0  # ranked early-exit leftovers, untimed
    ranked: bool = False  # candidates were ordered by the cost model

    @property
    def speedup(self) -> float:
        return self.default_us / self.best_us if self.best_us else 1.0


#: ranked search stops after this many consecutive candidates fail to
#: improve the best measured time (prediction order means the rest are
#: predicted even slower); 0 disables early exit
COST_PATIENCE = 3


def _cost_patience() -> int:
    return int(os.environ.get("REPRO_AUTOTUNE_PATIENCE", COST_PATIENCE))


def _contract_checker(family: str, shape: dict[str, Any]):
    """Trace-time contract verdicts for the search (``repro.analysis``):
    a candidate tile that provably exceeds the VMEM budget or indexes out
    of bounds is pruned before bench time is spent on it. The default
    config is never pruned — it is what untuned dispatch runs, so it must
    always carry a timing. Checker unavailable → no pruning (the search
    must degrade to measuring, never crash)."""

    def check(cand: dict[str, Any]):
        try:
            from repro.analysis import contracts
        except Exception:  # noqa: BLE001 — analysis layer optional here
            return None
        return contracts.check_autotune_candidate(family, shape, cand)

    return check


def _cost_model(family: str, shape: dict[str, Any]):
    """Static roofline predictions for the search (``repro.analysis``,
    DESIGN.md §13): candidates are *ranked* best-predicted-first so the
    measured-time curve is front-loaded and the search can early-exit
    once measurements stop improving on the prediction order. Same
    degradation contract as :func:`_contract_checker`: model unavailable
    → no ranking (the search must degrade to exhaustive measurement,
    never crash). ``REPRO_AUTOTUNE_COST=0`` is the kill switch."""
    if os.environ.get("REPRO_AUTOTUNE_COST", "1") == "0":
        return None

    predict = None

    def cost(cand: dict[str, Any]):
        nonlocal predict
        if predict is None:
            try:
                from repro.analysis import costmodel

                predict = costmodel.candidate_cost(family, shape)
            except Exception:  # noqa: BLE001 — analysis layer optional
                predict = False
        if not predict:
            return None
        try:
            return predict(cand)
        except Exception:  # noqa: BLE001 — a bad prior must not crash
            return None

    return cost


def _ranked(
    cands: list[dict[str, Any]],
    cost: Callable[[dict[str, Any]], float | None] | None,
) -> tuple[list[dict[str, Any]], bool]:
    """Candidates ordered by predicted time (stable), ranked=True only
    when every candidate got a finite prediction — a partially-predicted
    ordering would make the early-exit compare apples to nothing."""
    if cost is None or not cands:
        return cands, False
    preds = [cost(c) for c in cands]
    if any(p is None or not (p == p and p != float("inf")) for p in preds):
        return cands, False
    order = sorted(range(len(cands)), key=lambda i: preds[i])
    return [cands[i] for i in order], True


def _search(
    key: str,
    run: Callable[[dict[str, Any]], jax.Array],
    candidates: Iterable[dict[str, Any]],
    default: dict[str, Any],
    contract: Callable[[dict[str, Any]], Any] | None = None,
    cost: Callable[[dict[str, Any]], float | None] | None = None,
) -> Result:
    """Time candidates (cost-ranked when a model is available), persist
    the winner, return the result.

    With ``cost``, candidates are timed best-predicted-first and the
    search stops after ``COST_PATIENCE`` consecutive candidates fail to
    improve the best measured time — on a faithful prediction order the
    remainder is predicted even slower, so measuring it buys nothing
    (``ANALYSIS.json``'s per-family Spearman gate is what keeps that
    order honest). Fewer candidates timed, same winner — asserted by
    tests/test_costmodel.py and the CI autotune step. The default config
    is always timed first (it is what untuned dispatch runs).

    Observability: the whole search runs under an ``autotune.search``
    span with one ``autotune.candidate`` span per timed config (the
    candidate timings become visible on the trace timeline), and the
    per-key ``autotune.searches`` / ``candidates`` / ``pruned`` /
    ``cost_skipped`` counters land in the metrics registry
    unconditionally — a search runs once per shape, so always-on
    counting costs nothing that matters."""
    reg = obs_metrics.REGISTRY
    reg.counter("autotune.searches").inc(1.0, key=key)
    cands = [c for c in candidates if c != default]
    # prune first: the cost-ranked early exit below must not decide
    # whether a provably-bad candidate is ever looked at
    pruned = 0
    for cand in list(cands):
        verdict = contract(cand) if contract is not None else None
        if verdict is not None:
            cands.remove(cand)
            pruned += 1
            reg.counter("autotune.pruned").inc(1.0, key=key)
            print(
                f"[autotune] pruned {key} cand={cand}: "
                f"{verdict.kind} ({verdict.detail})",
                file=sys.stderr,
            )
    cands, ranked = _ranked(cands, cost)
    patience = _cost_patience() if ranked else 0
    with obs_trace.span("autotune.search", key=key):
        with obs_trace.span("autotune.candidate", key=key, cand="default"):
            default_t = _time_fn(lambda: run(default))
        reg.counter("autotune.candidates").inc(1.0, key=key)
        best_cfg, best_t = dict(default), default_t
        timed = cost_skipped = since_improve = 0
        for i, cand in enumerate(cands):
            try:
                with obs_trace.span(
                    "autotune.candidate", key=key, cand=str(cand)
                ):
                    t = _time_fn(lambda: run(cand))
            except Exception:  # candidate invalid for this shape — skip
                continue
            timed += 1
            reg.counter("autotune.candidates").inc(1.0, key=key)
            if t < best_t:
                best_cfg, best_t = dict(cand), t
                since_improve = 0
            else:
                since_improve += 1
            if patience and since_improve >= patience:
                cost_skipped = len(cands) - i - 1
                if cost_skipped:
                    reg.counter("autotune.cost_skipped").inc(
                        float(cost_skipped), key=key
                    )
                break
    best_cfg["us"] = round(best_t * 1e6, 2)
    best_cfg["default_us"] = round(default_t * 1e6, 2)
    record(key, best_cfg)
    return Result(
        key, best_cfg, default_t * 1e6, best_t * 1e6, pruned,
        timed=timed + 1, cost_skipped=cost_skipped, ranked=ranked,
    )


def autotune_conv1d(
    x: jax.Array,
    w: jax.Array,
    *,
    stride: int = 1,
    interpret: bool | None = None,
    tile_candidates: Iterable[int] | None = None,
    precision: str = "fp",
) -> Result:
    """Search tile/block/regime space for a conv1d shape; persist winner.

    ``precision`` "w8a8"/"w8a16" tunes the quantized kernel path under its
    precision-named shape key (the dtype field of the key scheme)."""
    from repro.core.conv import regime_for
    from repro.kernels import ops
    from repro.kernels.sliding_conv1d import DEFAULT_TILE_L

    B, L, Cin = x.shape
    K, _, Cout = w.shape
    dtype_key = precision if precision != "fp" else x.dtype.name
    key = conv1d_key(B, L, Cin, Cout, K, stride, dtype_key)
    out_len = (L - K) // stride + 1

    # quant tuning is PINNED to the quant path: ops.conv1d exempts calls
    # with explicit tile/block/regime arguments (every candidate here) from
    # its measured-regression fallback — otherwise a second tuning pass
    # over a persistent cache would time the float kernel and record it
    # under the quant key, disarming the very comparison it feeds. w8a8
    # additionally pre-quantizes the operands so every candidate measures
    # the kernel on identical int8 inputs (the excluded quantize-act pass
    # is one elementwise op, negligible vs the conv itself).
    kw = {}
    xx, ww = x, w
    if precision == "w8a8":
        from repro.quant import qconv

        qw = qconv.quantize_weight(w)
        sx = qconv.act_scale(x)
        xx = qconv.quantize_act(x, sx)
        ww = qw.q
        kw = dict(w_scale=qw.scale, x_scale=sx)

    def run(cfg):
        # pass blocks through verbatim: explicit 0 means force-unblocked in
        # ops (None would re-consult the cache / auto-block heuristic and
        # measure a different config than the one recorded)
        return ops.conv1d(
            xx, ww, stride=stride, backend="sliding",
            tile_l=cfg["tile_l"],
            cin_block=cfg["cin_block"],
            cout_block=cfg["cout_block"],
            regime=cfg["regime"], interpret=interpret,
            precision=precision, **kw,
        )

    tiles = [
        t for t in (tile_candidates or TILE_L_CANDIDATES) if t <= out_len
    ] or [min(DEFAULT_TILE_L, out_len)]
    regimes = {regime_for(K)}
    if K <= 8:  # small filters: tap-stacked vs unrolled is worth measuring
        regimes |= {"custom" if K in (3, 5) else "generic", "generic"}
    cands = [
        {"tile_l": t, "cin_block": ci, "cout_block": co, "regime": r}
        for t in tiles
        for ci in _blocks_for(Cin)
        for co in _blocks_for(Cout)
        for r in sorted(regimes)
    ]
    default = {
        "tile_l": min(DEFAULT_TILE_L, out_len), "cin_block": 0,
        "cout_block": 0, "regime": regime_for(K),
    }
    cshape = dict(
        B=B, L=L, Cin=Cin, Cout=Cout, K=K, stride=stride,
        precision=precision,
        dtype=x.dtype.name if precision == "fp" else "float32",
    )
    return _search(key, run, cands, default,
                   contract=_contract_checker("conv1d", cshape),
                   cost=_cost_model("conv1d", cshape))


def autotune_conv2d(
    x: jax.Array,
    w: jax.Array,
    *,
    stride: tuple[int, int] = (1, 1),
    interpret: bool | None = None,
    tile_candidates: Iterable[tuple[int, int]] | None = None,
    precision: str = "fp",
) -> Result:
    """Search tile/block space for a conv2d shape; persist winner."""
    from repro.core.conv import regime_for
    from repro.kernels import ops
    from repro.kernels.sliding_conv2d import DEFAULT_TILE_H, DEFAULT_TILE_W

    B, H, W, Cin = x.shape
    kh, kw, _, Cout = w.shape
    dtype_key = precision if precision != "fp" else x.dtype.name
    key = conv2d_key(B, H, W, Cin, Cout, kh, kw, *stride, dtype_key)
    oh = (H - kh) // stride[0] + 1
    ow = (W - kw) // stride[1] + 1

    def run(cfg):
        # blocks verbatim — see autotune_conv1d.run
        return ops.conv2d(
            x, w, stride=stride, backend="sliding",
            tile_h=cfg["tile_h"], tile_w=cfg["tile_w"],
            cin_block=cfg["cin_block"],
            cout_block=cfg["cout_block"],
            regime=cfg["regime"], interpret=interpret,
            precision=precision,
        )

    regime = "custom" if (kh == kw and kh in (3, 5)) else regime_for(kw)
    cands = [
        {"tile_h": th, "tile_w": tw, "cin_block": ci, "cout_block": co,
         "regime": regime}
        for th, tw in (tile_candidates or TILE_HW_CANDIDATES)
        if th <= oh * 2 and tw <= ow * 2
        for ci in _blocks_for(Cin)
        for co in _blocks_for(Cout)
    ]
    default = {
        "tile_h": min(DEFAULT_TILE_H, oh), "tile_w": min(DEFAULT_TILE_W, ow),
        "cin_block": 0, "cout_block": 0, "regime": regime,
    }
    cshape = dict(
        B=B, H=H, W=W, Cin=Cin, Cout=Cout, kh=kh, kw=kw, stride=stride,
        precision=precision,
        dtype=x.dtype.name if precision == "fp" else "float32",
    )
    return _search(key, run, cands, default,
                   contract=_contract_checker("conv2d", cshape),
                   cost=_cost_model("conv2d", cshape))


def autotune_conv1d_depthwise(
    x: jax.Array,
    w: jax.Array,
    *,
    stride: int = 1,
    interpret: bool | None = None,
    tile_candidates: Iterable[int] | None = None,
    precision: str = "w8a8",
) -> Result:
    """Search tile/block space for the quantized depthwise conv1d kernel;
    persists the winner under the ``conv1ddw|…|<precision>`` key."""
    from repro.kernels import ops
    from repro.kernels.sliding_conv1d import DEFAULT_TILE_L

    B, L, C = x.shape
    K = w.shape[0]
    key = conv1d_dw_key(B, L, C, K, stride, precision)
    out_len = (L - K) // stride + 1

    def run(cfg):
        return ops.conv1d_depthwise(
            x, w, stride=stride, padding="VALID", tile_l=cfg["tile_l"],
            c_block=cfg["c_block"], interpret=interpret, precision=precision,
        )

    tiles = [
        t for t in (tile_candidates or TILE_L_CANDIDATES) if t <= out_len
    ] or [min(DEFAULT_TILE_L, out_len)]
    cands = [
        {"tile_l": t, "c_block": cb}
        for t in tiles
        for cb in _blocks_for(C)
    ]
    default = {"tile_l": min(DEFAULT_TILE_L, out_len), "c_block": 0}
    cshape = dict(
        B=B, L=L, C=C, K=K, stride=stride, precision=precision,
        dtype="float32",
    )
    return _search(key, run, cands, default,
                   contract=_contract_checker("conv1d_depthwise", cshape),
                   cost=_cost_model("conv1d_depthwise", cshape))


def autotune_attention_decode(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    lengths: jax.Array | None = None,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    impl: str | None = None,
    interpret: bool | None = None,
    block_candidates: Iterable[int] | None = None,
) -> Result:
    """Search the fused decode-attention tiling (kv_seq block size ×
    KV-head grouping) for a cache shape; persist the winner under the
    ``attn_dec|…`` key consulted by ``ops.attention_decode``.

    q: (B, H, D); k/v: (B, S, KV, D) (int8 with scale rows, or float).
    The timed call is the dispatched impl — the compiled blocked-scan path
    on CPU (where ``block_s`` controls the scan tile) and the Pallas
    kernel on TPU (where ``h_block`` also matters)."""
    import jax.numpy as jnp

    from repro.kernels import attention_decode as attn_dec
    from repro.kernels import ops

    B, H, D = q.shape
    S, KV = k.shape[1], k.shape[2]
    kind = "int8" if k.dtype == jnp.int8 else k.dtype.name
    key = attn_dec_key(B, S, KV, H // KV, D, kind)
    if lengths is None:
        lengths = jnp.full((B,), S, jnp.int32)

    def run(cfg):
        return ops.attention_decode(
            q, k, v, lengths=lengths, k_scale=k_scale, v_scale=v_scale,
            impl=impl, block_s=cfg["block_s"], h_block=cfg["h_block"],
            interpret=interpret,
        )

    tiles = sorted(
        {
            t for t in (block_candidates or attn_dec.BLOCK_S_CANDIDATES)
            if t < S
        }
        | {S}  # single-block: the whole cache in one pass (CPU winner)
    )
    # h_block only exists on the Pallas kernel; the compiled jax path
    # ignores it, so searching both values there would just time the
    # identical computation twice and persist noise
    resolved_impl = impl or (
        "pallas" if jax.default_backend() == "tpu" else "jax"
    )
    hbs = sorted({1, KV}) if resolved_impl == "pallas" else [1]
    cands = [
        {"block_s": t, "h_block": hb} for t in tiles for hb in hbs
    ]
    # the speedup baseline mirrors what an UNTUNED ops.attention_decode
    # would actually run for this impl (single block on the jax path,
    # DEFAULT_BLOCK_S tiles on pallas) — else the recorded
    # speedup_vs_default claims a win over a config dispatch never uses
    default_bs = (
        S if resolved_impl != "pallas" else min(attn_dec.DEFAULT_BLOCK_S, S)
    )
    default = {"block_s": default_bs, "h_block": KV}
    cshape = dict(B=B, S=S, KV=KV, G=H // KV, D=D, kind=kind)
    return _search(key, run, cands, default,
                   contract=_contract_checker("attention_decode", cshape),
                   cost=_cost_model("attention_decode", cshape))


def autotune_pool1d(
    x: jax.Array,
    *,
    window: int,
    op: str = "max",
    interpret: bool | None = None,
) -> Result:
    """Measure the pooling kernel's evaluation methods for a shape and
    persist the winner's ``method``. For max pooling the two candidates are
    the van Herk / Gil-Werman two-phase scan (O(n), window-independent) and
    the shift-and-max loop (O(n·w) but lower constant) — the shift form
    wins for small windows and loses from w≈64 up (the BENCH pool/w256 row
    showed the hardcoded choice losing 1.4×), so the backend is selected
    per window size from this cache instead of being hardcoded."""
    from repro.kernels import ops

    B, L, C = x.shape
    key = pool1d_key(B, L, C, window, op, x.dtype.name)

    def run(cfg):
        return ops.pool1d(
            x, window=window, op=op, method=cfg["method"],
            interpret=interpret,
        )

    methods = ["scan", "shift"] if op == "max" else ["scan"]
    default = {"method": methods[0]}
    return _search(key, run, [{"method": m} for m in methods], default)


# ---------------------------------------------------------------------------
# backward (training) tuning — fwd+bwd timed together, winner recorded under
# the |grad shape key consulted by the custom-VJP dw-kernel dispatch
# ---------------------------------------------------------------------------

def autotune_conv1d_grad(
    x: jax.Array,
    w: jax.Array,
    *,
    stride: int = 1,
    interpret: bool | None = None,
    tile_candidates: Iterable[int] | None = None,
) -> Result:
    """Search the backward dw-kernel tile for a conv1d shape (times one
    fwd+bwd through ``jax.grad``); persists the winner under the grad key."""
    from repro.kernels import ops
    from repro.kernels.sliding_conv1d import DEFAULT_TILE_L

    B, L, Cin = x.shape
    K, _, Cout = w.shape
    key = conv1d_key(B, L, Cin, Cout, K, stride, x.dtype.name, grad=True)
    out_len = (L - K) // stride + 1

    def run(cfg):
        def f(xx, ww):
            return ops.conv1d(
                xx, ww, stride=stride, backend="sliding",
                bwd_tile_l=cfg["tile_l"], interpret=interpret,
            ).sum()

        return jax.grad(f, argnums=(0, 1))(x, w)

    tiles = [
        t for t in (tile_candidates or TILE_L_CANDIDATES) if t <= out_len
    ] or [min(DEFAULT_TILE_L, out_len)]
    default = {"tile_l": min(DEFAULT_TILE_L, out_len)}
    cshape = dict(B=B, L=L, Cin=Cin, Cout=Cout, K=K, stride=stride)
    return _search(key, run, [{"tile_l": t} for t in tiles], default,
                   cost=_cost_model("conv1d_bwd_dw", cshape))


def autotune_conv2d_grad(
    x: jax.Array,
    w: jax.Array,
    *,
    stride: tuple[int, int] = (1, 1),
    interpret: bool | None = None,
    tile_candidates: Iterable[tuple[int, int]] | None = None,
) -> Result:
    """Search the backward dw-kernel tiles for a conv2d shape."""
    from repro.kernels import ops
    from repro.kernels.sliding_conv2d import DEFAULT_TILE_H, DEFAULT_TILE_W

    B, H, W, Cin = x.shape
    kh, kw, _, Cout = w.shape
    key = conv2d_key(B, H, W, Cin, Cout, kh, kw, *stride, x.dtype.name,
                     grad=True)
    oh = (H - kh) // stride[0] + 1
    ow = (W - kw) // stride[1] + 1

    def run(cfg):
        def f(xx, ww):
            return ops.conv2d(
                xx, ww, stride=stride, backend="sliding",
                bwd_tile_h=cfg["tile_h"], bwd_tile_w=cfg["tile_w"],
                interpret=interpret,
            ).sum()

        return jax.grad(f, argnums=(0, 1))(x, w)

    cands = [
        {"tile_h": th, "tile_w": tw}
        for th, tw in (tile_candidates or TILE_HW_CANDIDATES)
        if th <= oh * 2 and tw <= ow * 2
    ]
    default = {
        "tile_h": min(DEFAULT_TILE_H, oh), "tile_w": min(DEFAULT_TILE_W, ow),
    }
    cshape = dict(B=B, H=H, W=W, Cin=Cin, Cout=Cout, kh=kh, kw=kw,
                  stride=stride)
    return _search(key, run, cands, default,
                   cost=_cost_model("conv2d_bwd_dw", cshape))
