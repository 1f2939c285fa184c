"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--autotune] [--grad]
        [--quant] [--serve]

Prints ``name,us_per_call,derived`` CSV and writes ``BENCH_conv.json``
(name → us_per_call) alongside it so the perf trajectory is machine-
trackable across PRs:
  fig1/*      paper Fig. 1 — 2-D conv speedup (sliding vs im2col+GEMM)
  fig2/*      paper Fig. 2 — 2-D conv arithmetic throughput vs filter size
  conv1d/*    companion 1-D sliding conv speedup table + pooling scan claim
  roofline/*  per-(arch×shape) dominant roofline term from the dry-run JSONs
  autotune/*  (--autotune) best-vs-default tile/block search per shape
  grad/*      (--grad) fwd+bwd (training) timings for the fig1/fig2/conv1d
              shapes — sliding vs im2col through ``jax.value_and_grad``
  quant/*     (--quant) int8 PTQ inference (repro.quant) vs bf16 vs f32
              sliding, and vs int8 im2col — the paper's conclusion claim
              that compression methods compose with the technique
  serve/*     (--serve) smoke-config decode-step time per cache variant:
              fp cache, int8 cache with the dequant-view read (kv8), and
              the fused flash read over resident int8 codes (kv8_fused) —
              plus est. HBM bytes per attention read and a greedy-tokens-
              match check across all three

``--autotune`` runs the shape-keyed search (``repro.kernels.autotune``) over
every fig1/fig2/conv1d conv shape, persists winners in the JSON tuning cache
consulted by ``repro.kernels.ops``, and reports best-vs-default speedup.

``--grad`` times one loss + gradient evaluation (compiled pure-JAX sliding
vs im2col backends — the wall-clock-meaningful comparison on CPU; the
Pallas custom-VJP kernels share the same algorithmic structure and are
validated against these in interpret mode by ``tests/test_grads.py``).

``--quant`` times the compiled pure-JAX quantized evaluations
(``repro.quant.qconv`` fast path: int8 operands dequantized at the matmul
inputs — XLA CPU has no native int8 GEMM, so int8 buys 4× smaller operand
traffic and the fast f32 GEMM instead of bf16's convert-heavy path;
activation quantization is ON the clock). The Pallas int8 kernels carry
the true int8×int8→int32 contract and are validated in interpret mode by
``tests/test_quant.py``.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

BENCH_JSON = Path("BENCH_conv.json")


def autotune_rows(quick: bool) -> list[str]:
    import numpy as np
    import jax.numpy as jnp

    from benchmarks import fig1_speedup, fig2_throughput, table_conv1d
    from repro.kernels import autotune

    rng = np.random.default_rng(0)
    rows = []

    def fmt(result):
        c = result.best
        blocks = f"ci{c['cin_block']}_co{c['cout_block']}"
        tile = (
            f"tl{c['tile_l']}" if "tile_l" in c
            else f"th{c['tile_h']}_tw{c['tile_w']}"
        )
        return (
            f"best={tile}_{blocks}_{c['regime']} "
            f"speedup_vs_default={result.speedup:.2f}x"
        )

    # 2-D shapes: fig1 (128²) and fig2 (96²) filter sweeps
    for h, cin, sizes in (
        (fig1_speedup.H, fig1_speedup.CIN,
         [3, 9, 31] if quick else fig1_speedup.FILTER_SIZES),
        (fig2_throughput.H, fig2_throughput.CIN,
         [3, 17] if quick else fig2_throughput.SIZES),
    ):
        x = jnp.asarray(rng.normal(size=(1, h, h, cin)).astype(np.float32))
        for k in sizes:
            w = jnp.asarray(
                rng.normal(size=(k, k, cin, cin)).astype(np.float32)
            )
            r = autotune.autotune_conv2d(x, w)
            rows.append(
                f"autotune/conv2d_{h}x{h}_k{k},{r.best_us:.1f},{fmt(r)}"
            )
    # 1-D shapes: the conv1d table sweep
    L, C = table_conv1d.L, table_conv1d.C
    if quick:
        L = 4096  # quick mode: interpret-mode grids get expensive at 16k
    x = jnp.asarray(rng.normal(size=(1, L, C)).astype(np.float32))
    for k in [3, 33] if quick else table_conv1d.WIDTHS:
        w = jnp.asarray(rng.normal(size=(k, C, C)).astype(np.float32))
        r = autotune.autotune_conv1d(x, w)
        rows.append(f"autotune/conv1d_L{L}_k{k},{r.best_us:.1f},{fmt(r)}")
        # the quant key for the same shape: with BOTH keys measured, the
        # ops.conv1d dispatch can fall back to the faster precision path
        # for shapes where 1-D int8 regresses (per-tap accumulator-bound)
        rq = autotune.autotune_conv1d(x, w, precision="w8a8")
        rows.append(
            f"autotune/conv1d_L{L}_k{k}_w8a8,{rq.best_us:.1f},"
            f"{fmt(rq)} vs_fp={r.best_us / rq.best_us:.2f}x"
        )
    # max-pool evaluation method (scan vs shift): the crossover is
    # window-dependent — tuned entries feed ops.pool1d's backend selection
    xp = jnp.asarray(rng.normal(size=(1, L, C)).astype(np.float32))
    for wdw in [4, 256] if quick else [4, 16, 64, 256]:
        r = autotune.autotune_pool1d(xp, window=wdw, op="max")
        rows.append(
            f"autotune/pool1d_L{L}_w{wdw},{r.best_us:.1f},"
            f"best={r.best['method']} speedup_vs_default={r.speedup:.2f}x"
        )
    # fused decode-attention tiling (kv_seq block × head grouping) at the
    # qwen3 serving cache shape — feeds ops.attention_decode's dispatch
    from repro.optim.compress import quantize_int8

    # the shape serve_rows/CI actually decode at (qwen3 smoke, cache 2048)
    # so the persisted entry is the one dispatch consults there
    Bq, Sq, KVq, Gq, Dq = 2, 2048, 2, 2, 32
    qd = jnp.asarray(
        rng.normal(size=(Bq, KVq * Gq, Dq)).astype(np.float32)
    )
    kd = jnp.asarray(rng.normal(size=(Bq, Sq, KVq, Dq)).astype(np.float32))
    vd = jnp.asarray(rng.normal(size=(Bq, Sq, KVq, Dq)).astype(np.float32))
    kq8, ks8 = quantize_int8(kd)
    vq8, vs8 = quantize_int8(vd)
    r = autotune.autotune_attention_decode(
        qd, kq8, vq8, k_scale=ks8, v_scale=vs8,
        block_candidates=(256,) if quick else None,
    )
    rows.append(
        f"autotune/attn_dec_S{Sq}_int8,{r.best_us:.1f},"
        f"best=bs{r.best['block_s']}_hb{r.best['h_block']} "
        f"speedup_vs_default={r.speedup:.2f}x"
    )
    return rows


def grad_rows(quick: bool) -> list[str]:
    """fwd+bwd timings for the fig1/fig2/conv1d shapes (``grad/*`` rows)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import fig1_speedup, fig2_throughput, table_conv1d
    from benchmarks.common import row, time_fn
    from repro.core import conv1d_im2col, conv1d_sliding, conv2d_im2col, conv2d_sliding

    rng = np.random.default_rng(0)
    rows = []

    def timed_grad(fn, x, w):
        f = jax.jit(
            jax.value_and_grad(
                lambda xx, ww: jnp.sum(fn(xx, ww, padding="VALID")),
                argnums=(0, 1),
            )
        )
        return time_fn(f, x, w)

    # 2-D: fig1 (128²) and fig2 (96²) sweeps
    for fig, h, cin, sizes in (
        ("fig1", fig1_speedup.H, fig1_speedup.CIN,
         [3, 9, 31] if quick else fig1_speedup.FILTER_SIZES),
        ("fig2", fig2_throughput.H, fig2_throughput.CIN,
         [3, 17] if quick else fig2_throughput.SIZES),
    ):
        x = jnp.asarray(rng.normal(size=(1, h, h, cin)).astype(np.float32))
        for k in sizes:
            w = jnp.asarray(
                rng.normal(size=(k, k, cin, cin)).astype(np.float32)
            )
            t_s = timed_grad(conv2d_sliding, x, w)
            t_g = timed_grad(conv2d_im2col, x, w)
            rows.append(row(
                f"grad/{fig}_conv2d_k{k}_sliding", t_s,
                f"speedup={t_g / t_s:.2f}x",
            ))
            rows.append(row(f"grad/{fig}_conv2d_k{k}_im2col", t_g, ""))
    # 1-D: the conv1d table sweep
    L = 4096 if quick else table_conv1d.L
    C = table_conv1d.C
    x = jnp.asarray(rng.normal(size=(1, L, C)).astype(np.float32))
    for k in [3, 33] if quick else table_conv1d.WIDTHS:
        w = jnp.asarray(rng.normal(size=(k, C, C)).astype(np.float32))
        t_s = timed_grad(conv1d_sliding, x, w)
        t_g = timed_grad(conv1d_im2col, x, w)
        rows.append(row(
            f"grad/conv1d_L{L}_k{k}_sliding", t_s,
            f"speedup={t_g / t_s:.2f}x",
        ))
        rows.append(row(f"grad/conv1d_L{L}_k{k}_im2col", t_g, ""))
    return rows


def _race(fns: dict, iters: int = 8) -> dict:
    """Interleaved min-of-N seconds per candidate. The quant rows are
    precision *comparisons*, so candidates are timed round-robin (back-to-
    back sequential medians inherit multi-second machine-load drift and
    have produced 3× swings on this box) and min is taken — the standard
    noise-robust estimator when the quantity of interest is a ratio."""
    import time as _time

    import jax

    for fn, args in fns.values():
        jax.block_until_ready(fn(*args))
        jax.block_until_ready(fn(*args))
    best = {name: float("inf") for name in fns}
    for _ in range(iters):
        for name, (fn, args) in fns.items():
            t0 = _time.perf_counter()
            jax.block_until_ready(fn(*args))
            best[name] = min(best[name], _time.perf_counter() - t0)
    return best


def quant_rows(quick: bool) -> list[str]:
    """int8 PTQ rows (``quant/*``): int8 vs bf16 vs f32 sliding + int8
    im2col, on the fig1 2-D sweep and the conv1d table sweep. Activation
    quantization is ON the int8 clock (weights are pre-quantized, as in
    serving)."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import fig1_speedup, table_conv1d
    from benchmarks.common import row
    from repro import quant
    from repro.core import conv1d_sliding, conv2d_sliding

    rng = np.random.default_rng(0)
    rows = []

    def emit(name, t, t_col=None):
        rows.append(row(
            f"{name}_int8_sliding", t["int8"],
            f"speedup_vs_bf16={t['bf16'] / t['int8']:.2f}x "
            f"speedup_vs_f32={t['f32'] / t['int8']:.2f}x",
        ))
        rows.append(row(f"{name}_bf16_sliding", t["bf16"], ""))
        rows.append(row(f"{name}_f32_sliding", t["f32"], ""))
        if t_col is not None:
            rows.append(row(
                f"{name}_int8_im2col", t_col,
                f"sliding_vs_im2col={t_col / t['int8']:.2f}x",
            ))

    # 2-D: the fig1 128² sweep (k=5 is the acceptance shape; k=31 runs the
    # int8 compound regime — chunked reduction, no unrolled-tap fallback)
    h, cin = fig1_speedup.H, fig1_speedup.CIN
    x = jnp.asarray(rng.normal(size=(1, h, h, cin)).astype(np.float32))
    sx = quant.act_scale(x)
    for k in [3, 5, 9, 31] if quick else fig1_speedup.FILTER_SIZES:
        w = jnp.asarray(rng.normal(size=(k, k, cin, cin)).astype(np.float32))
        qw = quant.quantize_weight(w, sx)
        i8 = jax.jit(functools.partial(
            quant.conv2d_q, qw=qw, mode="w8a8", accumulate="fast"
        ))
        i8_col = jax.jit(functools.partial(
            quant.conv2d_q_im2col, qw=qw, x_scale=sx, accumulate="fast"
        ))
        bf = jax.jit(functools.partial(conv2d_sliding, padding="VALID"))
        t = _race({
            "int8": (i8, (x,)),
            "col": (i8_col, (x,)),
            "bf16": (bf, (x.astype(jnp.bfloat16), w.astype(jnp.bfloat16))),
            "f32": (bf, (x, w)),
        })
        emit(f"quant/fig1_conv2d_k{k}", t, t["col"])
    # 1-D: the conv1d table sweep
    L = 4096 if quick else table_conv1d.L
    C = table_conv1d.C
    x = jnp.asarray(rng.normal(size=(1, L, C)).astype(np.float32))
    sx = quant.act_scale(x)
    for k in [3, 33] if quick else table_conv1d.WIDTHS:
        w = jnp.asarray(rng.normal(size=(k, C, C)).astype(np.float32))
        qw = quant.quantize_weight(w, sx)
        i8 = jax.jit(functools.partial(
            quant.conv1d_q, qw=qw, mode="w8a8", accumulate="fast"
        ))
        bf = jax.jit(functools.partial(conv1d_sliding, padding="VALID"))
        t = _race({
            "int8": (i8, (x,)),
            "bf16": (bf, (x.astype(jnp.bfloat16), w.astype(jnp.bfloat16))),
            "f32": (bf, (x, w)),
        })
        emit(f"quant/conv1d_L{L}_k{k}", t)
    return rows


def serve_rows(quick: bool) -> list[str]:
    """``serve/*`` rows: smoke-config **decode-step** wall time per cache
    variant — fp cache (fused read), int8 cache with the PR-4 dequant-view
    read (``attn_decode="view"``, the ``_kv8`` baseline rows), and the
    fused flash read over resident int8 codes (``_kv8_fused``, DESIGN.md
    §9). Candidates are timed interleaved (``_race``) because the rows are
    ratios; each row carries the est. HBM bytes the attention read moves
    per step (int8 storage vs the f32 view's extra write+read) and a
    tokens-match check (greedy output must be identical across all three).
    The cache is sized well past prompt+gen — decode reads the whole
    static cache every step, which is the traffic being measured."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.common import row
    from repro.configs import get_config, smoke_config
    from repro.distributed.sharding import ParamDef, Runtime
    from repro.launch import serve as S
    from repro.models import build_model

    rows = []
    B, P, G = 2, 16, 8

    def audio_for(cfg):
        """Each request's mels for an audio model (4P frames), else None."""
        if cfg.family != "audio":
            return None
        rng = np.random.default_rng(1)
        return jnp.asarray(rng.uniform(-1, 1, (B, 4 * P, 80)), jnp.float32)

    def kv_read_bytes(model, cfg, cache_len, view: bool) -> int:
        """Bytes the per-step attention read moves: the kv_seq-axis cache
        leaves as stored, plus — on the dequant-view path — the float
        view of the int8 code leaves it materializes (write + read)."""
        import math

        total = 0
        for d in jax.tree.leaves(
            model.cache_defs(B, cache_len, **S.cache_kw(audio_for(cfg))),
            is_leaf=lambda x: isinstance(x, ParamDef),
        ):
            if "kv_seq" not in d.axes:
                continue
            n = math.prod(d.shape)
            total += n * jnp.dtype(d.dtype or cfg.param_dtype).itemsize
            if view and d.dtype == "int8":
                fsize = jnp.dtype(cfg.compute_dtype).itemsize
                total += 2 * n * fsize  # materialize + re-read the view
        return total

    def prep(arch, cache_len, kvq, attn):
        cfg = smoke_config(get_config(arch)).replace(
            kv_quant=kvq, attn_decode=attn
        )
        model = build_model(cfg, Runtime())
        params = model.init(jax.random.key(0))
        rng = np.random.default_rng(0)
        prompts = jnp.asarray(
            rng.integers(2, cfg.vocab_size, size=(B, P)), jnp.int32
        )
        audio = audio_for(cfg)
        toks, _ = S.generate(
            model, params, prompts, gen_len=G, cache_len=cache_len,
            audio=audio,
        )
        logits, cache = S.prefill_cache(
            model, params, prompts, cache_len=cache_len, gen_len=G,
            audio=audio,
        )
        decode = S._jitted(model)[1]
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        step = (decode, (params, cache, tok, jnp.int32(P)))
        return cfg, model, np.asarray(toks), step

    variants = (
        ("fp", "fp", "fused"),
        ("kv8", "int8", "view"),
        ("kv8_fused", "int8", "fused"),
    )
    archs = [("qwen3", "qwen3-1.7b", 2048)]
    if not quick:
        archs += [
            ("whisper", "whisper-medium", 512),
            ("jamba", "jamba-1.5-large-398b", 512),
        ]
    for name, arch, cache_len in archs:
        state = {
            tag: prep(arch, cache_len, kvq, attn)
            for tag, kvq, attn in variants
        }
        times = _race({t: st[3] for t, st in state.items()}, iters=30)
        toks = {t: st[2] for t, st in state.items()}
        # tokens_match is the fused-read acceptance property (same int8
        # cache, fused vs view read); match_fp reports the int8 cache's
        # own greedy drift vs the float cache (quantization error — can
        # legitimately flip an argmax at long cache lengths)
        match = bool((toks["kv8_fused"] == toks["kv8"]).all())
        match_fp = bool((toks["kv8"] == toks["fp"]).all())
        nbytes, rbytes = {}, {}
        for (tag, kvq, attn), (cfg, model, _, _step) in zip(
            variants, state.values()
        ):
            clen = S.resolve_cache_len(cfg, cache_len, P, G)
            nbytes[tag] = S.cache_nbytes(
                model.cache_defs(B, clen, **S.cache_kw(audio_for(cfg))),
                cfg.param_dtype,
            )
            rbytes[tag] = kv_read_bytes(model, cfg, clen, attn == "view")
        rows.append(row(
            f"serve/{name}_smoke_decode_fp", times["fp"],
            # metric marker: since PR 5 these rows time ONE decode step
            # (interleaved min), not whole-generate/(B·G) as in PR 4 —
            # cross-PR diffs of BENCH_conv.json must not read the
            # methodology change as a perf change
            f"metric=min_decode_step cache_bytes={nbytes['fp']} "
            f"read_bytes_step={rbytes['fp']}",
        ))
        rows.append(row(
            f"serve/{name}_smoke_decode_kv8", times["kv8"],
            f"cache_bytes={nbytes['kv8']} "
            f"read_bytes_step={rbytes['kv8']} "
            f"bytes_ratio={nbytes['fp'] / nbytes['kv8']:.2f}x "
            f"tokens_match_fp={match_fp}",
        ))
        rows.append(row(
            f"serve/{name}_smoke_decode_kv8_fused", times["kv8_fused"],
            f"cache_bytes={nbytes['kv8_fused']} "
            f"read_bytes_step={rbytes['kv8_fused']} "
            f"read_ratio_vs_view={rbytes['kv8'] / rbytes['kv8_fused']:.2f}x "
            f"speedup_vs_kv8={times['kv8'] / times['kv8_fused']:.2f}x "
            f"speedup_vs_fp={times['fp'] / times['kv8_fused']:.2f}x "
            f"tokens_match={match}",
        ))
    return rows


def _provenance() -> dict:
    """``__meta__`` header for BENCH_conv.json: enough to know what
    machine/toolchain produced the numbers, plus the obs registry
    snapshot (per-autotune-key dispatch call counts) so a perf
    regression can be traced to WHICH kernels actually ran."""
    import jax

    from repro import obs

    dev = jax.devices()[0]
    return {
        "bench_schema": 2,
        "jax": jax.__version__,
        "device_platform": dev.platform,
        "device_kind": dev.device_kind,
        "argv": sys.argv[1:],
        "obs": obs.REGISTRY.snapshot(),
    }


def main() -> None:
    quick = "--quick" in sys.argv
    tune = "--autotune" in sys.argv
    grad = "--grad" in sys.argv
    qnt = "--quant" in sys.argv
    srv = "--serve" in sys.argv
    from repro import compile_cache

    compile_cache.enable()
    from benchmarks import fig1_speedup, fig2_throughput, roofline_report, table_conv1d

    rows: list[str] = []
    rows += fig1_speedup.run(
        filter_sizes=[3, 5, 9, 17, 31] if quick else fig1_speedup.FILTER_SIZES
    )
    rows += fig2_throughput.run(
        sizes=[3, 9, 17] if quick else fig2_throughput.SIZES
    )
    rows += table_conv1d.run(widths=[3, 9, 33] if quick else table_conv1d.WIDTHS)
    try:
        rows += roofline_report.csv_rows(roofline_report.load_cells())
    except FileNotFoundError:
        rows.append("roofline/missing,0.0,run repro.launch.dryrun first")
    if tune:
        rows += autotune_rows(quick)
    if grad:
        rows += grad_rows(quick)
    if qnt:
        rows += quant_rows(quick)
    if srv:
        rows += serve_rows(quick)
    print("name,us_per_call,derived")
    for r in rows:
        print(r)
    # machine-readable mirror of the CSV: {name: us_per_call}, plus a
    # "__meta__" provenance header (sorts first; perf-diff tooling keys
    # start with fig/conv/... so the header never collides with a row)
    bench = {"__meta__": _provenance()}
    for r in rows:
        name, us, _ = r.split(",", 2)
        bench[name] = float(us)
    BENCH_JSON.write_text(json.dumps(bench, indent=1, sort_keys=True))
    print(f"# wrote {BENCH_JSON}", file=sys.stderr)
    if tune:
        from repro.kernels import autotune

        print(f"# tuning cache: {autotune.cache_path()}", file=sys.stderr)


if __name__ == "__main__":
    main()
