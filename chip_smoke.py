#!/usr/bin/env python3
"""Smoke test of the main serving path on one TPU, Pallas kernels compiled.

    python chip_smoke.py

Runs in one process and starts none. Phases, in order:

1. **device** — JAX's first device must be a TPU; its kind and the device
   count are printed. Without a TPU the script exits non-zero at once: it
   never carries on on the CPU.
2. **kernels** — each main-path kernel through ``repro.kernels.ops``,
   compiled (``ops.use_interpret()`` is False on the TPU), against its
   float32 oracle: whisper's frontend convs in fp (with their gradients
   through the custom VJP) and w8a8, jamba's depthwise k=4 conv over 16384
   channels, ``pool1d``, and decode attention at the qwen3-1.7b and
   whisper-medium shapes in bf16 and int8 with ragged lengths. Each line
   prints the largest error (normalised by the oracle's largest magnitude)
   beside its bound.
3. **serve** — whisper-medium at its published widths, random weights from
   ``SEED``, served through ``repro.launch.serve.generate`` (the path
   ``serve.main`` takes) with the Pallas conv frontend, an int8 KV cache and
   the fused decode kernel: batch 4, each request its own 30 s of mels
   (3000 frames), a 128-token prompt, 32 generated tokens, 3 requests.
   The prefill logits must be finite and agree with the same model on
   the pure-JAX conv path within a bf16 bound; the tokens must lie in the
   vocabulary, and the greedy requests must be bit-identical.
4. **fail loudly** — any ``HEALTH`` event (a demoted kernel, a fallback, a
   retry) or a decode-attention read served by anything but the Pallas
   kernel fails the run: on this path a demotion is a bring-up failure.

Timings (prefill, per token, compile seconds) are one smoke run, not a
benchmark. The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``;
it is printed only when every phase passed. The compile cache goes where
``JAX_COMPILATION_CACHE_DIR`` says, else to ``<checkout>/.cache/jax-compile``.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SEED = 0
# bf16 outputs round each element to 2^-8 relative; the bounds leave a few
# roundings of room, normalised by the oracle's largest magnitude
BF16_FWD = 1e-2
BF16_GRAD = 2e-2  # the backward also rounds dz and the weight gradient
ATTN = 1e-2
# The two conv paths round differently in bf16, and 48 layers of random
# weights amplify that. The bound is measured in the same run: how far the
# pure-JAX model's own logits move when its mel input is perturbed by one
# bf16 rounding (±2^-8, relative), over PERTURBATIONS draws, times a margin.
PERTURBATIONS = 2
LOGITS_MARGIN = 2.0

# whisper-medium's frontend (conv1: 80→1024, conv2: 1024→1024 stride 2)
# over a 30 s clip; jamba-1.5's Mamba conv (d_inner 16384, k=4); decode
# caches of qwen3-1.7b (4k context) and whisper cross-attention (1500
# encoder frames)
CONVS = (("conv1", 80, 1, 3000), ("conv2", 1024, 2, 3000))
DEPTHWISE = dict(B=1, L=2048, C=16384, K=4)
POOLS = (("sum", 4), ("max", 4), ("max", 64))
POOL_SHAPE = (1, 4096, 1024)
ATTN_SHAPES = (
    ("qwen3-1.7b", dict(B=8, S=4096, KV=8, G=2, D=128)),
    ("whisper-medium", dict(B=4, S=1500, KV=16, G=1, D=64)),
)
SERVE = dict(arch="whisper-medium", batch=4, frames=3000, prompt=128, gen=32,
             requests=3)


class Smoke:
    """Collects each check's line and whether it held."""

    def __init__(self):
        self.failures: list[str] = []

    def check(self, name: str, ok: bool, detail: str) -> None:
        print(f"[{'ok' if ok else 'FAIL'}] {name}: {detail}", flush=True)
        if not ok:
            self.failures.append(name)

    def error(self, name: str, got, ref, bound: float) -> None:
        """Largest |got - ref| over the largest |ref|, against ``bound``."""
        import numpy as np

        finite = bool(np.isfinite(np.asarray(got, np.float32)).all())
        err = rel_err(got, ref)
        self.check(name, finite and err <= bound,
                   f"max err {err:.3e} (bound {bound:.3e})"
                   + ("" if finite else ", NON-FINITE output"))


def rel_err(got, ref) -> float:
    import numpy as np

    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-30))


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (a persistent
    cache hit counts its read instead of the compile)."""

    def __init__(self):
        import jax

        self.seconds = 0.0

        def listen(event: str, secs: float, **_kw) -> None:
            if event.startswith("/jax/core/compile/"):
                self.seconds += secs

        jax.monitoring.register_event_duration_secs_listener(listen)


def device_phase():
    import jax

    devices = jax.devices()
    d = devices[0]
    print(f"[device] platform={d.platform} kind={d.device_kind} "
          f"count={len(devices)}", flush=True)
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU — JAX's first device is "
                 f"{d.platform!r} ({d.device_kind}); this test runs only "
                 f"on a TPU and never falls back to the CPU")
    return d, len(devices)


def kernel_phase(smoke: Smoke, *, convs=CONVS, depthwise=DEPTHWISE,
                 pools=POOLS, pool_shape=POOL_SHAPE, attn_shapes=ATTN_SHAPES):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import conv as core_conv
    from repro.kernels import attention_decode as attn_dec
    from repro.kernels import ops, ref
    from repro.models.common import quantize_kv_leaf
    from repro.quant import qconv

    rng = np.random.default_rng(SEED)
    bf16, f32 = jnp.bfloat16, jnp.float32

    def normal(shape, scale=1.0, dtype=bf16):
        return jnp.asarray(rng.normal(size=shape) * scale, dtype)

    def highest(fn, *args):
        with jax.default_matmul_precision("highest"):
            return jax.jit(fn)(*args)

    for name, cin, stride, frames in convs:
        x = normal((1, frames, cin))
        w = normal((3, cin, 1024), cin ** -0.5)
        b = normal((1024,), 0.1)
        ct = normal((1, frames // stride, 1024), dtype=f32)
        kw = dict(stride=stride, padding="SAME", activation="gelu_erf")

        def loss(x, w, b, backend="sliding"):
            y = ops.conv1d(x, w, bias=b, backend=backend, **kw)
            return (y.astype(f32) * ct).sum(), y

        grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2), has_aux=True))
        (gx, gw, gb), y = grad(x, w, b)
        (rx, rw, rb), yr = highest(
            jax.grad(lambda *a: loss(*a, backend="xla"), argnums=(0, 1, 2),
                     has_aux=True),
            x.astype(f32), w.astype(f32), b.astype(f32),
        )
        smoke.error(f"{name} fp fwd {tuple(x.shape)}", y, yr, BF16_FWD)
        for g, r, what in ((gx, rx, "dx"), (gw, rw, "dw"), (gb, rb, "db")):
            smoke.error(f"{name} fp {what}", g, r, BF16_GRAD)

        qw = qconv.quantize_weight(w)
        xs = qconv.act_scale(x)
        qkw = dict(bias=b, stride=stride, padding="SAME",
                   activation="gelu_erf")
        yq = jax.jit(lambda x, q, s, xs: ops.conv1d(
            x, q, precision="w8a8", w_scale=s, x_scale=xs, **qkw,
        ))(x, qw.q, qw.scale, xs)
        yq_ref = highest(lambda x, q, s, xs: qconv.conv1d_q(
            x, qconv.QuantizedWeight(q, s), mode="w8a8", x_scale=xs,
            accumulate="int32", out_dtype=f32, **qkw,
        ), x, qw.q, qw.scale, xs)
        smoke.error(f"{name} w8a8 fwd", yq, yq_ref, BF16_FWD)

    B, L, C, K = (depthwise[k] for k in ("B", "L", "C", "K"))
    x, w, b = normal((B, L, C)), normal((K, C), 0.5), normal((C,), 0.1)
    y = jax.jit(lambda x, w, b: ops.conv1d_depthwise(
        x, w, bias=b, activation="silu"))(x, w, b)
    yr = highest(lambda x, w, b: ops.epilogue_unfused(
        core_conv.conv1d_xla(
            jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0))), w[:, None, :],
            padding="VALID", groups=C,
        ), b, "silu",
    ), x.astype(f32), w.astype(f32), b.astype(f32))
    smoke.error(f"depthwise k{K} {(B, L, C)}", y, yr, BF16_FWD)

    x = normal(pool_shape)
    for op, window in pools:
        y = jax.jit(lambda x: ops.pool1d(x, window=window, op=op))(x)
        yr = ref.pool_ref(x.astype(f32), window=window, op=op)
        smoke.error(f"pool1d {op} w{window} {pool_shape}", y, yr, BF16_FWD)

    for model, s in attn_shapes:
        B, S, KV, G, D = (s[k] for k in ("B", "S", "KV", "G", "D"))
        q = normal((B, KV * G, D))
        k, v = normal((B, S, KV, D)), normal((B, S, KV, D))
        lengths = rng.integers(1, S + 1, size=B)
        lengths[0], lengths[-1] = S, 0  # a full slot and an empty one
        lengths = jnp.asarray(lengths, jnp.int32)
        for kind in ("bf16", "int8"):
            if kind == "int8":
                (kq, ks), (vq, vs) = quantize_kv_leaf(k), quantize_kv_leaf(v)
            else:
                kq, vq, ks, vs = k, v, None, None
            out = jax.jit(lambda q, kq, vq, ks, vs, n: ops.attention_decode(
                q, kq, vq, lengths=n, k_scale=ks, v_scale=vs,
            ))(q, kq, vq, ks, vs, lengths)
            want = highest(lambda q, kq, vq, ks, vs, n: attn_dec.
                           attention_decode_ref(
                               q.reshape(B, KV, G, D), kq, vq, ks, vs, n,
                           ), q.astype(f32), kq, vq, ks, vs, lengths)
            smoke.error(f"attention_decode {model} {kind} "
                        f"B{B} S{S} KV{KV} G{G} D{D}",
                        out, want.reshape(B, KV * G, D), ATTN)


def serve_phase(smoke: Smoke, *, arch=SERVE["arch"], cfg_overrides=None,
                batch=SERVE["batch"], frames=SERVE["frames"],
                prompt=SERVE["prompt"], gen=SERVE["gen"],
                requests=SERVE["requests"]):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import obs
    from repro.configs import get_config
    from repro.distributed.sharding import Runtime
    from repro.launch import serve
    from repro.launch.steps import make_prefill_step
    from repro.models import build_model

    cfg = get_config(arch).replace(
        conv_backend="sliding_pallas", kv_quant="int8", attn_decode="fused",
        **(cfg_overrides or {}),
    )
    model = build_model(cfg, Runtime())
    t0 = time.perf_counter()
    params = model.init(jax.random.key(SEED))
    jax.block_until_ready(params)
    n_params = sum(p.size for p in jax.tree.leaves(params))
    print(f"[serve] {cfg.name}: {cfg.encoder_layers}+{cfg.num_layers} "
          f"layers, d_model {cfg.d_model}, {n_params / 1e6:.1f}M params "
          f"({cfg.param_dtype}), init {time.perf_counter() - t0:.1f}s",
          flush=True)
    rng = np.random.default_rng(SEED)
    prompts = jnp.asarray(
        rng.integers(2, cfg.vocab_size, size=(batch, prompt)), jnp.int32
    )
    audio = jnp.asarray(rng.uniform(-1, 1, (batch, frames, 80)), jnp.float32)
    cache_len = serve.resolve_cache_len(cfg, prompt + gen, prompt, gen)

    logits, _ = serve.prefill_cache(model, params, prompts,
                                    cache_len=cache_len, gen_len=gen,
                                    audio=audio)
    smoke.check("serve prefill logits finite",
                bool(jnp.isfinite(logits).all()), str(tuple(logits.shape)))
    ref_model = build_model(cfg.replace(conv_backend="sliding"), Runtime())
    ref_prefill = jax.jit(make_prefill_step(ref_model))
    batch_in = serve.serve_batch(ref_model, prompts, audio)
    ref_logits = ref_prefill(params, batch_in)[0]
    frames = batch_in["frames"]
    floor = max(
        rel_err(ref_prefill(params, dict(batch_in, frames=frames * (
            1 + jnp.asarray(rng.choice([-1.0, 1.0], frames.shape) * 2**-8,
                            frames.dtype)
        )))[0], ref_logits)
        for _ in range(PERTURBATIONS)
    )
    print(f"[serve] bf16 floor: a ±2^-8 input perturbation moves the "
          f"pure-JAX logits by {floor:.3e}", flush=True)
    smoke.error("serve prefill logits vs pure-JAX conv path", logits,
                ref_logits, LOGITS_MARGIN * floor)

    reg = obs.REGISTRY
    prefill_h = reg.histogram("serve.prefill_s")
    step_h = reg.histogram("serve.decode_step_s")
    label = dict(arch=cfg.name)
    tokens = []
    for r in range(requests):
        p0, s0, n0 = prefill_h.sum(**label), step_h.sum(**label), \
            step_h.count(**label)
        t_req = time.perf_counter()
        toks, _done = serve.generate(model, params, prompts, gen_len=gen,
                                     cache_len=cache_len, audio=audio)
        toks = np.asarray(toks)
        wall = time.perf_counter() - t_req
        steps = step_h.count(**label) - n0
        per_tok = (step_h.sum(**label) - s0) / max(steps, 1)
        print(f"[serve] request {r}: {wall:.3f}s wall, prefill "
              f"{prefill_h.sum(**label) - p0:.4f}s, {per_tok * 1e3:.3f} "
              f"ms/token over {steps} decode steps (batch {batch})",
              flush=True)
        tokens.append(toks)
    toks = tokens[0]
    smoke.check("serve tokens in vocabulary",
                toks.shape == (batch, gen)
                and bool(((toks >= 0) & (toks < cfg.vocab_size)).all()),
                f"shape {toks.shape}, vocab {cfg.vocab_size}")
    smoke.check("serve greedy requests bit-identical",
                all(np.array_equal(t, toks) for t in tokens[1:]),
                f"{requests} requests")


def fail_loudly(smoke: Smoke) -> None:
    from repro.health import HEALTH
    from repro.kernels import ops

    events = HEALTH.summary()
    for line in events:
        print(f"[health] {line}", flush=True)
    smoke.check("no health events", not events, f"{len(events)} event(s)")
    dispatch = dict(ops.ATTN_DECODE_DISPATCH.items())
    for key, impl in sorted(dispatch.items()):
        print(f"[dispatch] {key}: {impl}", flush=True)
    smoke.check("decode attention served by the Pallas kernel",
                bool(dispatch) and set(dispatch.values()) == {"pallas"},
                f"{len(dispatch)} cache shape(s)")


def main() -> int:
    t_start = time.perf_counter()
    device, count = device_phase()
    from repro import compile_cache
    from repro.kernels import ops

    print(f"[device] compile cache: {compile_cache.enable()}", flush=True)
    clock = CompileClock()
    smoke = Smoke()
    smoke.check("kernels compiled, not interpreted", not ops.use_interpret(),
                f"use_interpret()={ops.use_interpret()}")
    for name, phase in (("kernels", kernel_phase), ("serve", serve_phase)):
        c0, t0 = clock.seconds, time.perf_counter()
        try:
            phase(smoke)
        except Exception as e:  # noqa: BLE001 — report, then fail the run
            smoke.check(f"{name} phase", False, repr(e)[:2000])
        print(f"[{name}] {time.perf_counter() - t0:.1f}s, of which compile "
              f"{clock.seconds - c0:.1f}s", flush=True)
    fail_loudly(smoke)
    print(f"[total] {time.perf_counter() - t_start:.1f}s, compile "
          f"{clock.seconds:.1f}s", flush=True)
    if smoke.failures:
        print(f"[FAIL] {len(smoke.failures)} check(s) failed: "
              + ", ".join(smoke.failures), file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": count,
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
