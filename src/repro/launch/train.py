"""End-to-end training driver (runs on CPU for the examples; the same code
path drives the production mesh — the dry-run compiles this exact step).

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b --smoke \
        --steps 50 --batch 8 --seq 256 --run-dir /tmp/run

Features: deterministic resumable data, auto-resume from the latest atomic
checkpoint, async checkpointing every ``--ckpt-every``, straggler watchdog,
bounded-restart wrapper, optional int8 error-feedback gradient compression
over the DP axes (``--grad-compress``, multi-device meshes).
"""
from __future__ import annotations

import argparse
import contextlib
import os
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache, faults, obs
from repro.checkpoint import CheckpointManager
from repro.configs import get_config, smoke_config
from repro.data import SyntheticLMData, make_batch_iterator
from repro.distributed.ft import RestartPolicy, StepWatchdog, beat
from repro.health import HEALTH, Reason, canon_reason
from repro.distributed.sharding import Runtime
from repro.launch.steps import make_train_step
from repro.models import build_model
from repro.optim import OptConfig, init_opt_state


# Per-step modality streams: tags keep the frames/patches streams disjoint
# from each other and from the token pipeline's SeedSequence([seed, row]).
_TAG_FRAMES = 1_000_003
_TAG_PATCHES = 1_000_033

#: runtime (in-compiled-call) demotions one step may absorb before its
#: failure propagates to the restart wrapper (each one re-jits the step)
_MAX_RUNTIME_DEMOTIONS_PER_STEP = 4


def step_stream(seed: int, step: int, tag: int) -> np.random.Generator:
    """RNG that is a pure function of (seed, step) — resumed runs replay the
    exact modality inputs an uninterrupted run saw at every step (a
    process-lifetime generator diverges after restart: the resumed process
    draws its step-N sample from a fresh stream position)."""
    return np.random.default_rng(np.random.SeedSequence([seed, tag, step]))


def build_batch_extras(cfg, B, rng):
    """Synthetic modality inputs for vlm archs (one draw per step)."""
    extras = {}
    if cfg.family == "vlm":
        extras["patches"] = jnp.asarray(
            rng.normal(size=(B, cfg.num_patches, 1152)).astype(np.float32)
        )
    return extras


def train_loop(args) -> dict:
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    cfg = cfg.replace(grad_accum=args.grad_accum or cfg.grad_accum)
    if getattr(args, "conv_backend", None):
        cfg = cfg.replace(conv_backend=args.conv_backend)
    rt = Runtime()  # single host; multi-device handled by the dry-run path
    model = build_model(cfg, rt)
    opt_cfg = OptConfig(
        lr=args.lr, total_steps=args.steps, warmup_steps=max(args.steps // 20, 5),
        state_dtype=cfg.opt_state_dtype,
    )
    def make_step_fn():
        # a fresh closure per call: its jit cache starts empty, so the
        # rebuilt step re-traces — the runtime catch layer and probation
        # both rely on this to re-dispatch the ops ladder (DESIGN.md §15)
        return jax.jit(
            make_train_step(model, opt_cfg, accum_steps=cfg.grad_accum,
                            accum_dtype=cfg.grad_accum_dtype)
        )

    step_fn = make_step_fn()

    ckpt = CheckpointManager(Path(args.run_dir) / "ckpt", keep=3)
    data = SyntheticLMData(
        vocab_size=cfg.vocab_size, seq_len=args.seq, global_batch=args.batch,
        seed=args.seed,
    )
    # audio frontend: "stub" feeds precomputed (B, S, d_model) frame
    # embeddings; "mels" feeds (B, S, 80) mel frames so the sliding-conv
    # frontend (and its backward kernels under sliding_pallas) trains.
    frame_dim = cfg.d_model
    if cfg.family == "audio" and getattr(args, "audio_frontend", "stub") == "mels":
        from repro.models.whisper import N_MELS

        frame_dim = N_MELS

    # resume from the newest checkpoint that VALIDATES — a run killed
    # mid-async-save leaves a torn step behind; latest_valid_step
    # quarantines it and falls back to the previous intact one
    start = ckpt.latest_valid_step()
    if start is not None and not args.no_resume:
        skeleton = {
            "params": model.init(jax.random.key(args.seed)),
            "opt": None,
        }
        skeleton["opt"] = init_opt_state(skeleton["params"], opt_cfg)
        with obs.span("train.resume", step=start):
            state = ckpt.restore(start, skeleton)
        start_step = start + 1
        obs.REGISTRY.counter("train.resumes").inc(1.0, arch=cfg.name)
        obs.info("train", f"resumed from step {start}")
    else:
        params = model.init(jax.random.key(args.seed))
        state = {"params": params, "opt": init_opt_state(params, opt_cfg)}
        start_step = 0

    wd = StepWatchdog(
        on_straggler=lambda s, t, ema: obs.warn(
            "ft", f"straggler at step {s}: {t:.2f}s vs EMA {ema:.2f}s"
        )
    )
    reg = obs.REGISTRY
    losses = []
    probed: set[tuple[str, str]] = set()
    retrace_t0 = None
    it = make_batch_iterator(data, start_step=start_step)
    for step, host_batch in it:
        if step >= args.steps:
            break
        # probation poll: a demoted rung whose cooldown elapsed needs a
        # fresh dispatch — rebuild the jitted step ONCE per breaker so
        # the re-trace can grant the probe (the hot loop itself never
        # re-dispatches)
        ready = [pr for pr in HEALTH.probation_ready() if pr not in probed]
        if ready:
            probed.update(ready)
            step_fn = make_step_fn()
            obs.info("train", "probation re-jit for "
                     + ", ".join(f"{s}/{i}" for s, i in ready))
        batch = {k: jnp.asarray(v) for k, v in host_batch.items()}
        if cfg.family == "audio":
            half = args.seq  # encoder frames mirror the token length
            srng = step_stream(args.seed, step, _TAG_FRAMES)
            batch["frames"] = jnp.asarray(
                srng.normal(size=(args.batch, half, frame_dim)).astype(np.float32)
            )
        batch.update(
            build_batch_extras(
                cfg, args.batch, step_stream(args.seed, step, _TAG_PATCHES)
            )
        )
        t0 = time.perf_counter()  # monotonic: step timing must not see
        #                           wall-clock jumps (NTP, suspend)
        with obs.span("train.step", step=step):
            faults.sleep_point("slow_step", "train")  # chaos: straggler step
            for attempt in range(_MAX_RUNTIME_DEMOTIONS_PER_STEP + 1):
                try:
                    # state is NOT reassigned until after the float()
                    # sync: the jitted call returns poisoned buffers
                    # asynchronously, and the trap only surfaces
                    # (XlaRuntimeError / poisoned loss) at the sync — an
                    # eager assignment would hand the retry nan params
                    new_state, metrics = step_fn(state, batch)
                    loss = float(metrics["loss"])
                    state = new_state
                    break
                except Exception as e:  # noqa: BLE001 — trip-gated retry
                    trip = faults.consume_trip()
                    if trip is None or attempt == _MAX_RUNTIME_DEMOTIONS_PER_STEP:
                        raise
                    # runtime kernel failure: demote the rung the trip
                    # names, rebuild the jitted step without it, retry
                    # THIS step on the untouched state
                    try:
                        reason = Reason(trip.kind).value
                    except ValueError:
                        reason = canon_reason(e)
                    HEALTH.record(
                        trip.site, reason, f"demote:{trip.rung}(runtime)",
                        detail=f"key={trip.key or trip.site} step {step} "
                               f"{repr(e)[:160]}",
                    )
                    HEALTH.demote(trip.site, trip.rung, reason=reason)
                    reg.counter("runtime.demote").inc(
                        1.0, site=trip.site, rung=trip.rung,
                        key=trip.key or trip.site,
                    )
                    probed.discard((trip.site, trip.rung))
                    step_fn = make_step_fn()
                    retrace_t0 = time.perf_counter()
        if retrace_t0 is not None:
            # first successful step after a runtime demotion rebuilt the
            # jit: its duration is the re-jit cost the demotion bought
            dt_ms = (time.perf_counter() - retrace_t0) * 1000.0
            reg.counter("runtime.retrace_ms").inc(dt_ms, arch=cfg.name)
            obs.info("train", f"retrace after runtime demotion: {dt_ms:.0f}ms")
            retrace_t0 = None
        dt = time.perf_counter() - t0
        wd.observe(step, dt)
        # clean-step credit toward demoted rungs' probation cooldowns
        HEALTH.tick()
        beat(args.run_dir, host_id=0)
        losses.append(loss)
        toks = args.batch * args.seq
        reg.counter("train.steps").inc(1.0, arch=cfg.name)
        reg.counter("train.tokens").inc(float(toks), arch=cfg.name)
        reg.histogram("train.step_s").observe(dt, arch=cfg.name)
        reg.gauge("train.tokens_per_s").set(
            toks / dt if dt > 0 else 0.0, arch=cfg.name
        )
        reg.gauge("train.loss").set(loss, arch=cfg.name)
        if step % args.log_every == 0:
            obs.info(
                "train",
                f"step {step} loss {loss:.4f} "
                f"gnorm {float(metrics['grad_norm']):.3f} "
                f"lr {float(metrics['lr']):.2e} ({dt:.2f}s)"
            )
        if args.ckpt_every and step > 0 and step % args.ckpt_every == 0:
            tc = time.perf_counter()
            with obs.span("train.ckpt_save", step=step, blocking=False):
                ckpt.save(step, state, blocking=False)
            reg.histogram("train.ckpt_save_s").observe(
                time.perf_counter() - tc, arch=cfg.name
            )
        if args.fail_at is not None and step == args.fail_at:
            raise RuntimeError(f"injected failure at step {step}")
    tc = time.perf_counter()
    with obs.span("train.ckpt_save", step=args.steps - 1, blocking=True):
        ckpt.save(args.steps - 1, state, blocking=True)
    reg.histogram("train.ckpt_save_s").observe(
        time.perf_counter() - tc, arch=cfg.name
    )
    if args.run_dir:
        obs.write_artifacts(args.run_dir)
    return {"losses": losses, "final_loss": losses[-1] if losses else None}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--run-dir", default="/tmp/repro_run")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--grad-accum", type=int, default=None)
    ap.add_argument("--conv-backend", default=None,
                    choices=["sliding", "sliding_pallas", "im2col_gemm", "xla"],
                    help="override cfg.conv_backend (sliding_pallas trains "
                         "through the Pallas custom-VJP kernels)")
    ap.add_argument("--audio-frontend", default="stub",
                    choices=["stub", "mels"],
                    help="audio archs: stub frame embeddings, or mel frames "
                         "through the sliding-conv frontend")
    ap.add_argument("--no-resume", action="store_true")
    ap.add_argument("--fail-at", type=int, default=None,
                    help="inject a crash at this step (FT testing)")
    ap.add_argument("--max-restarts", type=int, default=0,
                    help="auto-restart budget after crashes")
    ap.add_argument("--trace", action="store_true",
                    help="record a JAX profiler trace, the train loop's "
                         "spans with the device's ops, into "
                         "<run-dir>/profile")
    args = ap.parse_args()

    compile_cache.enable()
    # the profile is written when the block ends, however it ends
    with (jax.profiler.trace(os.path.join(args.run_dir, "profile"))
          if args.trace else contextlib.nullcontext()):
        _train(args)


def _train(args):
    policy = RestartPolicy(max_restarts=args.max_restarts)
    while True:
        try:
            out = train_loop(args)
            obs.info("train", f"done; final loss {out['final_loss']:.4f}")
            return
        except RuntimeError as e:
            delay = policy.next_backoff()
            if delay is None:
                HEALTH.record("train", "restarts_exhausted", "raise",
                              detail=repr(e)[:200])
                raise
            HEALTH.record("train", "step_crash", "restart",
                          detail=repr(e)[:200])
            obs.warn("ft", f"{e}; restarting in {delay:.1f}s "
                           f"({policy.restarts}/{policy.max_restarts})")
            time.sleep(min(delay, 2.0))  # capped for tests
            args.fail_at = None  # the injected fault is transient


if __name__ == "__main__":
    main()
