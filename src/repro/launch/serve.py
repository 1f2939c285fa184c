"""Serving driver: batched prefill + decode with a static KV cache.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --smoke \
        --batch 4 --prompt-len 32 --gen 16 [--quant int8]

Implements the standard two-phase serving flow the decode_* dry-run shapes
lower: one prefill per batch of requests, then token-by-token decode with
greedy/temperature sampling. Continuous batching is approximated by slot
recycling: finished sequences keep decoding into masked positions and
their slots are refilled between generation rounds. The EOS id that marks
a slot finished comes from the model config (``cfg.eos_id``, per-arch —
hardcoding 1 broke recycling for tokenizers where 1 is a real token).

``--quant int8`` runs the conv path (whisper frontend, mamba convs) w8a8:
an eager calibration prefill collects activation scales, ``repro.quant``
swaps int8 weights into the params (chained sites — whisper conv1→conv2 —
get ``out_scale`` so int8 activations flow between them directly), and
decode runs with ``conv_precision="w8a8"``. Conv-free archs pass through
unchanged.

``--kv-quant int8`` stores the KV cache as int8 with per-row f32 scales
(quantized along each position's head_dim row via the ``optim/compress``
primitive): the prefill cache is quantized before padding and decode steps
quantize each new token's K/V rows in place — both through the ONE
``common.quantize_kv_leaf`` quantizer (DESIGN.md §8). The attention READ
is fused by default (``--attn-decode fused``): the flash-style decode
kernel folds the dequant into its online softmax so the int8 codes stay
resident and no float K/V view is materialized (DESIGN.md §9);
``--attn-decode view`` keeps the dequantize-whole-cache baseline for A/B
runs. Reported cache bytes drop ~2× (bf16 params) to ~3.5× (f32 smoke).

Serving is crash-safe (DESIGN.md §10): ``generate`` runs under a bounded
``RestartPolicy`` retry (non-finite logits — guarded per step — or a
runtime failure re-run the request instead of crashing the server), an
optional per-request ``deadline_s`` truncates overlong decodes with an
eos-padded result and a reason-coded health event, and the decode loop
drives a ``StepWatchdog`` + heartbeat like train when ``run_dir`` is given.

Runtime fault domain (DESIGN.md §15): a kernel that dies *inside* the
compiled call (the ``faults.guest_trap`` drill, or a real device fault
surfacing as ``XlaRuntimeError``) is mapped back to its (site, rung) via
the trip mailbox, demoted in ``HEALTH``, and the request re-jits without
the dead rung — the retrace cost lands in ``runtime.retrace_ms``. Blast
radius is bounded below the request level too: a single poisoned slot
(non-finite logits in one batch row) is quarantined — eos-masked and
recycled — instead of failing the batch; admission sheds new requests
when the decode-step p95 projects past the deadline budget; and a
crash-safe request journal under ``--run-dir`` replays in-flight
requests to bit-identical greedy tokens after a restart.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import time
import weakref
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache, faults, obs
from repro.configs import get_config, smoke_config
from repro.distributed.ft import RestartPolicy, StepWatchdog, beat
from repro.distributed.sharding import ParamDef, Runtime
from repro.health import HEALTH, Reason, canon_reason
from repro.launch.steps import make_prefill_step
from repro.models import build_model


def init_cache_concrete(model, B, S, **kw):
    return jax.tree.map(
        lambda d: jnp.zeros(d.shape, jnp.dtype(d.dtype or model.cfg.param_dtype)),
        model.cache_defs(B, S, **kw),
        is_leaf=lambda x: isinstance(x, ParamDef),
    )


def quantize_cache_to_defs(cache, defs):
    """Quantize float prefill cache leaves that the (``cfg.kv_quant``)
    cache defs store as int8, emitting the paired ``<name>_scale`` leaf
    the defs expect. The actual quantizer is ``common.quantize_kv_leaf``
    — the SAME function the per-token decode update
    (``common.store_kv_token``) uses, so the prefill and decode halves of
    the (q, scale) pair can never drift onto different grids. Leaves the
    defs keep float (recurrent conv/ssm states) pass through unchanged."""
    from repro.models.common import quantize_kv_leaf

    def walk(c, d):
        out = {}
        for name, df in d.items():
            if isinstance(df, dict):
                out[name] = walk(c[name], df)
            elif name.endswith("_scale") and name[: -len("_scale")] in d:
                continue  # emitted alongside its int8 base leaf below
            elif df.dtype == "int8" and f"{name}_scale" in d:
                q, s = quantize_kv_leaf(c[name])
                out[name] = q
                out[f"{name}_scale"] = s
            else:
                out[name] = c[name]
        return out

    return walk(cache, defs)


def cache_nbytes(defs, param_dtype) -> int:
    """Total bytes a cache built from ``defs`` occupies (ParamDef dtype,
    falling back to the model param dtype)."""
    import math

    return sum(
        math.prod(d.shape) * jnp.dtype(d.dtype or param_dtype).itemsize
        for d in jax.tree.leaves(
            defs, is_leaf=lambda x: isinstance(x, ParamDef)
        )
    )


def pad_cache_to_defs(cache, full, defs):
    """Pad each prefill cache leaf up to the decode cache shape along its
    **sequence axis**, identified by the ``"kv_seq"`` name in the leaf's
    ``ParamDef.axes`` — not by guessing which axis happens to equal the
    prompt length (a shape-coincidence heuristic misfires whenever another
    axis equals it). Leaves without a ``kv_seq`` axis (recurrent conv/ssm
    states) pass through unchanged. A leaf whose defs merge its trailing
    axes (whisper's lane-dense cross K/V, quantized per head first) is
    then reshaped to them."""

    def pad(c, d, df):
        if "kv_seq" in df.axes:
            ax = df.axes.index("kv_seq")
            if c.shape[ax] != d.shape[ax]:
                pads = [(0, 0)] * c.ndim
                pads[ax] = (0, d.shape[ax] - c.shape[ax])
                c = jnp.pad(c, pads)
        return c.reshape(d.shape).astype(d.dtype)

    return jax.tree.map(pad, cache, full, defs)


def cache_kw(audio) -> dict:
    """``cache_defs``' keywords for a batch's inputs: the cross cache of
    an encoder-decoder holds the encoder's positions of the audio, one
    per two mel frames."""
    return {} if audio is None else {"enc_len": audio.shape[1] // 2}


# per-model jitted entry points: jax.jit caches trace/compile per wrapper,
# and a fresh wrapper per generate() call would re-trace every time — a
# repeat generate() on the same model (benchmarks, tests) must pay compile
# once, not per call. The jitted closures hold only a weakref to the model
# (a bound method in the value would strongly reference the key, pinning
# every served model + its executables in this module-level dict forever).
_JITTED = weakref.WeakKeyDictionary()


def _jitted(model):
    """(prefill, decode) of the model. The decode callable returns
    ``(logits, cache)``: the jitted ``decode_step`` returns the leaves it
    wrote, and the callable merges them into the cache it was given
    outside the jit, so a leaf the step only read comes back as the very
    array passed in (:func:`_fused_decode` keeps only the others)."""
    fns = _JITTED.get(model)
    if fns is None:
        mref = weakref.ref(model)
        step = jax.jit(lambda params, cache, tok, pos: mref().decode_step(
            params, cache, tok, pos))

        def decode(params, cache, tok, pos):
            logits, written = step(params, cache, tok, pos)
            return logits, {**cache, **written}

        fns = (
            jax.jit(lambda params, batch: mref().prefill(params, batch)),
            decode,
        )
        _JITTED[model] = fns
    return fns


def held_nbytes(cache, written) -> int:
    """Bytes of the cache leaves a decode step read but did not return."""
    return sum(a.nbytes for n, v in cache.items() if n not in written
               for a in jax.tree.leaves(v))


# per-model encoder program (enc-dec), held beside the prefill callable it
# was built with: a runtime demotion drops _JITTED[model], and the next
# request must trace the encoder again without the demoted rung
_ENCODER = weakref.WeakKeyDictionary()


def _encoder(model):
    prefill = _jitted(model)[0]
    held = _ENCODER.get(model)
    if held is None or held[0] is not prefill:
        mref = weakref.ref(model)
        held = (prefill, jax.jit(
            lambda params, frames: mref().encode_cross(params, frames)))
        _ENCODER[model] = held
    return held[1]


class LoadShedError(RuntimeError):
    """Request rejected at admission: the decode-step p95 projects the
    request past its deadline budget — shedding beats accepting work that
    is already doomed to truncate (DESIGN.md §15)."""


#: decode-step samples required before admission trusts the p95 estimate
_SHED_MIN_SAMPLES = 8
#: runtime (in-compiled-call) demotions one request may absorb before its
#: failure propagates — each one re-jits, so this bounds retrace thrash
_MAX_RUNTIME_DEMOTIONS = 8
# set by the runtime catch layer after it drops the jit cache; the next
# prefill logs its duration as the re-jit cost the demotion bought
_RETRACE_PENDING = False


class RequestJournal:
    """Crash-safe append-only request journal (DESIGN.md §15).

    One jsonl record per transition: ``begin`` (the full request — prompts
    and decode parameters) at admission, ``end`` (tokens + done mask) at
    completion. Every append rewrites the file via tmp+rename (the
    ``ft.beat`` idiom), so a crash leaves either the old or the new
    journal, never a torn line. A restarted server replays ``pending()``
    — begins without a matching end — and greedy decode being
    deterministic, the replay reproduces bit-identical tokens.
    """

    def __init__(self, run_dir):
        self.path = Path(run_dir) / "requests.jsonl"

    def _append(self, rec: dict) -> None:
        prev = self.path.read_text() if self.path.exists() else ""
        tmp = self.path.with_name(f".{self.path.name}.{os.getpid()}.tmp")
        tmp.write_text(prev + json.dumps(rec) + "\n")
        tmp.replace(self.path)

    def begin(self, req_id: str, prompts, *, gen_len: int, cache_len: int,
              temperature: float, seed: int) -> None:
        """Record a request: its prompts and decode parameters, not its
        other inputs (audio), which :func:`replay_pending` then refuses."""
        self._append({
            "id": req_id, "event": "begin",
            "prompts": np.asarray(prompts).tolist(),
            "gen_len": gen_len, "cache_len": cache_len,
            "temperature": temperature, "seed": seed,
        })

    def end(self, req_id: str, tokens, done) -> None:
        self._append({
            "id": req_id, "event": "end",
            "tokens": np.asarray(tokens).tolist(),
            "done": np.asarray(done).tolist(),
        })

    def records(self) -> list[dict]:
        if not self.path.exists():
            return []
        return [
            json.loads(line)
            for line in self.path.read_text().splitlines()
            if line.strip()
        ]

    def pending(self) -> list[dict]:
        """Begin records with no matching end — in flight at the crash."""
        begun: dict[str, dict] = {}
        ended: set[str] = set()
        for r in self.records():
            if r["event"] == "begin":
                begun[r["id"]] = r
            elif r["event"] == "end":
                ended.add(r["id"])
        return [r for rid, r in begun.items() if rid not in ended]


def _check_audio(cfg, prompts, audio) -> None:
    """An audio model needs each request's own mels, ``audio`` (B, T, 80),
    one row per prompt; any other model refuses audio."""
    if cfg.family != "audio":
        if audio is not None:
            raise ValueError(f"{cfg.name} takes no audio")
        return
    if audio is None:
        raise ValueError(f"{cfg.name} serves audio: pass each request's "
                         "mels as audio=(B, T, 80)")
    if audio.shape[0] != prompts.shape[0]:
        raise ValueError(f"audio for {audio.shape[0]} requests, prompts "
                         f"for {prompts.shape[0]}")


def serve_batch(model, prompts, audio=None):
    """The prefill's batch: the prompts and each request's other inputs
    (an audio model's mels, which its conv frontend takes)."""
    cfg = model.cfg
    _check_audio(cfg, prompts, audio)
    batch = {"tokens": prompts}
    if audio is not None:
        batch["frames"] = audio
    if cfg.family == "vlm":
        batch["patches"] = jnp.zeros((prompts.shape[0], cfg.num_patches, 1152),
                                     jnp.float32)
    return batch


def resolve_cache_len(cfg, cache_len: int, P: int, gen_len: int) -> int:
    """Clamp an undersized cache request. An enc-dec cache's ``seq`` rows
    are the decoder's self-attention positions, which must hold prompt +
    gen (its cross rows come from the audio, ``cache_kw``), and no more
    than the decoder has learned positions for. One helper so generate()
    and the CLI's byte reporting can never disagree about the length."""
    if cfg.encoder_layers:
        if cfg.max_target_positions and P + gen_len > cfg.max_target_positions:
            raise ValueError(f"{cfg.name}: prompt {P} + {gen_len} tokens "
                             f"exceed {cfg.max_target_positions} positions")
        return max(cache_len, P + gen_len)
    return cache_len


def prefill_cache(model, params, prompts, *, cache_len: int,
                  gen_len: int = 0, audio=None):
    """Prefill + decode-ready cache: run the model's prefill, then pad
    (and, under ``cfg.kv_quant``, quantize) the emitted cache up to
    ``cache_len`` along each leaf's kv_seq axis. Returns (last-token
    logits, cache). Shared by :func:`generate` and the decode-step
    benchmarks (``benchmarks.run --serve``), so both time/drive the exact
    serving cache layout.

    An encoder-decoder first runs its encoder program over ``audio``
    (each request's mels), synced, under the span ``serve.prefill.encode``;
    the prefill then reads the encoder's cross K/V.

    With kv_quant the float prefill leaves quantize FIRST so the
    (q, scale) pair pads coherently.
    """
    cfg = model.cfg
    B, P = prompts.shape
    cache_len = resolve_cache_len(cfg, cache_len, P, gen_len)
    batch = serve_batch(model, prompts, audio)
    prefill, _ = _jitted(model)
    t_p = time.perf_counter()
    with obs.span("serve.prefill.forward"):
        if cfg.encoder_layers:
            with obs.span("serve.prefill.encode"):
                batch["cross"] = _encoder(model)(params, batch.pop("frames"))
                # a direct-output sync, as the prefill's below
                jax.block_until_ready(batch["cross"])
        logits, cache = prefill(params, batch)
        # sync the compiled call's DIRECT outputs: an in-compiled-call
        # failure (guest trap, device fault) is only guaranteed to surface
        # as XlaRuntimeError on these arrays — a dependent computation
        # enqueued before the error lands can read garbage instead
        # (DESIGN.md §15). Free in practice: the first token's screen
        # syncs on logits anyway.
        jax.block_until_ready((logits, cache))
    global _RETRACE_PENDING
    if _RETRACE_PENDING:
        # first prefill after a runtime demotion dropped the jit cache:
        # its duration IS the re-jit cost the demotion bought
        _RETRACE_PENDING = False
        dt_ms = (time.perf_counter() - t_p) * 1000.0
        obs.REGISTRY.counter("runtime.retrace_ms").inc(dt_ms, arch=cfg.name)
        obs.info("serve", f"retrace after runtime demotion: {dt_ms:.0f}ms")
    with obs.span("serve.prefill.cache"):
        kw = cache_kw(audio)
        full = init_cache_concrete(model, B, cache_len, **kw)
        defs = model.cache_defs(B, cache_len, **kw)
        if cfg.kv_quant == "int8":
            cache = quantize_cache_to_defs(cache, defs)
        # metadata-only gauge (no device sync): the decode-cache footprint
        # this request serves from
        obs.REGISTRY.gauge("serve.kv_cache_bytes").set(
            float(cache_nbytes(defs, cfg.param_dtype)), kind="served"
        )
        cache = pad_cache_to_defs(cache, full, defs)
    return logits, cache


def _sample(logits, done, temperature, key, poison, *, eos: int,
            nan_guard: bool):
    """The per-token screen, choice and masking, traced into a compiled
    program (the fused decode step; alone, the prefill's first token).

    ``bad`` (B,) marks slots whose logits are non-finite (NaN/Inf would
    silently argmax to token 0 and poison the continuation); under
    ``nan_guard`` they join ``done`` (quarantine), so their tokens pin to
    eos like any finished slot. The token is the argmax, or with a ``key``
    a categorical draw at ``temperature`` from a split of it (the key is
    threaded through, split inside the program). Then, in this order:
    finished slots pin to eos, and a slot that emits eos is done. ``done``
    None means no slot has finished yet; ``poison`` (B,) NaNs those rows
    of the logits first (fault injection, ``faults.poison_rows``).
    Returns ``(tok (B, 1) int32, done, bad, key)``."""
    B = logits.shape[0]
    if done is None:
        done = jnp.zeros((B,), bool)
    rows = (B,) + (1,) * (logits.ndim - 1)
    if poison is not None:
        logits = jnp.where(poison.reshape(rows), jnp.nan, logits)
    bad = None
    if nan_guard:
        bad = ~jnp.isfinite(logits).all(axis=tuple(range(1, logits.ndim)))
        done = done | bad
    last = logits[:, -1]
    if key is None:
        tok = jnp.argmax(last, axis=-1)
    else:
        key, sub = jax.random.split(key)
        tok = jax.random.categorical(
            sub, last / temperature.astype(last.dtype)
        )
    tok = jnp.where(done, eos, tok.astype(jnp.int32))[:, None]
    return tok, done | (tok[:, 0] == eos), bad, key


# the prefill's first token: the same sampler, one compiled program per
# batch instead of an eager op per line
_sample_first = jax.jit(_sample, static_argnames=("eos", "nan_guard"))

# per-model fused decode step, held beside the decode callable it was
# built from: a runtime demotion or probation drops _JITTED[model] (and a
# test may replace it), and the next request must trace the fused step
# again around the new callable
_FUSED = weakref.WeakKeyDictionary()


def _fused_decode(model):
    """One compiled program per decode step: the decode callable in
    ``_JITTED[model]``, then :func:`_sample` on its logits. Called as
    ``step(params, cache, tok, pos, done, temperature, key, poison,
    nan_guard=...)`` and returns ``(tok, done, bad, written, key)``, no
    logits: ``written`` holds only the cache leaves the decode callable
    returned anew, which the caller merges into its cache. A leaf the
    step only reads (whisper's cross K/V) comes back as the input itself
    and is not an output of the program, so no step copies it.
    ``temperature`` is an operand (None with ``key`` None when greedy), so
    a new temperature compiles nothing; ``poison`` is None unless a fault
    fires, so the poisoned variant compiles only under chaos runs."""
    decode = _jitted(model)[1]
    held = _FUSED.get(model)
    if held is None or held[0] is not decode:
        eos = model.cfg.eos_id

        def step(params, cache, tok, pos, done, temperature, key, poison, *,
                 nan_guard):
            logits, new = decode(params, cache, tok, pos)
            # the merge into the cache happens outside the decode jit
            # (_jitted), so identity tells a written leaf from a read one
            written = {n: a for n, a in new.items() if a is not cache.get(n)}
            tok, done, bad, key = _sample(logits, done, temperature, key,
                                          poison, eos=eos,
                                          nan_guard=nan_guard)
            return tok, done, bad, written, key

        held = (decode, jax.jit(step, static_argnames="nan_guard"))
        _FUSED[model] = held
    return held[1]


def _screen(bad, done, step: int, arch: str) -> None:
    """Host half of the per-step numeric guard, with slot-level blast
    radius (DESIGN.md §15): one read-back of the compiled step's (B,)
    ``bad`` mask. Every slot bad → fail fast, the retry wrapper re-runs
    the request (the batch-wide failure class: a broken kernel). SOME
    slots bad → the step already quarantined them (eos-masked, marked
    done, recyclable) — one poisoned request must not kill its siblings;
    record the slots newly bad against ``done`` as it was before the step
    (None: none was done)."""
    bad = np.asarray(bad)
    if not bad.any():
        return
    if bad.all():
        raise FloatingPointError(f"non-finite logits at decode step {step}")
    newly = bad if done is None else bad & ~np.asarray(done)
    n = int(newly.sum())
    if n:
        HEALTH.record(
            "serve/slot", "nan_logits", "quarantine",
            detail=f"step {step}: {n} slot(s) "
                   f"{np.flatnonzero(newly).tolist()}",
        )
        obs.REGISTRY.counter("serve.quarantined").inc(float(n), arch=arch)


def _poison(nan_guard: bool, B: int):
    """This step's injected NaN rows (decided on the host, as a fault
    fires), as a device mask, or None: the clean path moves nothing."""
    if not nan_guard:
        return None
    rows = faults.poison_rows("nan_activations", "serve/logits",
                              "serve/slot", B)
    return None if rows is None else jnp.asarray(rows)


def _generate_once(model, params, prompts, *, audio, gen_len, cache_len,
                   temperature, seed, deadline_s, nan_guard, run_dir,
                   host_id, watchdog):
    cfg = model.cfg
    B, P = prompts.shape
    reg = obs.REGISTRY
    # perf_counter, NOT the wall clock: steps/deadlines/watchdog measure
    # durations — a wall-clock jump (NTP step, suspend) must not fire
    # false straggler or deadline events. The wall clock remains only
    # where an absolute timestamp is recorded (the heartbeat file).
    t_start = time.perf_counter()
    # spans name every stretch of host time in the loop, so a profiler's
    # device trace says which line the chip waited on (obs.names.SPANS)
    with obs.span("serve.prefill", arch=cfg.name):
        logits, cache = prefill_cache(
            model, params, prompts, cache_len=cache_len, gen_len=gen_len,
            audio=audio,
        )
        step = _fused_decode(model)
        with obs.span("serve.prefill.sample"):
            # the first token is greedy whatever the temperature
            poison = _poison(nan_guard, B)
            tok, done, bad, _ = _sample_first(
                logits, None, None, None, poison, eos=cfg.eos_id,
                nan_guard=nan_guard,
            )
            if nan_guard:
                _screen(bad, None, -1, cfg.name)
            key = temp = None
            if temperature > 0:
                key = jax.random.key(seed)
                temp = jnp.float32(temperature)
        # TTFT: prefill through the sampler that yields the first token
        t_first = time.perf_counter() - t_start
        reg.histogram("serve.prefill_s").observe(t_first, arch=cfg.name)
    out = [tok]
    step_hist = reg.histogram("serve.decode_step_s")
    fused_steps = reg.counter("serve.decode.fused_steps")
    for i in range(gen_len - 1):
        t_step = time.perf_counter()
        with obs.span("serve.decode_step", arch=cfg.name, step=P + i):
            faults.sleep_point("slow_step", "serve")
            with obs.span("serve.decode.dispatch"):
                poison = _poison(nan_guard, B)
                was_done = done
                tok, done, bad, written, key = step(
                    params, cache, tok, jnp.int32(P + i), done, temp, key,
                    poison, nan_guard=nan_guard,
                )
                if i == 0:
                    # metadata only (no device sync): what the program
                    # reads each step and leaves where it is
                    reg.gauge("serve.decode.held_cache_bytes").set(
                        float(held_nbytes(cache, written)), arch=cfg.name)
                cache = {**cache, **written}
            with obs.span("serve.decode.wait"):
                # direct-output sync: guarantees an in-compiled-call
                # failure surfaces HERE as XlaRuntimeError instead of
                # feeding garbage to the next step
                jax.block_until_ready((tok, done, bad, written))
            with obs.span("serve.decode.screen"):
                if nan_guard:
                    _screen(bad, was_done, i, cfg.name)
            with obs.span("serve.decode.sample"):
                out.append(tok)
            if poison is None:
                fused_steps.inc(1.0, arch=cfg.name)
            else:
                fused_steps.inc(1.0, arch=cfg.name, poisoned="true")
            dt_step = time.perf_counter() - t_step
            step_hist.observe(dt_step, arch=cfg.name)
            # clean-call credit toward demoted rungs' probation cooldowns —
            # jitted decode never re-dispatches, so loop steps are the clock
            HEALTH.tick()
            if watchdog is not None:
                watchdog.observe(P + i, dt_step)
            if run_dir is not None:
                beat(run_dir, host_id)
            if (
                deadline_s is not None
                and time.perf_counter() - t_start > deadline_s
            ):
                # deadline: truncate the request — remaining positions pad
                # with eos and every slot is marked recyclable
                HEALTH.record(
                    "serve/generate", "deadline_exceeded", "truncate",
                    detail=f"{len(out)}/{gen_len} tokens in {deadline_s}s",
                )
                reg.counter("serve.deadline_exceeded").inc(
                    1.0, arch=cfg.name
                )
                out.append(
                    jnp.full((B, gen_len - len(out)), cfg.eos_id, jnp.int32)
                )
                done = jnp.ones_like(done)
                break
    n_done = int(done.sum())
    reg.counter("serve.tokens_generated").inc(
        float(B * gen_len), arch=cfg.name
    )
    reg.gauge("serve.slots_total").set(float(B), arch=cfg.name)
    reg.gauge("serve.slots_recyclable").set(float(n_done), arch=cfg.name)
    reg.gauge("serve.slot_occupancy").set(
        (B - n_done) / B if B else 0.0, arch=cfg.name
    )
    return jnp.concatenate(out, axis=1), done


def _admission_check(model, gen_len: int, deadline_s: float | None) -> None:
    """Load shedding (DESIGN.md §15): with a deadline budget set and
    enough decode-step samples to trust the histogram, reject a request
    whose projected decode time (step p95 × gen_len) already exceeds the
    budget — shedding at admission beats accepting work that is doomed to
    truncate mid-decode after consuming a batch slot. Non-positive
    deadlines bypass admission: they are the force-truncate idiom (the
    request is accepted and truncates at its first step), not a budget."""
    if deadline_s is None or deadline_s <= 0:
        return
    reg = obs.REGISTRY
    hist = reg.histogram("serve.decode_step_s")
    n = hist.count(arch=model.cfg.name)
    if n < _SHED_MIN_SAMPLES:
        return
    p95 = hist.quantile(0.95, arch=model.cfg.name)
    projected = p95 * gen_len
    if projected <= deadline_s:
        return
    HEALTH.record(
        "serve/admission", "load_shed", "shed",
        detail=f"p95 {p95 * 1e3:.1f}ms x {gen_len} = {projected:.2f}s "
               f"> deadline {deadline_s}s (n={n})",
    )
    reg.counter("serve.shed").inc(1.0, arch=model.cfg.name)
    raise LoadShedError(
        f"projected decode {projected:.2f}s exceeds deadline {deadline_s}s"
    )


def generate(model, params, prompts, *, gen_len: int, cache_len: int,
             audio=None, temperature: float = 0.0, seed: int = 0,
             deadline_s: float | None = None, max_retries: int = 2,
             nan_guard: bool = True, run_dir=None, host_id: int = 0,
             watchdog: StepWatchdog | None = None,
             journal: RequestJournal | None = None,
             request_id: str | None = None):
    """prompts: (B, P) int32 -> ((B, gen_len) int32, done mask (B,) bool).

    ``audio`` (B, T, 80) float32: each request's own log-mel frames, which
    an audio model (whisper) must be given and any other refuses. The
    decoder prompt's length does not depend on the audio's.

    Slots whose sequence hit ``cfg.eos_id`` are finished: they keep
    decoding into masked positions (their tokens pinned to eos) so the
    static batch shape holds, and the returned ``done`` mask tells the
    caller which slots are recyclable.

    Robustness (DESIGN.md §10): the request runs under a bounded retry —
    a failure mid-decode (non-finite logits caught by the per-step
    ``nan_guard``) re-runs it up to ``max_retries`` times with short
    backoff before propagating. ``deadline_s`` bounds wall-clock per
    request: on expiry the result is truncated (eos-padded, all slots
    done) instead of running open-ended, and at admission the request is
    SHED (``LoadShedError``, no retry) when the decode-step p95 projects
    past the budget. When ``run_dir`` is given the decode loop heartbeats
    per step and a ``watchdog`` (or a default one) flags straggler steps
    into ``HEALTH``.

    Runtime fault domain (DESIGN.md §15): a kernel failure *inside* the
    compiled call carries a ``faults.Trip`` naming its (site, rung,
    dispatch key). The catch layer demotes that rung in ``HEALTH``, drops
    the model's jit cache so the re-run re-traces without it (the next
    prefill logs the retrace cost), and re-runs WITHOUT consuming the
    retry budget — bounded separately by ``_MAX_RUNTIME_DEMOTIONS``.
    Demoted rungs re-enter via probation: when a breaker's cooldown
    elapses, the jit cache is dropped once so the re-trace can grant the
    probe. With ``journal`` given the request is journaled begin/end for
    crash replay (``request_id`` names it).
    """
    reg = obs.REGISTRY
    _check_audio(model.cfg, prompts, audio)
    _admission_check(model, gen_len, deadline_s)
    if journal is not None:
        journal.begin(
            request_id or "req", prompts, gen_len=gen_len,
            cache_len=cache_len, temperature=temperature, seed=seed,
        )
    if watchdog is None and run_dir is not None:
        def _flag_straggler(step, s, ema):
            HEALTH.record(
                "serve/decode", "straggler", "flag",
                detail=f"step {step}: {s:.3f}s vs EMA {ema:.3f}s",
            )
            reg.counter("serve.stragglers").inc(1.0)

        watchdog = StepWatchdog(on_straggler=_flag_straggler)
    policy = RestartPolicy(
        max_restarts=max_retries, base_backoff_s=0.05, max_backoff_s=2.0
    )
    reg.counter("serve.requests").inc(1.0, arch=model.cfg.name)
    global _RETRACE_PENDING
    runtime_demotions = 0
    probed: set[tuple[str, str]] = set()
    while True:
        # probation poll: a demoted rung whose cooldown elapsed only gets
        # its probe at a fresh dispatch — drop the jit cache ONCE per
        # breaker per request so the re-trace can grant it (jitted loops
        # never re-dispatch on their own)
        ready = [pr for pr in HEALTH.probation_ready() if pr not in probed]
        if ready:
            probed.update(ready)
            if _JITTED.pop(model, None) is not None:
                obs.info(
                    "serve",
                    "probation re-jit for "
                    + ", ".join(f"{s}/{i}" for s, i in ready),
                )
        try:
            t_req = time.perf_counter()
            with obs.span("serve.generate", arch=model.cfg.name):
                result = _generate_once(
                    model, params, prompts, audio=audio, gen_len=gen_len,
                    cache_len=cache_len, temperature=temperature,
                    seed=seed, deadline_s=deadline_s, nan_guard=nan_guard,
                    run_dir=run_dir, host_id=host_id, watchdog=watchdog,
                )
            reg.histogram("serve.request_s").observe(
                time.perf_counter() - t_req, arch=model.cfg.name
            )
            if journal is not None:
                journal.end(request_id or "req", result[0], result[1])
            return result
        except Exception as e:  # noqa: BLE001 — bounded retry, then raise
            trip = faults.consume_trip()
            if trip is not None:
                # runtime kernel failure inside the compiled call: the
                # trip maps it back to (site, rung) — demote, drop the
                # jit cache, re-run on the next rung. The re-jit IS the
                # recovery, so this path does not consume the retry
                # budget; a separate cap bounds demotion thrash. The trip
                # kind outranks the surfaced exception: the failure may
                # reach us as either the XlaRuntimeError from the sync or
                # the NaN screen tripping on the poisoned buffer first.
                try:
                    reason = Reason(trip.kind).value
                except ValueError:
                    reason = canon_reason(e)
                HEALTH.record(
                    trip.site, reason, f"demote:{trip.rung}(runtime)",
                    detail=f"key={trip.key or trip.site} {repr(e)[:160]}",
                )
                HEALTH.demote(trip.site, trip.rung, reason=reason)
                reg.counter("runtime.demote").inc(
                    1.0, site=trip.site, rung=trip.rung,
                    key=trip.key or trip.site,
                )
                _JITTED.pop(model, None)
                _RETRACE_PENDING = True
                runtime_demotions += 1
                if runtime_demotions <= _MAX_RUNTIME_DEMOTIONS:
                    continue
            # frozen-vocabulary reason (health.Reason): fault kind →
            # verbatim, FloatingPointError → nan_logits, anything else →
            # runtime_error with the class name kept in detail
            reason = canon_reason(e)
            delay = policy.next_backoff()
            if delay is None:
                HEALTH.record(
                    "serve/generate", reason, "error:retries_exhausted",
                    detail=repr(e)[:200],
                )
                raise
            HEALTH.record(
                "serve/generate", reason, "retry", detail=repr(e)[:200]
            )
            reg.counter("serve.retries").inc(1.0, arch=model.cfg.name)
            time.sleep(delay)


def replay_pending(model, params, journal: RequestJournal, **kw):
    """Replay journaled in-flight requests after a restart. Greedy decode
    is deterministic, so each replayed request reproduces bit-identical
    tokens; completion writes the journal ``end`` record the crash never
    did. Returns ``[(request_id, tokens, done), ...]``.

    The journal holds no audio, so an audio model's records cannot be
    replayed: they are refused, not served without their audio."""
    out = []
    for rec in journal.pending():
        if model.cfg.family == "audio":
            raise ValueError(
                f"journal request {rec['id']!r}: {model.cfg.name} needs the "
                "request's audio, which the journal does not hold")
        prompts = jnp.asarray(rec["prompts"], jnp.int32)
        toks, done = generate(
            model, params, prompts, gen_len=rec["gen_len"],
            cache_len=rec["cache_len"], temperature=rec["temperature"],
            seed=rec["seed"], journal=journal, request_id=rec["id"], **kw
        )
        obs.REGISTRY.counter("serve.journal_replayed").inc(1.0)
        obs.info("serve", f"journal: replayed in-flight request {rec['id']}")
        out.append((rec["id"], toks, done))
    return out


def quantize_for_serving(model, params, prompts, audio=None):
    """int8 PTQ of the model's conv path: eager calibration prefill →
    activation scales (+ inter-layer chain scales, ``quant.CHAINS``) →
    int8 weight leaves. Returns (cfg', params')."""
    from repro import quant

    cfg = model.cfg
    calib = quant.Calibration()
    with obs.span("serve.quantize", arch=cfg.name):
        with quant.collecting(calib):
            prefill = make_prefill_step(model)
            prefill(params, serve_batch(model, prompts, audio))  # eager
        spec = calib.spec(chains=quant.CHAINS)
        qparams = quant.quantize_params(params, spec=spec)
    n = quant.quantized_site_count(qparams)
    if n == 0:
        obs.info("serve", f"--quant: {cfg.name} has no conv sites; unchanged")
        return cfg, params
    chained = sum(1 for e in spec.values() if "out_scale" in e)
    obs.info("serve", f"--quant: {n} conv weight(s) int8, "
             f"{len(calib.seen)} calibrated site(s), {chained} chained")
    return cfg.replace(conv_precision="w8a8"), qparams


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--quant", choices=["int8"], default=None,
                    help="post-training-quantize the conv path (w8a8)")
    ap.add_argument("--kv-quant", choices=["int8"], default=None,
                    help="store the serving KV cache int8 + per-row scales")
    ap.add_argument("--attn-decode", choices=["fused", "view"],
                    default="fused",
                    help="decode-attention read: fused flash kernel "
                         "(int8 codes stay resident) vs the dequant-view "
                         "baseline")
    ap.add_argument("--conv-backend", default=None,
                    choices=["sliding", "sliding_pallas", "im2col_gemm",
                             "xla"],
                    help="conv evaluation for the model's conv layers; "
                         "sliding_pallas routes through the ops dispatch "
                         "ladder (the chaos-CI path)")
    ap.add_argument("--run-dir", default=None,
                    help="heartbeat/watchdog directory for the decode "
                         "loop; obs artifacts (metrics.json [+ "
                         "profile/]) are written here at exit")
    ap.add_argument("--trace", action="store_true",
                    help="record a JAX profiler trace, the serve loop's "
                         "spans with the device's ops, into "
                         "<run-dir>/profile")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request wall-clock budget; expiry truncates "
                         "the batch with eos padding")
    ap.add_argument("--retries", type=int, default=2,
                    help="bounded retry budget per request")
    ap.add_argument("--requests", type=int, default=1,
                    help="sequential requests to serve (same prompts/seed "
                         "— greedy decode makes them bit-identical, which "
                         "is what lets chaos CI prove a repromoted rung "
                         "reproduces the clean tokens)")
    args = ap.parse_args()
    if args.trace and not args.run_dir:
        ap.error("--trace writes its profile under --run-dir")

    compile_cache.enable()
    if args.run_dir:
        os.makedirs(args.run_dir, exist_ok=True)  # the journal writes first
    # the profile is written when the block ends, however it ends
    with (jax.profiler.trace(os.path.join(args.run_dir, "profile"))
          if args.trace else contextlib.nullcontext()):
        _serve(args)


def _serve(args):
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    if args.kv_quant:
        cfg = cfg.replace(kv_quant=args.kv_quant)
    if args.conv_backend:
        cfg = cfg.replace(conv_backend=args.conv_backend)
    cfg = cfg.replace(attn_decode=args.attn_decode)
    rt = Runtime()
    model = build_model(cfg, rt)
    params = model.init(jax.random.key(args.seed))
    rng = np.random.default_rng(args.seed)
    prompts = jnp.asarray(
        rng.integers(2, cfg.vocab_size, size=(args.batch, args.prompt_len)),
        jnp.int32,
    )
    audio = None
    if cfg.family == "audio":
        from repro.models.whisper import N_AUDIO_CTX, N_MELS

        # each request's own 30 s of mels, in the range of whisper's
        # normalised log-mel
        audio = jnp.asarray(rng.uniform(
            -1, 1, (args.batch, 2 * N_AUDIO_CTX, N_MELS)), jnp.float32)
    if args.quant:
        cfg, params = quantize_for_serving(model, params, prompts, audio)
        model = build_model(cfg, rt)
    cache_len = args.prompt_len + args.gen
    cache_len = resolve_cache_len(cfg, cache_len, args.prompt_len, args.gen)
    journal = RequestJournal(args.run_dir) if args.run_dir else None
    t0 = time.perf_counter()
    if journal is not None:
        # a previous process crashed mid-request: finish its work first
        for rid, rtoks, _rdone in replay_pending(
            model, params, journal, deadline_s=args.deadline_s,
            max_retries=args.retries, run_dir=args.run_dir,
        ):
            obs.info("serve",
                     f"sample[{rid}]: {np.asarray(rtoks[0][:16])}")
    for r in range(args.requests):
        toks, done = generate(
            model, params, prompts, gen_len=args.gen,
            cache_len=cache_len, audio=audio, temperature=args.temperature,
            seed=args.seed, deadline_s=args.deadline_s,
            max_retries=args.retries, run_dir=args.run_dir,
            journal=journal, request_id=f"req{r}",
        )
        if args.requests > 1:
            obs.info("serve", f"sample[req{r}]: {np.asarray(toks[0][:16])}")
    dt = time.perf_counter() - t0
    # the summary facts the obs report CLI rebuilds these lines from —
    # metrics.json alone must reproduce this stdout summary
    reg = obs.REGISTRY
    run = reg.facts("serve.run")
    run.set("arch", cfg.name)
    run.set("shape", tuple(toks.shape))
    n_tok = args.requests * args.batch * args.gen
    run.set("elapsed_s", f"{dt:.2f}")
    run.set("tok_per_s", f"{n_tok / dt:.1f}")
    run.set("recyclable", int(done.sum()))
    run.set("batch", args.batch)
    run.set("eos_id", cfg.eos_id)
    run.set("sample", np.asarray(toks[0][:16]))
    obs.info("serve",
             f"generated {toks.shape} x{args.requests} in {dt:.2f}s "
             f"({n_tok / dt:.1f} tok/s); "
             f"{int(done.sum())}/{args.batch} slots recyclable "
             f"(eos={cfg.eos_id})")
    from repro.kernels import ops as kops

    for akey, impl in sorted(kops.ATTN_DECODE_DISPATCH.items()):
        # one line per attention-read shape: CI asserts the fused kernel
        # actually dispatched (the autotune key names the cache shape);
        # the dedup-counted log stays bounded however long the run was
        obs.info("serve",
                 f"attn-decode: impl={impl} key={akey} "
                 f"calls={kops.ATTN_DECODE_DISPATCH.count(akey)}")
    kw = cache_kw(audio)
    bytes_now = cache_nbytes(model.cache_defs(args.batch, cache_len, **kw),
                             cfg.param_dtype)
    fp_model = build_model(cfg.replace(kv_quant="fp"), rt)
    bytes_fp = cache_nbytes(fp_model.cache_defs(args.batch, cache_len, **kw),
                            cfg.param_dtype)
    reg.gauge("serve.kv_cache_bytes").set(float(bytes_now), kind="served")
    reg.gauge("serve.kv_cache_bytes").set(float(bytes_fp), kind="fp")
    obs.info("serve",
             f"kv-cache bytes: {bytes_now} "
             f"(fp {bytes_fp}, ratio {bytes_fp / bytes_now:.2f}x)")
    obs.info("serve", f"sample: {np.asarray(toks[0][:16])}")
    for line in HEALTH.summary():
        # one reason-coded line per degradation event — the chaos CI job
        # asserts the expected ones appear (and clean runs assert none do)
        obs.info("serve", f"health: {line}")
    if args.run_dir:
        for p in obs.write_artifacts(args.run_dir):
            obs.info("serve", f"obs artifact: {p}")


if __name__ == "__main__":
    main()
