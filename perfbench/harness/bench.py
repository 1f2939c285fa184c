"""One run of one cell: set up, measure whole batches, check, report.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Set-up (counted in ``setup_s``, from process start): refuse a device
missing from the peak table, make the weights on the device from the
seed, and serve two batches of the cell's own shape so every program the
window runs is compiled or read from the persistent cache, and whatever
else a first call costs is paid. The window
then sends whole batches through ``repro.launch.serve.generate`` until
``--seconds`` have passed (the program returns a batch's tokens only when
the batch ends, so a window cut inside a batch would count half a batch).
Each batch is its prompts and the configuration's other inputs for it
(``batch_inputs``), both drawn from the seed before the batch is sent and
passed to ``generate`` as keywords; the reference is given the same rows.
A request fails when its batch raised a ``HEALTH`` event (a demoted
kernel, a retry, truncation, shed or quarantine), or when any decode
attention read was served by anything but the Pallas kernel.

With ``--trace 1`` the JAX profiler records the first whole batches, up
to ``TRACE_SECONDS``, each inside a ``bench.generate`` annotation; the
line then carries the per-layer metrics, the device's busy time and a
breakdown. Either way the run ends by comparing a sample of the served
requests with the plain reference (``check.py``).
"""
from __future__ import annotations

import contextlib
import functools
import math
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass

import numpy as np

from harness import check, manifest, peaks as peaks_mod, traffic, weights

TRACE_SECONDS = 5.0
ANNOTATION = "bench.generate"
WARMUP_BATCHES = 2


def log(msg: str) -> None:
    print(f"[perfbench] {msg}", file=sys.stderr, flush=True)


@dataclass
class Batch:
    index: int
    t_submit: float
    t_done: float
    tokens: np.ndarray
    failed: bool
    traced: bool


@dataclass
class Run:
    """What a per-layer metric's ``read(run)`` is given."""

    cell: manifest.Cell
    peaks: dict
    mix: traffic.Mix
    work: dict  # the configuration's work() for one batch
    batches: list[Batch]
    counters: dict  # program histograms over the window: (sum s, count)
    trace: object = None  # tracing.Trace of the traced batches
    trace_window: tuple[float, float] | None = None

    @property
    def traced(self) -> list[Batch]:
        return [b for b in self.batches if b.traced]


class CompileCounter:
    """Counts JAX trace and compile events while armed."""

    def __init__(self):
        import jax

        self.armed = False
        self.events = 0

        def listen(event: str, secs: float, **_kw) -> None:
            if self.armed and event.startswith("/jax/core/compile/"):
                self.events += 1

        jax.monitoring.register_event_duration_secs_listener(listen)


def latency_p95_s(batches: list[Batch], clients: int) -> float:
    """95th percentile over all requests of submit -> response: every
    client of a batch waits the batch's time."""
    return float(np.percentile([b.t_done - b.t_submit for b in batches
                                for _ in range(clients)], 95))


def _health_events() -> int:
    from repro.health import HEALTH

    return sum(ev.count for ev in HEALTH.events)


def _decode_impls() -> set[str]:
    """The implementations that served decode attention reads so far."""
    from repro.kernels import ops

    return {impl for _, impl in ops.ATTN_DECODE_DISPATCH.items()}


def build(cell: manifest.Cell):
    """The program's model for the cell's configuration."""
    from repro.configs import get_config
    from repro.distributed.sharding import Runtime
    from repro.models import build_model

    prog = cell.config["program"]
    cfg = get_config(prog["arch"]).replace(
        **cell.reference.program_fields(cell.config), **prog["overrides"])
    return build_model(cfg, Runtime())


def make_weights(cell: manifest.Cell, model, seed: int):
    """The seeded weights, by the rule the configuration's optional
    ``seeded_weights`` key adjusts (``weights.make``)."""
    return weights.make(model, seed, **cell.config.get("seeded_weights", {}))


def batch_inputs(cell: manifest.Cell, mix: traffic.Mix, seed: int,
                 batch: int) -> dict[str, np.ndarray]:
    """The ``batch``-th batch's non-token inputs: the configuration's
    ``request_inputs``, drawn from the batch's own stream of the seed, one
    row per client, keyed by ``generate``'s keyword names."""
    got = cell.reference.request_inputs(
        cell.config, traffic.inputs_rng(seed, batch), mix.clients)
    for name, v in got.items():
        if np.shape(v)[:1] != (mix.clients,):
            raise ValueError(f"{cell.config_name}: input {name!r} has shape "
                             f"{np.shape(v)}, not {mix.clients} rows")
    return got


def slot_inputs(inputs: dict[str, np.ndarray], slot: int) -> dict | None:
    """One request's rows of its batch's inputs, or None where there are
    none: what the reference's ``extra`` receives."""
    return {name: v[slot] for name, v in inputs.items()} or None


def _on_device(inputs: dict[str, np.ndarray]) -> dict:
    import jax.numpy as jnp

    return {name: jnp.asarray(v) for name, v in inputs.items()}


def serve_fn(model, params, mix: traffic.Mix):
    from repro.launch import serve

    P, G = mix.prompt_tokens, mix.output_tokens
    cache_len = serve.resolve_cache_len(model.cfg, P + G, P, G)
    return functools.partial(serve.generate, model, params, gen_len=G,
                             cache_len=cache_len)


def served_requests(cell, mix, model, batches, seed, pick):
    """(prompt, served tokens, other inputs) of each picked (batch, slot),
    the prompt and inputs drawn again from the seed."""
    eos = model.cfg.eos_id
    by_index = {b.index: b for b in batches}
    out = []
    for bi, slot in pick:
        prompt = traffic.prompts(mix, model.cfg.vocab_size, seed, bi)[slot]
        extra = slot_inputs(batch_inputs(cell, mix, seed, bi), slot)
        toks = by_index[bi].tokens[slot]
        out.append((prompt, toks[: check.served_len(toks, eos)], extra))
    return out


def readings(cell, model, mix: traffic.Mix, seed: int, *, control=False):
    """The output check's numbers for ``seed`` outside a timed window: as
    many whole batches as the run's sample needs, then the same sample and
    comparison as a run; with ``control`` each control's too."""
    import jax.numpy as jnp

    params = make_weights(cell, model, seed)
    generate = serve_fn(model, params, mix)
    n_req = max(check.MIN_REQUESTS,
                math.ceil(check.SAMPLE_TOKENS / mix.output_tokens))
    batches = []
    for i in range(math.ceil(n_req / mix.clients)):
        p = traffic.prompts(mix, model.cfg.vocab_size, seed, i)
        inputs = batch_inputs(cell, mix, seed, i)
        toks = np.asarray(generate(jnp.asarray(p), **_on_device(inputs))[0])
        batches.append(Batch(i, 0.0, 0.0, toks, False, False))
    finished = [(b.index, s) for b in batches for s in range(mix.clients)]
    pick = check.sample(seed, finished, mix.output_tokens)
    return check.compare(
        cell.reference, params, cell.config,
        served_requests(cell, mix, model, batches, seed, pick),
        control=control)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, root=manifest.CHECKOUT) -> dict:
    """Run the cell once and return the result line as a dict."""
    cell = manifest.resolve(workload, root)
    mix = traffic.Mix.parse(cell.traffic)
    import jax
    import jax.numpy as jnp

    devices = jax.devices()
    peaks = peaks_mod.check_devices(devices, cell.chips)
    from repro import compile_cache, obs

    log(f"compile cache: {compile_cache.enable()}")
    compiles = CompileCounter()
    model = build(cell)
    params = make_weights(cell, model, seed)
    jax.block_until_ready(params)
    generate = serve_fn(model, params, mix)
    V = model.cfg.vocab_size
    warm = traffic.prompts(mix, V, seed, traffic.WARMUP)
    warm_inputs = batch_inputs(cell, mix, seed, traffic.WARMUP)
    # the first batch compiles (or reads the cache); the second takes
    # whatever else a first call costs out of the window
    for _ in range(WARMUP_BATCHES):
        np.asarray(generate(jnp.asarray(warm), **_on_device(warm_inputs))[0])
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f}s ({WARMUP_BATCHES} warm-up batches)")

    reg, lab = obs.REGISTRY, dict(arch=model.cfg.name)
    hists = {n: reg.histogram(f"serve.{n}") for n in ("prefill_s",
                                                       "decode_step_s")}
    before = {n: (h.sum(**lab), h.count(**lab)) for n, h in hists.items()}
    health = _health_events()
    trace_dir = tempfile.mkdtemp(prefix="perfbench-trace-") if trace else None
    tracing = False
    if trace:
        # no Python function tracing: the harness's annotations and JAX's
        # own dispatch spans say what the host was doing
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        tracing = True
    compiles.armed = True
    batches: list[Batch] = []
    per_batch = []  # (prefill s, decode s) of each batch, program's clock
    t0 = time.perf_counter()
    while not batches or time.perf_counter() - t0 < seconds:
        i = len(batches)
        prompts = traffic.prompts(mix, V, seed, i)
        inputs = batch_inputs(cell, mix, seed, i)
        ann = (jax.profiler.TraceAnnotation(ANNOTATION) if tracing
               else contextlib.nullcontext())
        sums = [h.sum(**lab) for h in hists.values()]
        t_sub = time.perf_counter()
        with ann:
            toks = np.asarray(generate(jnp.asarray(prompts),
                                       **_on_device(inputs))[0])
        t_done = time.perf_counter()
        per_batch.append([h.sum(**lab) - s
                          for h, s in zip(hists.values(), sums)])
        now_health = _health_events()
        batches.append(Batch(i, t_sub, t_done, toks, now_health != health,
                             tracing))
        health = now_health
        if tracing and t_done - t0 >= TRACE_SECONDS:
            jax.profiler.stop_trace()
            tracing = False
    compiles.armed = False
    if tracing:
        jax.profiler.stop_trace()
    t_end = batches[-1].t_done
    if compiles.events:
        log(f"{compiles.events} trace/compile event(s) inside the window")
    log("batch seconds: " + " ".join(f"{b.t_done - b.t_submit:.4f}"
                                     for b in batches))
    # a host stall shows as one batch's decode or prefill time grown
    log("batch prefill/decode seconds: " + " ".join(
        "/".join(f"{v:.4f}" for v in row) for row in per_batch))

    impls = _decode_impls()
    if impls != {"pallas"}:
        log(f"decode attention served by {sorted(impls)}, not pallas only")
        for b in batches:
            b.failed = True
    stats = devices[0].memory_stats() or {}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    counters = {n: (h.sum(**lab) - before[n][0], h.count(**lab) - before[n][1])
                for n, h in hists.items()}

    finished = [(b.index, s) for b in batches if not b.failed
                for s in range(mix.clients)]
    pick = check.sample(seed, finished, mix.output_tokens)
    reading = check.compare(
        cell.reference, params, cell.config,
        served_requests(cell, mix, model, batches, seed, pick))
    correct, compared = check.judge(reading, cell.limits)

    eos = model.cfg.eos_id
    n_failed = sum(mix.clients for b in batches if b.failed)
    result = {"correct": bool(correct),
              "attempted": len(batches) * mix.clients, "failed": n_failed}
    if not trace:
        served = sum(check.served_len(row, eos) for b in batches
                     if not b.failed for row in b.tokens)
        values = {"tokens_per_s": served / (t_end - t0),
                  "latency_p95_s": latency_p95_s(batches, mix.clients),
                  "setup_s": setup_s}
        metrics = {m.name: {"value": values[m.name], "unit": m.unit}
                   for m in cell.end_to_end}
    else:
        from harness import tracing as tr

        run = Run(cell, peaks, mix,
                  cell.reference.work(cell.config, mix.clients,
                                      mix.prompt_tokens, mix.output_tokens),
                  batches, counters)
        run.trace = tr.load(trace_dir)
        run.trace_window = run.trace.window(ANNOTATION)
        w0, w1 = run.trace_window
        device["busy_s"] = run.trace.busy_s(w0, w1)
        device["window_s"] = w1 - w0
        metrics = {}
        for m in cell.per_layer:
            v = m.reader.read(run)
            if v is not None:
                metrics[m.name] = {"value": float(v), "unit": m.unit}
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in run.trace.top_ops(w0, w1)],
            "idle_gaps": [[n, s] for n, s in run.trace.idle_gaps(w0, w1)],
        }
        shutil.rmtree(trace_dir, ignore_errors=True)
    result["metrics"] = metrics
    result["device"] = device
    # the numbers compared, each beside its limit: the line's last key
    result["compared"] = compared
    log(f"compared {reading['tokens']} served tokens of {len(pick)} "
        f"requests")
    return result
