"""Frozen vocabularies for metric and span names (DESIGN.md §12).

Like ``health.Reason``, the observability namespace is closed: the
registry rejects unregistered metric names at runtime and the
``repro.analysis`` lint pass enforces the same at every literal call
site (and bans f-string names outright). A typo'd metric silently forks
the series CI and the report CLI read — a new instrument means a new
member HERE first.

Naming scheme: ``<layer>.<what>[_<unit>]`` — layers are ``dispatch``
(the ops ladder), ``autotune``, ``health``, ``serve``, ``train``;
durations carry an ``_s`` suffix, monotonically increasing totals a
``_total`` suffix. Label keys are reused from the existing
vocabularies: ``site`` (dispatch-ladder site), ``key`` (autotune shape
key), ``rung`` (ladder rung name), ``reason``/``action``
(health.Reason), ``arch`` (model config name).
"""
from __future__ import annotations

#: counter / gauge / histogram names the Registry accepts
METRICS = frozenset({
    # kernel dispatch (ops._ladder) — per autotune shape key
    "dispatch.calls",
    "dispatch.log_calls",          # named DispatchLog mirrors (key hits)
    # autotune searches
    "autotune.searches",
    "autotune.candidates",
    "autotune.pruned",
    "autotune.cost_skipped",       # ranked early-exit leftovers, untimed
    # health registry mirror (site/reason/action labels)
    "health.events",
    "health.repromote",            # circuit-breaker probation passed
    # runtime fault domain (DESIGN.md §15): in-compiled-call failures
    "runtime.demote",              # guest trap / sentinel → rung demoted
    "runtime.retrace_ms",          # cumulative re-jit cost after demotion
    # serving
    "serve.requests",
    "serve.retries",
    "serve.deadline_exceeded",
    "serve.stragglers",
    "serve.tokens_generated",
    "serve.prefill_s",
    "serve.decode_step_s",
    "serve.decode.fused_steps",    # decode steps served by the fused program
    "serve.request_s",
    "serve.slots_total",
    "serve.slots_recyclable",
    "serve.slot_occupancy",
    "serve.kv_cache_bytes",
    "serve.decode.held_cache_bytes",  # cache a decode step reads, not outputs
    "serve.quarantined",           # poisoned slots eos-masked + recycled
    "serve.shed",                  # requests rejected at admission
    "serve.journal_replayed",      # in-flight requests replayed on restart
    # training
    "train.steps",
    "train.tokens",
    "train.step_s",
    "train.tokens_per_s",
    "train.ckpt_save_s",
    "train.resumes",
    "train.loss",
    # string-valued facts tables (Registry.facts)
    "run.info",
    "serve.run",
    "dispatch.attn_decode",
    "dispatch.quant_fallback",
})

#: span names (obs.span); the serve loop's nest as the code runs them:
#: serve.generate > serve.prefill > serve.prefill.{forward,cache,sample}
#: (an encoder-decoder's encoder program in serve.prefill.forward >
#: serve.prefill.encode), then per token serve.decode_step >
#: serve.decode.{dispatch,wait,screen,sample} (the benchmark's serve-loop
#: metrics read these)
SPANS = frozenset({
    "autotune.search",
    "autotune.candidate",
    "serve.generate",
    "serve.prefill",
    "serve.prefill.forward",
    "serve.prefill.encode",
    "serve.prefill.cache",
    "serve.prefill.sample",
    "serve.decode_step",
    "serve.decode.dispatch",
    "serve.decode.wait",
    "serve.decode.screen",
    "serve.decode.sample",
    "serve.quantize",
    "train.step",
    "train.ckpt_save",
    "train.resume",
    "health.event",
})
