"""Device time per prefill spent in the encoder program: device busy time
inside the program's ``serve.prefill.encode`` spans (the conv frontend,
the encoder's layers and each decoder layer's cross K/V, synced at the
span's end), per traced prefill. From the profiler trace; nothing where
the program has no such span (a decoder-only model, or a program without
the span). Layer: the serve loop (``launch/serve.py`` ``prefill_cache``).
Should move ``tokens_per_s``."""

SPAN = "serve.prefill.encode"


def read(run):
    tr = run.trace
    spans = tr.spans(SPAN)
    if not spans or not tr.devices:
        return None
    return 1e3 * sum(tr.busy_s(s, e) for s, e in spans) / len(spans)
