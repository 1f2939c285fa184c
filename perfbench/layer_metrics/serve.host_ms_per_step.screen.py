"""Device idle time per decode step inside the program's
``serve.decode.screen`` spans: the read-back to the host of the (B,)
``bad`` mask that the compiled decode step computed, and the host's
check of it (from the profiler trace, over the decode steps of the
traced batches, as ``serve.host_ms_per_step`` counts them). Layer: the
serve loop (``launch/serve.py`` ``_screen``). Should move
``tokens_per_s``."""

SPAN = "serve.decode.screen"


def read(run):
    spans = run.trace.spans(SPAN)
    steps = len(run.traced) * (run.mix.output_tokens - 1)
    if not spans or not steps or not run.trace.devices:
        return None
    return 1e3 * run.trace.idle_within(spans) / steps
