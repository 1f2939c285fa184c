"""Device idle time per decode step inside the program's
``serve.decode.sample`` spans: the host's append of the step's token,
which the compiled decode step already chose, masked and marked done
(from the profiler trace, over the decode steps of the traced batches,
as ``serve.host_ms_per_step`` counts them). Layer: the serve loop
(``launch/serve.py`` ``_generate_once``). Should move
``tokens_per_s``."""

SPAN = "serve.decode.sample"


def read(run):
    spans = run.trace.spans(SPAN)
    steps = len(run.traced) * (run.mix.output_tokens - 1)
    if not spans or not steps or not run.trace.devices:
        return None
    return 1e3 * run.trace.idle_within(spans) / steps
