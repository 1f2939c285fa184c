"""Device idle time per decode step inside the program's
``serve.decode_step`` spans but outside their four parts (dispatch,
wait, screen, sample): the step's own bookkeeping — step histogram,
``HEALTH.tick``, watchdog, heartbeat, deadline check (from the profiler
trace, over the decode steps of the traced batches, as
``serve.host_ms_per_step`` counts them). Layer: the serve loop
(``launch/serve.py`` ``_generate_once``). Should move
``tokens_per_s``."""

STEP = "serve.decode_step"
PARTS = ("serve.decode.dispatch", "serve.decode.wait",
         "serve.decode.screen", "serve.decode.sample")


def read(run):
    tr = run.trace
    spans = tr.spans(STEP)
    parts = [tr.spans(p) for p in PARTS]
    steps = len(run.traced) * (run.mix.output_tokens - 1)
    if not spans or not all(parts) or not steps or not tr.devices:
        return None
    idle = tr.idle_within(spans) - sum(tr.idle_within(p) for p in parts)
    return 1e3 * idle / steps
