"""Host time per decode step in which the device ran nothing: the device's
idle time inside the traced ``bench.generate`` spans, over the decode
steps of the traced batches (from the profiler trace). The loop is
host-synchronous, so nearly all of it falls between decode steps; the
few gaps around the prefill are counted too. Layer: the serve loop
(``launch/serve.py`` ``_generate_once``). Should move ``tokens_per_s``."""

from harness.bench import ANNOTATION


def read(run):
    steps = len(run.traced) * (run.mix.output_tokens - 1)
    if not steps or not run.trace.devices:
        return None
    return 1e3 * run.trace.idle_within(run.trace.spans(ANNOTATION)) / steps
