"""Device idle time per decode step inside the program's
``serve.decode.dispatch`` and ``serve.decode.wait`` spans together: the
host moving the step's position to the device and dispatching the jitted
decode step, then blocking on its logits, so the step's whole launch and
any gap inside the compiled step (from the profiler trace, over the decode
steps of the traced batches, as ``serve.host_ms_per_step`` counts them).
The two spans are read as one because where the launch's idle falls
between them moves with how the profiler lines up the host and device
clocks; their sum does not. Layer: the serve loop (``launch/serve.py``
``_generate_once``). Should move ``tokens_per_s``."""

SPANS = ("serve.decode.dispatch", "serve.decode.wait")


def read(run):
    tr = run.trace
    parts = [tr.spans(s) for s in SPANS]
    steps = len(run.traced) * (run.mix.output_tokens - 1)
    if not all(parts) or not steps or not tr.devices:
        return None
    return 1e3 * sum(tr.idle_within(p) for p in parts) / steps
