"""95th percentile, over all requests of the window, of submit -> response
on the benchmark's clock: the end-to-end ``latency_p95_s``, read per layer
in cells where it is the slowest of a dozen batches, so a stall of the
host anywhere in the window sets it. Layer: the serve loop
(``launch/serve.py``, host-synchronous per decode step). Should move
``tokens_per_s``."""
from harness.bench import latency_p95_s


def read(run):
    return latency_p95_s(run.batches, run.mix.clients) if run.batches \
        else None
