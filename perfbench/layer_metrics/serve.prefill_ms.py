"""Mean time of one prefill call, from the program's ``serve.prefill_s``
histogram (its exact sum and count; never its bucket quantiles): prompt
forward, cache quantize and pad, synced. Layer: the serve loop
(``launch/serve.py``). Should move ``tokens_per_s``."""


def read(run):
    total, n = run.counters["prefill_s"]
    return 1e3 * total / n if n else None
