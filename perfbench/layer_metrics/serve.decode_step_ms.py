"""Mean wall time of one decode step, from the program's
``serve.decode_step_s`` histogram (exact sum and count): the step call,
its sync, the logits screen and the sampling. Layer: the serve loop
(``launch/serve.py`` ``_generate_once``). Should move ``tokens_per_s``."""


def read(run):
    total, n = run.counters["decode_step_s"]
    return 1e3 * total / n if n else None
