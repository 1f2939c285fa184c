"""Device time per prefill spent after the prefill forward: device busy
time inside the program's ``serve.prefill`` spans less that inside their
``serve.prefill.forward`` spans — the decode cache's allocation, int8
quantization and padding, then the first logits screen and argmax
(ROADMAP S5). From the profiler trace, per traced prefill; counts only
what ran before the ``serve.prefill`` span closed, which in-order
execution and the screen's read-back make nearly all of it. Layer: the
serve loop (``launch/serve.py`` ``prefill_cache``). Should move
``tokens_per_s``."""

SPAN = "serve.prefill"
FORWARD = "serve.prefill.forward"


def read(run):
    tr = run.trace
    prefills, forwards = tr.spans(SPAN), tr.spans(FORWARD)
    if not prefills or not forwards or not tr.devices:
        return None
    busy = (sum(tr.busy_s(s, e) for s, e in prefills)
            - sum(tr.busy_s(s, e) for s, e in forwards))
    return 1e3 * busy / len(prefills)
