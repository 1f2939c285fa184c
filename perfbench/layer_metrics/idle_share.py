"""Share of the traced window in which no operation ran on the device:
1 - busy / window, busy being the union of the device's op intervals
(profiler trace), the window running from the first traced
``bench.generate`` span to the last. Layer: the device. Should move
``tokens_per_s``."""


def read(run):
    if not run.trace.devices:
        return None
    w0, w1 = run.trace_window
    return 100 * (1 - run.trace.busy_s(w0, w1) / (w1 - w0))
