"""The fused decode-attention kernel as a share of its roofline: the least
time of every decode read in the traced batches (the live int8 K/V rows
and their scales of each step;
``work()["kernels"]["attention_decode"]``), over the device time of the
kernel's trace events, found by the name the
trace gives the Pallas call: that of its jitted wrapper. Bound by
bytes. Layer: kernels (``kernels/attention_decode.py``). Should move
``tokens_per_s``."""

from harness.counts import least_seconds

KERNEL = "attention_decode"
NAMES = ("decode_attention_pallas",)


def read(run):
    work = run.work["kernels"].get(KERNEL)
    if work is None or not run.traced:
        return None
    secs = run.trace.kernel_s(NAMES, *run.trace_window)
    if secs <= 0:
        return None
    least = least_seconds(*work, run.peaks) * len(run.traced)
    return 100 * least / secs
