"""The fused decode-attention kernel on cross-attention reads as a share of
its roofline: the least time of every cross read of the traced batches
(each decode step's int8 cross K/V rows of every encoder position and
their scales; ``work()["kernels"]["cross_attention_decode"]``), over the
device time of the kernel's trace events under the name the cross reads
give its ``pallas_call``. Bound by bytes. Nothing where the configuration
counts no cross reads or the trace has no such kernel. Layer: kernels
(``kernels/attention_decode.py`` via ``ops.attention_decode``). Should
move ``tokens_per_s``."""

from harness.counts import least_seconds

KERNEL = "cross_attention_decode"
NAMES = ("cross_attention_decode_pallas",)


def read(run):
    work = run.work.get("kernels", {}).get(KERNEL)
    if work is None or not run.traced:
        return None
    secs = run.trace.kernel_s(NAMES, *run.trace_window)
    if secs <= 0:
        return None
    least = least_seconds(*work, run.peaks) * len(run.traced)
    return 100 * least / secs
