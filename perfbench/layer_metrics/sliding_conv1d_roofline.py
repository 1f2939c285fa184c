"""The paper's sliding conv1d kernel as a share of its roofline, over an
audio frontend's convolutions: the least time of each convolution of the
traced batches (``work()["kernels"]["sliding_conv1d"]``: a list of one
(operations, bytes) pair per convolution of a batch, each bound on its
own), over the device time of the kernel's trace events, found by the
kernel's ``pallas_call`` name. A strided convolution runs the same kernel
over its phase-split input, under the same name. Nothing where the
configuration counts no such convolution or the trace has no such
kernel. Layer: kernels (``kernels/sliding_conv1d.py`` via
``ops.conv1d``). Should move ``tokens_per_s``."""

from harness.counts import least_seconds

KERNEL = "sliding_conv1d"
NAMES = ("conv1d_sliding_pallas",)


def read(run):
    work = run.work.get("kernels", {}).get(KERNEL)
    if work is None or not run.traced:
        return None
    secs = run.trace.kernel_s(NAMES, *run.trace_window)
    if secs <= 0:
        return None
    least = sum(least_seconds(f, b, run.peaks) for f, b in work)
    return 100 * least * len(run.traced) / secs
