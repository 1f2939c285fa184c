"""The decode step's share of its roofline: for each step the least time
the chip could take (the larger of its operations at the bf16 peak and its
bytes at the HBM bandwidth), summed, over the program's summed decode-step
time. The bytes are the weights a step reads plus the live int8 K/V rows
(prompt + tokens so far, not the padded capacity). Bound by bytes. Layer:
the model step. Should move ``tokens_per_s``."""

from harness.counts import least_seconds


def read(run):
    total, n = run.counters["decode_step_s"]
    steps = run.work["decode_steps"]
    if not n or total <= 0 or n % len(steps):
        return None
    least = sum(least_seconds(f, b, run.peaks) for f, b in steps)
    return 100 * least * (n // len(steps)) / total
