"""Model FLOP/s utilisation of decoding: the decode steps' work, counted
from the configuration's shapes with the live cache length of each step,
over the program's summed decode-step time times the chip's bf16 peak.
Decoding is bound by bytes, so this stays small; ``decode_step_roofline``
is the bound that counts bytes. Layer: the model step. Should move
``tokens_per_s``."""


def read(run):
    total, n = run.counters["decode_step_s"]
    steps = run.work["decode_steps"]
    if not n or total <= 0 or n % len(steps):
        return None
    flops = sum(f for f, _ in steps) * (n // len(steps))
    return 100 * flops / (total * run.peaks["bf16_flops"])
