"""Model FLOP/s utilisation of prefill: the prefill work of the window's
batches, counted from the configuration's shapes (``configs/<name>.py``
``work``), over the program's summed prefill time times the chip's bf16
peak. Layer: the model step
(``models/transformer.py``). Should move ``tokens_per_s``."""


def read(run):
    total, n = run.counters["prefill_s"]
    if not n or total <= 0:
        return None
    return 100 * run.work["prefill_flops"] * n / (
        total * run.peaks["bf16_flops"])
