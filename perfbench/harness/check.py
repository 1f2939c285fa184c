"""Decide ``correct``: served greedy tokens against the plain reference.

After the window a sample of the finished requests, drawn from the seed,
is run through the configuration's float32 reference, teacher-forced over
each prompt and its served tokens, given the same other inputs (audio,
say) as the served request. For each served token the reference's
logits give its *gap*: how far the token's logit lies below the
reference's best at that position. A greedy server that computes what the
reference computes serves gaps of rounding size; one that computes
something else serves tokens the reference ranks far below its best. The
widest gap over the sample is compared with the cell's limit
(``cells/<workload>.json``). A slot's tokens after its end-of-sequence
token are pinned by the program and are not compared.

Each control puts the reference itself in the program's place with one
stated precision taken one step down (``refmath.CONTROLS``: int8 weight
products, or int4 K/V rows): at each position the token it puts first is
read on the float32 reference's logits, the same way, and judged by the
same limit (``judge``).
"""
from __future__ import annotations

import math

import numpy as np

from harness.refmath import CONTROLS

#: served tokens the sample should hold, and the fewest requests in it
SAMPLE_TOKENS = 256
MIN_REQUESTS = 4
#: a sample run that never finished reads as this (JSON has no infinity)
NOT_FINITE = 1e30


def sample(seed: int, finished: list[tuple[int, int]], gen: int):
    """Requests (batch, slot) to compare, drawn from the seed."""
    n = min(len(finished), max(MIN_REQUESTS, math.ceil(SAMPLE_TOKENS / gen)))
    rng = np.random.default_rng(np.random.SeedSequence(int(seed),
                                                       spawn_key=(0,)))
    pick = rng.choice(len(finished), size=n, replace=False)
    return [finished[i] for i in sorted(pick)]


def served_len(tokens: np.ndarray, eos: int) -> int:
    """Tokens a slot really served: up to and with its first eos."""
    hit = np.flatnonzero(tokens == eos)
    return int(hit[0]) + 1 if hit.size else len(tokens)


def gaps(ref_logits: np.ndarray, chosen: np.ndarray) -> np.ndarray:
    """How far each chosen token's logit lies below the best, per row."""
    ref = np.asarray(ref_logits, np.float64)
    if not np.isfinite(ref).all():
        return np.full(len(chosen), NOT_FINITE)
    return ref.max(-1) - ref[np.arange(len(chosen)), chosen]


def compare(ref_module, weights, config: dict, requests, *, control=False):
    """``requests``: (prompt, served tokens, other inputs) triples, the
    served tokens already cut at eos, the other inputs the request's own
    row of each of its batch's non-token inputs (``{name: row}``, or None
    where the configuration has none), handed to the reference's
    ``logits`` as its ``extra``. Returns the widest gap of the served
    tokens and, with ``control``, each control's widest gap under
    ``"controls"``."""
    widest, n = 0.0, 0
    widest_c = dict.fromkeys(CONTROLS, 0.0)
    for prompt, served, extra in requests:
        ref = ref_module.logits(weights, config, extra, prompt, served)
        widest = max(widest, float(gaps(ref, served).max()))
        n += len(served)
        for name in CONTROLS if control else ():
            low = ref_module.logits(weights, config, extra, prompt, served,
                                    lower=name)
            widest_c[name] = max(widest_c[name],
                                 float(gaps(ref, low.argmax(-1)).max()))
    out = {"max_logit_gap": widest, "tokens": n}
    if control:
        out["controls"] = {name: {"max_logit_gap": g, "tokens": n}
                           for name, g in widest_c.items()}
    return out


def judge(reading: dict, limits: dict) -> tuple[bool, dict]:
    """``correct``, and each number compared beside its limit."""
    compared = {name: {"value": reading[name], "limit": limit}
                for name, limit in limits.items()}
    correct = reading["tokens"] > 0 and all(
        c["value"] <= c["limit"] for c in compared.values())
    return correct, compared
