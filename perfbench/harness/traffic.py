"""The one traffic generator: a mix's parameters in, seeded requests out.

A mix (``traffic/<name>.json``) is data:

    {"loop": "closed", "clients": 32, "prompt_tokens": 512,
     "output_tokens": 128, "sampling": "greedy"}

``closed`` means the ``clients`` wait for their replies: the program's
serving entry takes one static batch, so the clients form one batch of
``clients`` requests, and the next batch is sent when the last one returns.
Every seed gives the same sizes; the seed picks the prompt tokens and
each request's other inputs only, so runs of different seeds do the same
work. A batch's other inputs (audio, say) come from the configuration's
``request_inputs``, drawn from ``inputs_rng``: a stream of the seed apart
from the prompts', so a configuration that adds inputs leaves every
prompt as it was.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

LOOPS = ("closed",)
SAMPLING = ("greedy",)


@dataclass(frozen=True)
class Mix:
    clients: int
    prompt_tokens: int
    output_tokens: int

    @classmethod
    def parse(cls, d: dict) -> "Mix":
        if d.get("loop") not in LOOPS:
            raise ValueError(f"unsupported loop {d.get('loop')!r}")
        if d.get("sampling") not in SAMPLING:
            raise ValueError(f"unsupported sampling {d.get('sampling')!r}")
        mix = cls(int(d["clients"]), int(d["prompt_tokens"]),
                  int(d["output_tokens"]))
        if min(mix.clients, mix.prompt_tokens) < 1 or mix.output_tokens < 2:
            raise ValueError(f"mix too small: {mix}")
        return mix


#: the batch stream used to warm up, apart from the measured batches
WARMUP = -1


def prompts(mix: Mix, vocab: int, seed: int, batch: int) -> np.ndarray:
    """The ``batch``-th batch of prompts, (clients, prompt_tokens) int32,
    token ids uniform over the vocabulary."""
    ss = np.random.SeedSequence(int(seed), spawn_key=(batch + 1,))
    rng = np.random.default_rng(ss)
    return rng.integers(0, vocab, size=(mix.clients, mix.prompt_tokens),
                        dtype=np.int32)


def inputs_rng(seed: int, batch: int) -> np.random.Generator:
    """The generator the ``batch``-th batch's non-token inputs are drawn
    from: a stream of its own beside the prompts' ``(batch + 1,)``."""
    return np.random.default_rng(
        np.random.SeedSequence(int(seed), spawn_key=(batch + 1, 1)))
