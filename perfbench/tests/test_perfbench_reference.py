"""The plain float32 references against the program, at a small size on
the CPU: prefill logits and greedy decode logits through the cache.

The program runs here in float32 with a float cache, so the two agree to
rounding of float32 (1e-3 of the largest logit leaves a wide margin); the
served path at the cells' precision (bf16, int8 cache) is held to the
reference by the run's own output check (``test_perfbench_runs.py``)."""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH / "tests"), str(BENCH.parent / "src")]

from harness import bench, manifest, traffic, weights  # noqa: E402
import tiny  # noqa: E402

TOL = 1e-3


@pytest.mark.parametrize("workload", sorted(tiny.CELLS))
def test_reference_matches_program_logits(tmp_path, workload):
    import jax.numpy as jnp

    from repro.launch import serve

    cell = manifest.resolve(workload, tiny.make(tmp_path))
    cell.config["torch_dtype"] = "float32"
    cell.config["program"]["overrides"]["kv_quant"] = "fp"
    mix = traffic.Mix.parse(cell.traffic)
    model = bench.build(cell)
    params = weights.make(model, 2**40 + 3)
    B, P, G = mix.clients, mix.prompt_tokens, mix.output_tokens
    prompts = traffic.prompts(mix, model.cfg.vocab_size, 5, 0)
    cache_len = serve.resolve_cache_len(model.cfg, P + G, P, G)
    logits, cache = serve.prefill_cache(model, params, jnp.asarray(prompts),
                                        cache_len=cache_len, gen_len=G)
    _, decode = serve._jitted(model)
    got = [np.asarray(logits[:, -1])]
    tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
    toks = [tok]
    for i in range(G - 1):
        logits, cache = decode(params, cache, tok, jnp.int32(P + i))
        got.append(np.asarray(logits[:, -1]))
        tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
        toks.append(tok)
    got = np.stack(got, 1)  # (B, G, V)
    served = np.asarray(jnp.concatenate(toks, 1))
    inputs = cell.reference.request_inputs(
        cell.config, traffic.inputs_rng(5, 0), B)
    for b in range(B):
        want = cell.reference.logits(
            params, cell.config, bench.slot_inputs(inputs, b),
            prompts[b], served[b])
        err = np.abs(got[b] - want).max() / np.abs(want).max()
        assert err < TOL, (workload, b, err)
