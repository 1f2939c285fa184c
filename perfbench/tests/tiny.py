"""A benchmark tree with tiny cells, for the CPU tests.

``make(tmp)`` copies the benchmark directory under ``tmp`` and adds, by
new files only, a tiny configuration of the real one's architecture, two
tiny traffic mixes (short and long prompts) and a cell for each, with a
manifest naming them; the real metrics are kept, each reported in both. The limit is
for these sizes, set from CPU readings over 8 seeds and both mixes: the
tiny program's widest served gap read at most 0.030, the int4 K/V
control's at least 0.234 (the int8-weights control, 0.023-0.078, does not
separate at this size).
"""
from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
REAL = json.loads((BENCH.parent / "BENCHMARK.json").read_text())

QWEN = {
    "source": "tiny test size of qwen3-1.7b",
    "program": {"arch": "qwen3-1.7b",
                "overrides": {"kv_quant": "int8", "attn_decode": "fused",
                              "eos_id": 250}},
    "head_dim": 16, "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_hidden_layers": 2,
    "num_key_value_heads": 2, "rms_norm_eps": 1e-6, "rope_theta": 1000000,
    "tie_word_embeddings": True, "torch_dtype": "bfloat16",
    "vocab_size": 256,
}
MIXES = {
    "tiny": {"loop": "closed", "clients": 2, "prompt_tokens": 8,
             "output_tokens": 8, "sampling": "greedy"},
    "tiny-long": {"loop": "closed", "clients": 2, "prompt_tokens": 32,
                  "output_tokens": 4, "sampling": "greedy"},
}
#: cell -> traffic mix, all of the configuration ``tiny-qwen``
CELLS = {"tiny-qwen.tiny": "tiny", "tiny-qwen.tiny-long": "tiny-long"}


def make(tmp: Path, limit: float = 0.06) -> Path:
    """A checkout-like root under ``tmp`` holding the tiny cells."""
    root = Path(tmp) / "checkout"
    shutil.copytree(BENCH, root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    base = root / "perfbench"
    (base / "configs" / "tiny-qwen.json").write_text(json.dumps(QWEN))
    shutil.copy(base / "configs" / "qwen3-1.7b.py",
                base / "configs" / "tiny-qwen.py")
    for name, mix in MIXES.items():
        (base / "traffic" / f"{name}.json").write_text(json.dumps(mix))
    for cell in CELLS:
        (base / "cells" / f"{cell}.json").write_text(
            json.dumps({"max_logit_gap": limit}))
    man = dict(REAL)
    man["configs"] = [{"name": "tiny-qwen", "source": "tiny test size",
                       "file": "perfbench/configs/tiny-qwen.json",
                       "reduced": [], "why": "CPU test"}]
    man["workloads"] = [{"name": c, "config": "tiny-qwen", "traffic": t,
                         "chips": 1, "why": "CPU test"}
                        for c, t in CELLS.items()]
    # every metric is reported in both tiny cells
    man["end_to_end"] = [dict(m, workloads=list(CELLS)) if "workloads" in m
                         else m for m in REAL["end_to_end"]]
    man["per_layer"] = [dict(m, workloads=list(CELLS))
                        for m in REAL["per_layer"]]
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    return root
