"""A benchmark tree with a tiny whisper cell, for the CPU tests.

``make(tmp)`` copies the benchmark directory under ``tmp`` and adds, by
new files only, a tiny configuration of whisper-medium's architecture
(its reference module is the real one, copied), a tiny audio mix and a
cell, with a manifest naming them. The program serves it in float32 over
an int8 K/V cache, so the cache is the only precision step below the
float32 reference. The cell reports the real whisper cell's metrics.
"""
from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
REAL = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
CONFIG = "tiny-whisper"
CELL = "tiny-whisper.tiny-audio"
REAL_CELL = "whisper-medium.transcribe-30s"

WHISPER = {
    "source": "tiny test size of whisper-medium",
    "program": {"arch": "whisper-medium",
                "overrides": {"kv_quant": "int8", "attn_decode": "fused",
                              "conv_backend": "sliding_pallas",
                              "eos_id": 250}},
    "seeded_weights": {"qk_gain": 2.0},
    "d_model": 64, "decoder_attention_heads": 4, "decoder_ffn_dim": 128,
    "decoder_layers": 2, "encoder_attention_heads": 4,
    "encoder_ffn_dim": 128, "encoder_layers": 2, "layer_norm_eps": 1e-5,
    "max_source_positions": 16, "max_target_positions": 32,
    "num_mel_bins": 80, "tie_word_embeddings": True, "vocab_size": 256,
    "assumed": {"serving_dtype": "float32"},
}
MIX = {"loop": "closed", "clients": 2, "prompt_tokens": 4,
       "output_tokens": 8, "sampling": "greedy"}


def make(tmp: Path, limit: float = 0.2) -> Path:
    """A checkout-like root under ``tmp`` holding the tiny whisper cell."""
    root = Path(tmp) / "checkout"
    shutil.copytree(BENCH, root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    base = root / "perfbench"
    (base / "configs" / f"{CONFIG}.json").write_text(json.dumps(WHISPER))
    shutil.copy(base / "configs" / "whisper-medium.py",
                base / "configs" / f"{CONFIG}.py")
    (base / "traffic" / "tiny-audio.json").write_text(json.dumps(MIX))
    (base / "cells" / f"{CELL}.json").write_text(
        json.dumps({"max_logit_gap": limit}))
    man = dict(REAL)
    man["configs"] = [{"name": CONFIG, "source": "tiny test size",
                       "file": f"perfbench/configs/{CONFIG}.json",
                       "reduced": [], "why": "CPU test"}]
    man["workloads"] = [{"name": CELL, "config": CONFIG,
                         "traffic": "tiny-audio", "chips": 1,
                         "why": "CPU test"}]
    # the metrics the real whisper cell reports, reported here
    man["end_to_end"] = [m for m in REAL["end_to_end"]
                         if "workloads" not in m]
    man["per_layer"] = [dict(m, workloads=[CELL]) for m in REAL["per_layer"]
                        if REAL_CELL in m.get("workloads", [REAL_CELL])]
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    return root
