"""Config system: architecture configs, shape suites, and the registry.

Every assigned architecture gets a ``src/repro/configs/<id>.py`` defining a
``CONFIG: ModelConfig``. ``repro.configs.get_config(name)`` loads it;
``repro.configs.registry()`` lists all. Shapes (the assignment's four input
suites) live in ``SHAPES`` with per-arch applicability in
``shape_applicable``.
"""
from __future__ import annotations

import dataclasses
import importlib
from dataclasses import dataclass, field
from typing import Any


@dataclass(frozen=True)
class ModelConfig:
    # identity
    name: str
    family: str  # dense | moe | ssm | hybrid | vlm | audio
    # transformer backbone
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int | None = None  # defaults to d_model // num_heads
    activation: str = "silu"  # "silu" (SwiGLU) | "gelu" (GeGLU)
    qk_norm: bool = False
    tie_embeddings: bool = False
    rope_theta: float = 1_000_000.0
    norm_eps: float = 1e-6
    # MoE
    num_experts: int = 0
    experts_per_token: int = 0
    moe_every: int = 1  # MoE FFN on every n-th layer (jamba: 2)
    capacity_factor: float = 1.25
    # hybrid (jamba): one attention layer per `attn_every` layers, rest Mamba
    attn_every: int = 0
    mamba_d_state: int = 16
    mamba_conv_k: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int | None = None  # defaults to ceil(d_model / 16)
    # rwkv6
    rwkv_head_dim: int = 64
    rwkv_wkv_mode: str = "scan"  # "scan" (faithful baseline) | "chunked" (MXU)
    rwkv_wkv_chunk: int = 32
    # multimodal / enc-dec
    frontend: str | None = None  # "vision_stub" | "audio"
    encoder_layers: int = 0  # whisper: encoder depth (num_layers = decoder)
    cross_attention: bool = False
    # learned decoder positions (whisper: 448); 0 = none, no such limit
    max_target_positions: int = 0
    num_patches: int = 1024  # vlm: patch positions inside the sequence
    # numerics & technique
    param_dtype: str = "bfloat16"
    compute_dtype: str = "bfloat16"
    conv_backend: str = "sliding"  # the paper's technique toggle
    # int8 PTQ of the conv path: "fp" | "w8a8" | "w8a16" (repro.quant);
    # quantized weights are swapped into params by quant.apply
    conv_precision: str = "fp"
    # serving KV-cache storage: "fp" (param_dtype) | "int8" (per-head-dim-row
    # absmax int8 + f32 scale leaves; dequantized at attention read —
    # DESIGN.md §8, `serve --kv-quant int8`)
    kv_quant: str = "fp"
    # decode-attention read: "fused" (flash-style kernel with the int8
    # dequant folded into the online softmax — no float K/V view,
    # DESIGN.md §9) | "view" (the PR-4 dequantize-whole-cache baseline,
    # kept for A/B benchmarks and token-equality tests)
    attn_decode: str = "fused"
    # tokenizer EOS id for serving slot recycling (per-arch; 1 is the
    # llama-family convention and the synthetic-data default)
    eos_id: int = 1
    remat: str = "block"  # "none" | "block"
    attn_chunk: int = 1024  # flash-style KV/Q chunking threshold & size
    loss_chunk: int = 512  # sequence chunking of the CE loss
    # optimizer-state compression for the giant configs (see repro.optim)
    opt_state_dtype: str = "float32"  # "float32" | "bfloat16" | "int8"
    # scan-over-layers for compile-time control at 512 devices
    scan_layers: bool = True
    # gradient-accumulation microbatches per step (scan-serialized; bounds
    # peak activation memory — see launch.steps.make_train_step)
    grad_accum: int = 1
    grad_accum_dtype: str = "float32"  # bf16 halves accumulator HBM (398B)

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_expand * self.d_model

    @property
    def resolved_dt_rank(self) -> int:
        return self.mamba_dt_rank or -(-self.d_model // 16)

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}

# Sub-quadratic (SSM / hybrid) families run long_500k; pure full-attention
# archs skip it (O(L^2) prefill / oversized dense KV) — see DESIGN.md
# §Arch-applicability.
LONG_CONTEXT_FAMILIES = ("ssm", "hybrid")

ARCH_IDS = [
    "gemma-2b",
    "llama3-8b",
    "granite-8b",
    "qwen3-1.7b",
    "qwen3-moe-30b-a3b",
    "phi3.5-moe-42b-a6.6b",
    "rwkv6-1.6b",
    "jamba-1.5-large-398b",
    "llava-next-34b",
    "whisper-medium",
]

_MODULE_FOR = {a: a.replace("-", "_").replace(".", "_") for a in ARCH_IDS}


def get_config(name: str) -> ModelConfig:
    if name not in _MODULE_FOR:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_MODULE_FOR)}")
    mod = importlib.import_module(f"repro.configs.{_MODULE_FOR[name]}")
    return mod.CONFIG


def registry() -> dict[str, ModelConfig]:
    return {a: get_config(a) for a in ARCH_IDS}


def shape_applicable(cfg: ModelConfig, shape: ShapeConfig) -> tuple[bool, str]:
    """(runs?, reason-if-skipped) for an (arch × shape) cell."""
    if shape.name == "long_500k" and cfg.family not in LONG_CONTEXT_FAMILIES:
        return False, "full-attention arch: 500k ctx needs sub-quadratic attention"
    if (cfg.max_target_positions and shape.kind != "decode"
            and shape.seq_len // 2 > cfg.max_target_positions):
        return False, (f"decoder takes at most {cfg.max_target_positions} "
                       "learned positions")
    return True, ""


def smoke_config(cfg: ModelConfig) -> ModelConfig:
    """Reduced same-family config for CPU smoke tests."""
    kw: dict[str, Any] = dict(
        num_layers=min(cfg.num_layers, 2 if cfg.attn_every == 0 else cfg.attn_every),
        d_model=128,
        num_heads=4,
        num_kv_heads=min(cfg.num_kv_heads, 2) if cfg.num_kv_heads > 1 else 1,
        d_ff=256,
        vocab_size=512,
        head_dim=32,
        param_dtype="float32",
        compute_dtype="float32",
        attn_chunk=64,
        loss_chunk=64,
        scan_layers=cfg.scan_layers,
        opt_state_dtype="float32",
    )
    if cfg.num_experts:
        kw.update(num_experts=4, experts_per_token=2)
    if cfg.attn_every:
        kw.update(attn_every=cfg.attn_every, num_layers=cfg.attn_every)
        kw.update(mamba_d_state=8)
    if cfg.family == "ssm":
        kw.update(rwkv_head_dim=32)
    if cfg.encoder_layers:
        kw.update(encoder_layers=2)
    if cfg.family == "vlm":
        kw.update(num_patches=16)
    return cfg.replace(**kw)
