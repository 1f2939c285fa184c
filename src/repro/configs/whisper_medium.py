"""whisper-medium [audio] — encoder-decoder over 80-bin log-mels.

huggingface.co/openai/whisper-medium (config.json), arXiv:2212.04356:
24 encoder and 24 decoder layers, d_model 1024, 16 heads of 64, FFN 4096
with the exact GELU, vocabulary 51865 with the output head tied to the
token embedding, 448 learned decoder positions, LayerNorm eps 1e-5,
end-of-text 50257. The conv frontend (two k=3 conv1d, the second at
stride 2) runs the paper's sliding conv kernels; 3000 mel frames (30 s)
give 1500 encoder positions (``repro.models.whisper``).
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="whisper-medium",
    family="audio",
    num_layers=24,  # decoder depth
    encoder_layers=24,
    d_model=1024,
    num_heads=16,
    num_kv_heads=16,
    d_ff=4096,
    vocab_size=51_865,
    activation="gelu_plain",  # ungated MLP, exact GELU
    tie_embeddings=True,
    norm_eps=1e-5,
    cross_attention=True,
    frontend="audio",
    max_target_positions=448,
    eos_id=50_257,
)
