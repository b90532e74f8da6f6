"""Phi-1.5 [hf:microsoft/phi-1_5, config.json, model_type "phi"].

Attention and MLP in parallel from one LayerNorm with a bias; biases on
q, k, v, the dense output, fc1, fc2 and the LM head; a final LayerNorm;
tanh GELU ("gelu_new"); rotary on half of each 64-wide head; MHA, untied
embeddings. 1,418,270,720 parameters."""
from repro.models.config import ModelConfig


def config() -> ModelConfig:
    return ModelConfig(
        name="phi-1.5b", family="dense",
        n_layers=24, d_model=2048, n_heads=32, n_kv_heads=32,
        d_ff=8192, vocab=51200,
        rotary_pct=0.5, rope_theta=10000.0, act="gelu", gated_mlp=False,
        qkv_bias=True, parallel_block=True, norm="layer", linear_bias=True,
        norm_eps=1e-5,
    )
