"""Granite-4.0-H-Micro 3B [huggingface.co/ibm-granite/granite-4.0-h-micro,
config.json; model type granitemoehybrid]: 40 layers, Mamba-2 mixers
everywhere but layers 5, 15, 25 and 35 (GQA attention, 32 query / 8 kv
heads of 64, no position embedding, softmax scale attention_multiplier
1/64), every layer with a SwiGLU MLP of 8192 (shared_intermediate_size; no
experts).  Mamba-2: expand 2 (d_inner 4096), 64 heads of 64, state 128, one
group, conv width 4 with a bias over x, B and C, chunk 256.  Embeddings
times 12, each sublayer's output times 0.22 before its residual add,
logits divided by 8; RMSNorm eps 1e-5, tied embeddings, vocab 100,352."""
from repro.configs.base import ATTN, MAMBA, ModelConfig, register

CONFIG = register(ModelConfig(
    name="granite-4.0-h-micro", family="hybrid",
    num_layers=40, d_model=2048, num_heads=32, num_kv_heads=8, head_dim=64,
    d_ff=8192, vocab_size=100352,
    layer_pattern=(MAMBA,) * 5 + (ATTN,) + (MAMBA,) * 4,
    use_rope=False, query_scale=0.015625,
    ssm_state=128, ssm_expand=2, ssm_head_dim=64, ssm_chunk=256,
    ssm_conv_width=4, ssm_conv_bias_bc=True,
    embedding_multiplier=12.0, residual_multiplier=0.22, logits_scaling=8.0,
    norm_eps=1e-5, tie_embeddings=True,
))
