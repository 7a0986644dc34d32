"""Granite-4.0-H-Small — Mamba-2 / attention hybrid with a MoE FFN in every
layer [hf:ibm-granite/granite-4.0-h-small].  40 layers in periods of ten:
nine Mamba-2 mixers (128 heads of 64, d_state 128, one group, conv 4) and
one GQA attention layer (32 query and 8 KV heads of 128, no positional
encoding, softmax scale 1/128) at position 5.  Every layer's FFN is 72
routed experts of width 768, top-10 (softmax over the top-10 logits), plus
a shared SwiGLU expert of width 1,536.  A layer reads
h = x + 0.22 mixer(rms(x)); y = h + 0.22 (moe(rms(h)) + shared(rms(h))),
with embeddings x 12 and logits / 16; embeddings tied."""
from repro.configs.base import ATTN_MOE, MAMBA2_MOE, ModelConfig

PERIOD = (MAMBA2_MOE,) * 5 + (ATTN_MOE,) + (MAMBA2_MOE,) * 4

CONFIG = ModelConfig(
    name="granite_4_0_h_small", family="hybrid", n_layers=40, d_model=4096,
    n_heads=32, n_kv_heads=8, head_dim=128, d_ff=768, vocab_size=100352,
    n_experts=72, top_k=10, shared_expert_ff=1536, block_pattern=PERIOD,
    ssm_state=128, ssm_expand=2, ssm_conv=4, ssm_head_dim=64,
    nope=True, attn_scale=0.0078125, embedding_multiplier=12.0,
    residual_multiplier=0.22, logits_scaling=16.0, tie_embeddings=True,
    source="hf:ibm-granite/granite-4.0-h-small",
)

# two repeats of a two-layer period (a mixer, then attention): every kind of
# block, and the layer loop over repeats, at a tenth of the compile time
SMOKE = CONFIG.replace(n_layers=4, block_pattern=(MAMBA2_MOE, ATTN_MOE),
                       d_model=64, n_heads=4, n_kv_heads=2,
                       head_dim=16, d_ff=32, vocab_size=128, n_experts=8,
                       top_k=3, shared_expert_ff=48, ssm_state=16,
                       ssm_head_dim=16, attn_scale=0.0625)  # 1 / head_dim
