"""deepseek-moe-16b [arXiv:2401.06066; hf:deepseek-ai/deepseek-moe-16b-base]:
28L, d2048, 16H MHA (head_dim 128), vocab 102400, untied embeddings.
Layer 0 is dense (SwiGLU 10944, first_k_dense_replace=1); layers 1-27
are fine-grained MoE: 64 routed experts of ff 1408, top-6 softmax gates
NOT renormalised (norm_topk_prob=false), plus 2 shared experts.
rms_norm_eps 1e-6, rope_theta 10000.  64 experts shard cleanly over the
16-way model axis (EP, 4 per group)."""
from repro.configs.base import MoEConfig

CONFIG = MoEConfig(
    name="deepseek-moe-16b", family="moe",
    num_layers=28, d_model=2048, num_heads=16, num_kv_heads=16,
    head_dim=128, d_ff=10944, vocab_size=102400,
    num_experts=64, top_k=6, num_shared_experts=2, moe_d_ff=1408,
    first_k_dense=1, norm_topk=False, norm_eps=1e-6, rope_theta=10_000.0,
    expert_sharding="ep",
)
