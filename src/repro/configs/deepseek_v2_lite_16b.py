"""deepseek-v2-lite-16b [moe]: 27L d_model=2048 16H MLA (kv_lora=512, no
q_lora, nope=128, rope=64, v=128), 2 shared + 64 routed experts top-6 by
softmax, gates not renormalised (d_ff_expert=1408), first layer dense
(d_ff=10944), YaRN rope (factor 40 over 4096), vocab=102400
[arXiv:2405.04434; huggingface.co/deepseek-ai/DeepSeek-V2-Lite]."""
from repro.models import ModelConfig


def full() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-lite-16b", family="moe",
        n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
        use_mla=True, q_lora_rank=0, kv_lora_rank=512,
        qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128,
        moe=True, n_experts=64, top_k=6, n_shared_experts=2,
        d_ff_expert=1408, first_dense=1, d_ff=10944,
        topk_method="greedy", norm_topk_prob=False,
        routed_scaling_factor=1.0,
        # every routed expert held, dropless; one chip of an
        # expert-parallel deployment holds a share (experts_first/held)
        experts_held=64,
        vocab_size=102400, norm_eps=1e-6, rope_theta=10000.0,
        yarn_factor=40.0, yarn_original_max_pos=4096, yarn_beta_fast=32.0,
        yarn_beta_slow=1.0, yarn_mscale=0.707, yarn_mscale_all_dim=0.707,
        attn_chunk=1024, flash_threshold=2048, logit_chunk=512,
        param_dtype="bfloat16",
    )


def smoke() -> ModelConfig:
    return full().replace(
        n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, kv_lora_rank=16,
        qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16, n_experts=16,
        top_k=4, n_shared_experts=1, d_ff_expert=32, d_ff=128,
        experts_held=16, vocab_size=512, flash_threshold=4096,
        logit_chunk=0, dtype="float32", param_dtype="float32", remat=False)
