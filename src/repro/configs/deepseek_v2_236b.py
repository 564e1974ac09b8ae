"""deepseek-v2-236b [moe]: 60L d_model=5120 128H MLA (kv_lora=512,
q_lora=1536, nope=128, rope=64, v=128), 2 shared + 160 routed experts
top-6 (d_ff_expert=1536) by group-limited greedy routing (8 groups, top
3), gates not renormalised, scaled by 16; first layer dense
(d_ff=12288), YaRN rope (factor 40 over 4096), vocab=102400
[arXiv:2405.04434].  The MoE layer implements greedy routing only, so
the published config refuses to route; ``smoke()`` routes greedily."""
from repro.models import ModelConfig


def full() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-236b", family="moe",
        n_layers=60, d_model=5120, n_heads=128,
        use_mla=True, q_lora_rank=1536, kv_lora_rank=512,
        qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128,
        moe=True, n_experts=160, top_k=6, n_shared_experts=2,
        d_ff_expert=1536, first_dense=1, d_ff=12288,
        capacity_factor=1.25, vocab_size=102400,
        topk_method="group_limited_greedy", norm_topk_prob=False,
        routed_scaling_factor=16.0, norm_eps=1e-6, rope_theta=10000.0,
        yarn_factor=40.0, yarn_original_max_pos=4096, yarn_beta_fast=32.0,
        yarn_beta_slow=1.0, yarn_mscale=0.707, yarn_mscale_all_dim=0.707,
        attn_chunk=1024, flash_threshold=2048, logit_chunk=512,
        # 236B on 256 v5e chips: bf16 params + bf16 moments is what fits
        # (production would add a data-sharded f32 master copy; see
        # DESIGN.md SS6); FSDP over 'data' shards the expert weights.
        param_dtype="bfloat16",
    )


def smoke() -> ModelConfig:
    return full().replace(
        n_layers=3, d_model=64, n_heads=4, q_lora_rank=32, kv_lora_rank=16,
        qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16, n_experts=8, top_k=2,
        n_shared_experts=1, d_ff_expert=32, d_ff=128, vocab_size=512,
        capacity_factor=2.0, flash_threshold=4096, logit_chunk=0,
        topk_method="greedy",
        dtype="float32", param_dtype="float32", remat=False)
