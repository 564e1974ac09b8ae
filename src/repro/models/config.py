"""Model configuration covering all assigned architecture families:
dense GQA transformers, local:global interleave, MLA, MoE (uniform and
interleaved, with shared experts), Mamba-1, Mamba-2/SSD hybrids, and
embedding-input (audio/vlm backbone) variants.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str = "model"
    family: str = "dense"          # dense | moe | ssm | hybrid | audio | vlm

    # trunk dims
    n_layers: int = 4
    d_model: int = 256
    n_heads: int = 4
    n_kv_heads: int = 4
    head_dim: int = 0              # 0 -> d_model // n_heads
    d_ff: int = 1024
    vocab_size: int = 1024

    # inputs: "tokens" (LM) or "embeddings" (stub modality frontend)
    input_mode: str = "tokens"

    # attention
    attn_pattern: Tuple[str, ...] = ("global",)   # cycled over layers
    local_window: int = 1024
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    # YaRN rope scaling (arXiv:2309.00071, as DeepSeek-V2 publishes it);
    # yarn_factor 0 means plain rope
    yarn_factor: float = 0.0
    yarn_original_max_pos: int = 4096
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0

    # MLA (DeepSeek-V2)
    use_mla: bool = False
    q_lora_rank: int = 0
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128

    # MoE
    moe: bool = False
    n_experts: int = 0
    top_k: int = 1
    n_shared_experts: int = 0
    d_ff_expert: int = 0
    moe_period: int = 1            # MoE FFN every `period` layers ...
    moe_offset: int = 0            # ... at layer indices i % period == offset
    first_dense: int = 0           # first K layers use dense FFN regardless
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.001
    # routing: "greedy" top-k over softmax scores is the one implemented;
    # the top-k gates are renormalised to sum 1 only under norm_topk_prob
    topk_method: str = "greedy"
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    # the held-experts share (expert parallelism): this chip holds the
    # routed experts [experts_first, experts_first + experts_held) and
    # computes their part of the layer, dropless; 0 = the capacity-
    # dispatched layer over all n_experts
    experts_first: int = 0
    experts_held: int = 0

    # SSM
    ssm_kind: Optional[str] = None  # None | mamba1 | mamba2
    d_state: int = 16
    expand: int = 2
    conv_kernel: int = 4
    dt_rank: int = 0               # mamba1; 0 -> ceil(d_model/16)
    ssd_head_dim: int = 64         # mamba2
    ssd_chunk: int = 128
    # hybrid: apply a weight-shared attention block every `period` layers
    hybrid_attn_period: int = 0

    # numerics / compute
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    norm_eps: float = 1e-6
    attn_chunk: int = 1024          # kv-chunk for the flash path
    attn_schedule: str = "dense"    # dense (bounding-box) | triangular (compact)
    # GridPlan lowering knob (repro.core.plan): "closed_form" |
    # "prefetch_lut" | "bounding" | "" (= derive from attn_schedule).
    # When set it wins over attn_schedule for the XLA flash path; call
    # sites that invoke the Pallas kernels directly read it as
    # grid_mode via the accessor below.
    grid_lowering: str = ""
    # decode attention path: "xla" (full masked decode_attention) or
    # "blockspace" (the Pallas flash kernel with the run-time seq_pos
    # block skip; shards continuous-batching slot groups over the
    # registered serving mesh)
    attn_decode_kernel: str = "xla"
    flash_threshold: int = 8192     # use flash custom-vjp above this seq len
    remat: bool = True
    logit_chunk: int = 0            # 0 = unchunked cross-entropy
    # force the Megatron TP/SP collective pattern (activation gathers,
    # never weight gathers) via explicit intermediate constraints
    megatron_sp: bool = False

    # ------------------------------------------------------------------
    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def padded_vocab(self) -> int:
        """Embedding/head tables padded to a multiple of 16 so the vocab
        dim shards evenly over the model axis (Megatron practice).
        Logical vocab_size is unchanged (labels/tokens < vocab_size)."""
        return -(-self.vocab_size // 16) * 16

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def dt_rank_(self) -> int:
        return self.dt_rank or -(-self.d_model // 16)

    @property
    def ssd_heads(self) -> int:
        return self.d_inner // self.ssd_head_dim

    @property
    def attn_schedule_resolved(self) -> str:
        """The XLA flash schedule, honoring grid_lowering when set."""
        if self.grid_lowering:
            from repro.core.plan import xla_schedule
            return xla_schedule(self.grid_lowering)
        return self.attn_schedule

    @property
    def grid_mode(self) -> str:
        """grid_mode for call sites that invoke repro.kernels.ops
        directly (the model stack itself routes through the XLA path
        via attn_schedule_resolved)."""
        return self.grid_lowering or "closed_form"

    @property
    def yarn(self):
        """The YaRN parameters as a hashable tuple (factor, original max
        positions, beta_fast, beta_slow, mscale, mscale_all_dim), or
        None for plain rope."""
        if not self.yarn_factor:
            return None
        return (float(self.yarn_factor), int(self.yarn_original_max_pos),
                float(self.yarn_beta_fast), float(self.yarn_beta_slow),
                float(self.yarn_mscale), float(self.yarn_mscale_all_dim))

    @property
    def mla_softmax_scale(self) -> float:
        """(qk_nope + qk_rope)^-1/2, times mscale(factor,
        mscale_all_dim)^2 under YaRN (DeepSeek-V2's attention scale)."""
        from .layers import yarn_get_mscale
        scale = (self.qk_nope_dim + self.qk_rope_dim) ** -0.5
        if self.yarn and self.yarn_mscale_all_dim:
            m = yarn_get_mscale(self.yarn_factor, self.yarn_mscale_all_dim)
            scale *= m * m
        return float(scale)

    @property
    def latent_width(self) -> int:
        """One token's row in an MLA layer's paged pool: the compressed
        kv latent and the shared roped key."""
        return self.kv_lora_rank + self.qk_rope_dim

    @property
    def n_experts_local(self) -> int:
        """Routed experts whose weights this chip holds."""
        return self.experts_held or self.n_experts

    def attn_kind(self, layer: int) -> str:
        return self.attn_pattern[layer % len(self.attn_pattern)]

    def layer_mixer(self, layer: int) -> str:
        if self.ssm_kind is not None:
            return self.ssm_kind
        return "mla" if self.use_mla else "attn"

    def layer_ffn(self, layer: int) -> str:
        if not self.moe or layer < self.first_dense:
            return "dense"
        return "moe" if layer % self.moe_period == self.moe_offset else "dense"

    def has_shared_attn(self, layer: int) -> bool:
        p = self.hybrid_attn_period
        return bool(p) and layer % p == p - 1

    def jdtype(self):
        return jnp.dtype(self.dtype)

    def jparam_dtype(self):
        return jnp.dtype(self.param_dtype)

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    # number of parameters (analytic; used for MODEL_FLOPS roofline term)
    def param_count(self) -> int:
        d, v = self.d_model, self.vocab_size
        total = v * d  # embedding
        if self.input_mode != "embeddings":
            pass  # tied output head (we keep separate head below)
        total += v * d  # lm head
        for i in range(self.n_layers):
            total += 2 * d  # norms
            mixer = self.layer_mixer(i)
            if mixer == "attn":
                hq = self.n_heads * self.hd
                hkv = self.n_kv_heads * self.hd
                total += d * hq + 2 * d * hkv + hq * d
                if self.qkv_bias:
                    total += hq + 2 * hkv
            elif mixer == "mla":
                ql = self.q_lora_rank or d
                qdim = self.n_heads * (self.qk_nope_dim + self.qk_rope_dim)
                total += (d * ql if self.q_lora_rank else 0) + ql * qdim
                total += d * (self.kv_lora_rank + self.qk_rope_dim)
                total += self.kv_lora_rank * self.n_heads * (
                    self.qk_nope_dim + self.v_head_dim)
                total += self.n_heads * self.v_head_dim * d
            elif mixer == "mamba1":
                di, n, dtr = self.d_inner, self.d_state, self.dt_rank_
                total += d * 2 * di + di * self.conv_kernel
                total += di * (dtr + 2 * n) + dtr * di + di * n + 2 * di
                total += di * d
            elif mixer == "mamba2":
                di, n, nh = self.d_inner, self.d_state, self.ssd_heads
                total += d * (2 * di + 2 * n + nh)  # in_proj(x,z,B,C,dt)
                total += (di + 2 * n) * self.conv_kernel
                total += 2 * nh + di  # A, D, dt_bias... (approx)
                total += di * d
            ffn = self.layer_ffn(i)
            if self.family == "hybrid":
                ffn = "none"  # zamba-style: MLP lives in the shared block
            if ffn == "none":
                pass
            elif ffn == "dense":
                total += 3 * d * self.d_ff
            else:
                fe = self.d_ff_expert or self.d_ff
                total += d * self.n_experts  # router
                total += self.n_experts_local * 3 * d * fe
                total += self.n_shared_experts * 3 * d * fe
        if self.hybrid_attn_period:
            hq = self.n_heads * self.hd
            hkv = self.n_kv_heads * self.hd
            total += 2 * d * d  # concat in-proj
            total += d * hq + 2 * d * hkv + hq * d + 3 * d * self.d_ff
        return total

    def active_param_count(self) -> int:
        """Params touched per token (MoE: top_k + shared experts only)."""
        if not self.moe:
            return self.param_count()
        dense_cfg = self.param_count()
        fe = self.d_ff_expert or self.d_ff
        n_moe_layers = sum(1 for i in range(self.n_layers)
                           if self.layer_ffn(i) == "moe")
        inactive = (self.n_experts - self.top_k) * 3 * self.d_model * fe
        return dense_cfg - n_moe_layers * inactive
