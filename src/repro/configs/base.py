"""Model configuration dataclass shared by every architecture.

Each assigned architecture gets one module in this package defining
``CONFIG`` (the exact assignment) plus ``reduced()`` (a tiny same-family
variant for CPU smoke tests).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                 # dense | moe | ssm | hybrid | audio | vlm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0           # 0 -> d_model // num_heads
    qkv_bias: bool = False
    tie_embeddings: bool = False
    # --- MoE ---
    num_experts: int = 0        # routed experts the router scores
    experts_per_token: int = 0
    moe_every: int = 1          # FFN is MoE on layers where idx % moe_every == moe_every-1
    capacity_factor: float = 1.25
    moe_combine: str = "gather"   # "gather" | "scatter" (§Perf lever)
    router_score: str = "softmax"  # "softmax" | "sigmoid": sigmoid scores,
                                   # top-k by score + a correction bias
    route_scale: float = 1.0    # the chosen experts' weights sum to this
    shared_experts: int = 0     # experts of width d_ff every token takes
    expert_shards: int = 1      # chips that share each layer's experts
    # the held experts' correction bias is offset on each batch to give
    # them their even share of the choices (models.moe.even_share_offset),
    # where a trained model's bias holds the loads near even
    even_share: bool = False
    aux_loss_weight: float = 0.01  # weight of the MoE load-balance loss
    # leading layers before the scanned periods: attention + dense FFN
    first_dense_layers: int = 0
    dense_ff: int = 0           # their FFN width
    # --- SSM (Mamba2/SSD) ---
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_conv: int = 4
    ssm_ngroups: int = 1
    ssm_chunk: int = 128
    # --- layer pattern: one char per layer in a repeating period.
    # 'A' = attention mixer, 'M' = mamba mixer. "" means all-'A' (or all-'M'
    # for family == "ssm").
    layer_pattern: str = ""
    # --- attention variant ---
    sliding_window: int = 0     # 0 = full causal attention
    # latent attention (MLA) where kv_lora_rank > 0: k_nope and v come
    # from a normed latent of kv_lora_rank, rotary on qk_rope_head_dim
    # dims with one rotary key shared by all heads
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_theta: float = 1e4
    attn_chunk: int = 1024      # query-chunk size of the flash-style scan
    attn_impl: str = "auto"     # "auto": the splash flash kernel (forward
                                # and backward) on a TPU where it applies,
                                # else "jnp" (kernels.ops.resolve_attn_impl);
                                # "pallas" | "jnp" force one path
    # --- modality frontend stub (audio/vlm): number of precomputed
    # frame/patch embeddings prepended to the token sequence.
    frontend: str = "none"      # none | audio | vision
    frontend_tokens: int = 0
    # --- numerics ---
    dtype: str = "bfloat16"
    norm_eps: float = 1e-5
    embed_scale: bool = True    # scale the embedding by sqrt(d_model)
    # provenance
    source: str = ""

    @property
    def mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def qk_head_dim(self) -> int:
        """q.k width: nope + rope dims under MLA, else the head dim."""
        if self.mla:
            return self.qk_nope_head_dim + self.qk_rope_head_dim
        return self.resolved_head_dim

    @property
    def held_experts(self) -> int:
        """Routed experts this chip computes."""
        return self.num_experts // self.expert_shards

    @property
    def dropless(self) -> bool:
        """MoE layers run the dropless layer (``models.moe.dropless_fwd``):
        the sigmoid-routed DeepSeek-V3 block, and any share of the experts
        (the capacity path computes them all). Softmax-routed configs
        keep the capacity path (``moe_fwd``)."""
        return self.router_score == "sigmoid" or self.expert_shards > 1

    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim:
            return self.head_dim
        return self.d_model // self.num_heads if self.num_heads else 0

    @property
    def pattern(self) -> str:
        if self.layer_pattern:
            return self.layer_pattern
        return "M" if self.family == "ssm" else "A"

    @property
    def num_periods(self) -> int:
        """Scanned periods, after the leading dense layers."""
        p, n = self.pattern, self.num_layers - self.first_dense_layers
        assert n % len(p) == 0, (self.name, self.num_layers, p)
        return n // len(p)

    @property
    def d_inner(self) -> int:
        """Mamba inner width."""
        return self.ssm_expand * self.d_model

    @property
    def ssm_nheads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    # ---- parameter counting (used for roofline MODEL_FLOPS = 6*N*D) ----
    def attention_params(self) -> int:
        """One attention mixer's parameters."""
        D, H, KV = self.d_model, self.num_heads, self.num_kv_heads
        if self.mla:
            r, rope = self.kv_lora_rank, self.qk_rope_head_dim
            return (D * H * self.qk_head_dim              # q
                    + D * (r + rope) + r                   # latent + its norm
                    + r * H * (self.qk_nope_head_dim + self.v_head_dim)
                    + H * self.v_head_dim * D)             # o
        hd = self.resolved_head_dim
        n = D * H * hd + 2 * D * KV * hd + H * hd * D      # q, k, v, o
        if self.qkv_bias:
            n += (H + 2 * KV) * hd
        return n

    def param_count(self, active_only: bool = False) -> int:
        """Parameters this configuration holds (its share of the routed
        experts); ``active_only``: with the experts one token takes."""
        D, F, V = self.d_model, self.d_ff, self.vocab_size
        n = V * D  # embedding
        if not self.tie_embeddings:
            n += V * D  # lm head
        k = self.first_dense_layers
        n += k * (2 * D + self.attention_params() + 3 * D * self.dense_ff)
        per = self.pattern
        for ch in list(per) * self.num_periods:
            n += 2 * D  # norms
            if ch == "A":
                n += self.attention_params()
            else:  # mamba mixer
                di, ds, nh = self.d_inner, self.ssm_state, self.ssm_nheads
                n += D * (2 * di + 2 * self.ssm_ngroups * ds + nh)  # in_proj
                n += di * self.ssm_conv + di                        # conv + norm-ish
                n += 2 * nh                                         # A_log, dt_bias
                n += di * D                                         # out_proj
        # FFNs (attention/mamba mixers both may carry an FFN when d_ff > 0)
        if F > 0:
            layers_with_ffn = self.num_layers - k
            moe_layers = 0
            if self.num_experts > 0:
                moe_layers = sum(
                    1 for i in range(layers_with_ffn)
                    if i % self.moe_every == self.moe_every - 1)
            dense_layers = layers_with_ffn - moe_layers
            n += dense_layers * 3 * D * F
            if self.num_experts > 0:
                e = self.experts_per_token if active_only else self.held_experts
                e += self.shared_experts
                router = D * self.num_experts
                if self.router_score == "sigmoid":
                    router += self.num_experts       # correction bias
                n += moe_layers * (e * 3 * D * F + router)
        return n
