"""moonlight-16b-a3b — the DeepSeek-V3 block at 16B: latent attention,
64 sigmoid-routed experts top-6 beside 2 shared ones, one leading dense
layer [https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json]."""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=1408,              # per-expert FFN width (moe_intermediate_size)
    vocab_size=163_840,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_theta=50_000.0,
    num_experts=64,
    experts_per_token=6,
    router_score="sigmoid",  # noaux_tc, n_group = topk_group = 1
    route_scale=2.446,
    shared_experts=2,
    aux_loss_weight=0.0,    # balanced by the correction bias, not a loss
    first_dense_layers=1,
    dense_ff=11_264,
    embed_scale=False,
    source="https://huggingface.co/moonshotai/Moonlight-16B-A3B "
           "(config.json, model_type deepseek_v3)",
)


def reduced() -> ModelConfig:
    """Every mechanism at CPU size: 1 dense + 1 MoE layer, 4 routed
    experts over 2 shares (this chip holds 2), top-2, one shared expert."""
    return CONFIG.replace(
        name="moonlight-smoke", num_layers=2, d_model=128, num_heads=4,
        num_kv_heads=4, d_ff=64, vocab_size=512, kv_lora_rank=32,
        qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
        num_experts=4, experts_per_token=2, expert_shards=2,
        shared_experts=1, dense_ff=256)
