"""deepseek-v2-lite [moe]: 27L d_model=2048 16H with latent attention
(MLA: kv_lora_rank=512, no q_lora; per head qk_nope 128, qk_rope 64,
v 128); layer 0 dense (d_ff=10944), then 64 routed experts of width
1408, top-6 (softmax scoring, greedy top-k), plus 2 shared experts;
vocab=102400, context 163840 (YaRN, factor 40)
[hf:deepseek-ai/DeepSeek-V2-Lite config.json; layer equations: the
DeepSeek-V2 paper, arXiv 2405.04434, §2.1-2.2]."""
from typing import Any, Dict

from repro.configs.base import ArchConfig

SOURCE = ("https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/"
          "config.json")

# The keys of the published config.json that fix the model's shape.
PUBLISHED: Dict[str, Any] = {
    "first_k_dense_replace": 1,
    "hidden_act": "silu",
    "hidden_size": 2048,
    "intermediate_size": 10944,
    "kv_lora_rank": 512,
    "max_position_embeddings": 163840,
    "moe_intermediate_size": 1408,
    "n_routed_experts": 64,
    "n_shared_experts": 2,
    "num_attention_heads": 16,
    "num_experts_per_tok": 6,
    "num_hidden_layers": 27,
    "num_key_value_heads": 16,
    "q_lora_rank": None,
    "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06,
    "rope_theta": 10000,
    "tie_word_embeddings": False,
    "v_head_dim": 128,
    "vocab_size": 102400,
}


def from_hf_config(c: Dict[str, Any], name: str = "deepseek-v2-lite"
                   ) -> ArchConfig:
    """`ArchConfig` of a DeepSeek-V2 ``config.json`` (keys as published)."""
    return ArchConfig(
        name=name,
        family="moe",
        num_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"],
        d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"],
        rope_theta=float(c["rope_theta"]),
        num_experts=c["n_routed_experts"],
        top_k=c["num_experts_per_tok"],
        n_shared_experts=c["n_shared_experts"],
        moe_d_ff=c["moe_intermediate_size"],
        first_k_dense=c["first_k_dense_replace"],
        kv_lora_rank=c["kv_lora_rank"],
        q_lora_rank=c["q_lora_rank"] or 0,
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"],
        v_head_dim=c["v_head_dim"],
        act=c["hidden_act"],
        norm_eps=c["rms_norm_eps"],
        tie_embeddings=c["tie_word_embeddings"],
    )


CONFIG = from_hf_config(PUBLISHED)
