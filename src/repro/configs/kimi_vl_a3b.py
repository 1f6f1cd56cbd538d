"""kimi-vl-a3b-instruct [vlm, moe] — the language model of Kimi-VL-A3B.

27L d_model=2048 16H, multi-head latent attention (kv_lora_rank 512, no
q-LoRA, qk nope/rope head dims 128/64, v 128), layer 0 a dense MLP of
11264, layers 1-26 MoE: 64 routed experts of 1408, top-6 by sigmoid
scores with the noaux_tc correction bias, renormalised and scaled by
2.446, plus 2 shared experts; vocab 163840, untied head, RMSNorm 1e-5,
RoPE theta 800000 with no scaling.
[huggingface.co/moonshotai/Kimi-VL-A3B-Instruct/blob/main/config.json]

The MoonViT tower is not modelled: an image enters as 2x2-merged 14x14
patches (the merge arXiv:2504.07491 describes for MoonViT) through one
linear projector to d_model.

``from_hf`` reads a config.json-style mapping, so that a benchmark
configuration stating the published keys gives this same ArchConfig.
"""
from __future__ import annotations

from typing import Mapping

from repro.configs.base import ArchConfig, register

SOURCE = "huggingface.co/moonshotai/Kimi-VL-A3B-Instruct/blob/main/config.json"

# the language model's config.json keys this architecture reads
PUBLISHED = {
    "vocab_size": 163840, "hidden_size": 2048, "intermediate_size": 11264,
    "moe_intermediate_size": 1408, "num_hidden_layers": 27,
    "num_attention_heads": 16, "num_key_value_heads": 16,
    "n_shared_experts": 2, "n_routed_experts": 64,
    "routed_scaling_factor": 2.446, "kv_lora_rank": 512, "q_lora_rank": None,
    "qk_rope_head_dim": 64, "v_head_dim": 128, "qk_nope_head_dim": 128,
    "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
    "num_experts_per_tok": 6, "first_k_dense_replace": 1,
    "norm_topk_prob": True, "scoring_func": "sigmoid",
    "rms_norm_eps": 1e-05, "rope_theta": 800000, "rope_scaling": None,
    "attention_bias": False, "tie_word_embeddings": False,
    "hidden_act": "silu", "moe_layer_freq": 1,
}


def from_hf(hf: Mapping, *, name: str = "kimi-vl-a3b-instruct",
            experts_held: int = 0, first_held_expert: int = 0,
            patch_size: int = 14, patch_merge: int = 2) -> ArchConfig:
    """The ArchConfig of a DeepSeek-V3-style config.json mapping.  Only
    what this repo implements is accepted: sigmoid noaux_tc routing over
    one group, no q-LoRA, no RoPE scaling, SiLU, no attention bias, an
    MoE layer after every dense one."""
    unsupported = {
        "scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1,
        "topk_group": 1, "q_lora_rank": None, "rope_scaling": None,
        "hidden_act": "silu", "attention_bias": False, "moe_layer_freq": 1}
    for key, want in unsupported.items():
        if hf.get(key, want) != want:
            raise ValueError(f"{key}={hf[key]!r}: only {want!r} is "
                             f"implemented")
    if hf["num_key_value_heads"] != hf["num_attention_heads"]:
        raise ValueError("MLA has one latent for every head")
    return ArchConfig(
        name=name, family="vlm", source=SOURCE,
        num_layers=hf["num_hidden_layers"], d_model=hf["hidden_size"],
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"],
        d_ff=hf["intermediate_size"], vocab_size=hf["vocab_size"],
        rope_theta=float(hf["rope_theta"]), norm_eps=hf["rms_norm_eps"],
        tie_embeddings=hf["tie_word_embeddings"],
        num_experts=hf["n_routed_experts"],
        num_experts_per_tok=hf["num_experts_per_tok"],
        first_k_dense_replace=hf["first_k_dense_replace"],
        moe_d_ff=hf["moe_intermediate_size"],
        num_shared_experts=hf["n_shared_experts"],
        norm_topk_prob=hf["norm_topk_prob"],
        routed_scaling_factor=hf["routed_scaling_factor"],
        experts_held=experts_held, first_held_expert=first_held_expert,
        kv_lora_rank=hf["kv_lora_rank"],
        qk_nope_head_dim=hf["qk_nope_head_dim"],
        qk_rope_head_dim=hf["qk_rope_head_dim"],
        v_head_dim=hf["v_head_dim"],
        frontend="patch_merge", patch_size=patch_size,
        patch_merge=patch_merge, vocab_pad_multiple=1, attention="full")


FULL = from_hf(PUBLISHED)

# every mechanism at a CPU-test size: two dense layers' worth of pattern
# (one dense, two MoE), 8 experts of which top-3, 2 shared
REDUCED_HF = {**PUBLISHED, "vocab_size": 512, "hidden_size": 64,
              "intermediate_size": 96, "moe_intermediate_size": 32,
              "num_hidden_layers": 3, "num_attention_heads": 4,
              "num_key_value_heads": 4, "n_routed_experts": 8,
              "num_experts_per_tok": 3, "kv_lora_rank": 32,
              "qk_rope_head_dim": 8, "qk_nope_head_dim": 16,
              "v_head_dim": 16}
REDUCED = from_hf(REDUCED_HF, name="kimi-vl-a3b-instruct-reduced",
                  patch_size=4)

register(FULL, REDUCED)
