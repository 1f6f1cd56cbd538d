"""Architecture configuration.

One ``ArchConfig`` per assigned architecture lives in
``repro/configs/<id>.py`` with the *exact* published dimensions; each also
provides ``reduced()`` — a same-family shrunken config for CPU smoke tests.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Mapping, Optional, Sequence


def pad_to(x: int, multiple: int) -> int:
    return int(math.ceil(x / multiple) * multiple)


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    # identity
    name: str
    family: str  # dense | moe | ssm | hybrid | vlm | audio
    source: str = ""

    # trunk dims
    num_layers: int = 0
    d_model: int = 0
    num_heads: int = 0
    num_kv_heads: int = 0
    head_dim: int = 0          # 0 -> d_model // num_heads
    d_ff: int = 0
    vocab_size: int = 0

    # attention options
    qk_norm: bool = False
    qkv_bias: bool = False
    rope_theta: float = 10_000.0
    pos_scheme: str = "rope"  # rope | sinusoidal | none
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    # depth-scaled residual (MiniCPM "scale_depth"); 0 disables
    scale_depth: float = 0.0
    # mup-style embedding/logit scaling (MiniCPM); 1.0 disables
    scale_emb: float = 1.0
    dim_model_base: int = 0  # for MiniCPM logit scaling; 0 disables

    # MoE
    num_experts: int = 0
    num_experts_per_tok: int = 0
    moe_capacity_factor: float = 1.25
    router_aux_loss_coef: float = 0.001

    # DeepSeek-V3-style MoE under MLA (Kimi-VL): the first
    # ``first_k_dense_replace`` layers are dense MLPs of ``d_ff``; the rest
    # are MoE layers of ``num_experts`` routed experts of width ``moe_d_ff``
    # plus ``num_shared_experts`` shared ones, merged into one MLP of width
    # ``num_shared_experts * moe_d_ff`` that every token runs.  Experts are
    # scored by sigmoid, selected by score + a correction bias, and weighed
    # by the unbiased scores, renormalised over the top k
    # (``norm_topk_prob``) and times ``routed_scaling_factor``; no token is
    # dropped (repro.models.moe.apply_moe_share).
    first_k_dense_replace: int = 0
    moe_d_ff: int = 0
    num_shared_experts: int = 0
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    # expert parallelism: this chip holds experts [first_held_expert,
    # first_held_expert + experts_held) of every MoE layer (0: all of them);
    # it routes over all num_experts and computes only its own experts' part
    experts_held: int = 0
    first_held_expert: int = 0

    # multi-head latent attention (MLA, DeepSeek-V2, without q-LoRA);
    # kv_lora_rank > 0 turns it on
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    # SSM (Mamba2)
    ssm_state: int = 0
    mamba_expand: int = 2
    mamba_head_dim: int = 64
    mamba_conv_width: int = 4
    mamba_ngroups: int = 1

    # RWKV6
    rwkv_head_dim: int = 64
    rwkv_decay_lora: int = 64
    rwkv_mix_lora: int = 32

    # hybrid (zamba2): shared attention block applied every N trunk layers,
    # cycling over `num_shared_blocks` weight-tied blocks.
    shared_attn_every: int = 0
    num_shared_blocks: int = 2

    # encoder-decoder (whisper)
    is_encoder_decoder: bool = False
    num_encoder_layers: int = 0
    encoder_seq_len: int = 1_500  # whisper: 30s audio -> 1500 frames

    # modality frontend stubs
    frontend: str = "none"  # none | vit_stub | audio_stub | patch_merge
    num_patches: int = 0    # vlm: precomputed patch embeddings per image
    # patch_merge: the RGB image cut into patch_size^2 patches, merge x
    # merge neighbours concatenated and projected to d_model by one linear
    # layer
    patch_size: int = 0
    patch_merge: int = 0

    # attention flavour for long-context applicability
    attention: str = "full"  # full | none (ssm) | hybrid

    # per-arch logical-rule overrides (see distributed/sharding.py)
    sharding_overrides: Optional[Mapping[str, Any]] = None
    # overrides applied only to train cells (e.g. FSDP/ZeRO-3: shard the
    # weights' "embed" dim over the data axis so params+AdamW moments fit)
    train_sharding_overrides: Optional[Mapping[str, Any]] = None
    # overrides applied only to prefill cells (big-token-batch regime:
    # MoE archs reuse the train EP layout here, not at decode)
    prefill_sharding_overrides: Optional[Mapping[str, Any]] = None

    # vocab padding multiple for TP-divisible embedding shards
    vocab_pad_multiple: int = 512

    # serving KV/state-cache dtype; f8 halves cache bytes (hillclimbed —
    # required for qwen1.5-32b decode_32k feasibility, see EXPERIMENTS.md)
    serve_cache_dtype: str = "bfloat16"

    # ---- derived -----------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or (self.d_model // max(self.num_heads, 1))

    @property
    def padded_vocab(self) -> int:
        return pad_to(self.vocab_size, self.vocab_pad_multiple)

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def held_experts(self) -> tuple[int, ...]:
        """The routed experts this chip holds (all where experts_held 0)."""
        n = self.experts_held or self.num_experts
        return tuple(range(self.first_held_expert, self.first_held_expert + n))

    @property
    def patch_features(self) -> int:
        return self.patch_merge ** 2 * self.patch_size ** 2 * 3

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_expand * self.d_model

    @property
    def mamba_nheads(self) -> int:
        return self.mamba_d_inner // self.mamba_head_dim

    @property
    def rwkv_nheads(self) -> int:
        return self.d_model // self.rwkv_head_dim

    def param_count(self) -> int:
        """Analytic parameter count (embedding included, unpadded vocab)."""
        d, hd = self.d_model, self.resolved_head_dim
        qdim = self.num_heads * hd
        kvdim = self.num_kv_heads * hd
        attn = d * qdim + 2 * d * kvdim + qdim * d  # q,k,v,o
        if self.qkv_bias:
            attn += qdim + 2 * kvdim
        mlp = 3 * d * self.d_ff  # gate/up/down (SwiGLU)
        per_layer = 0
        n_attn_layers = self.num_layers
        if self.family in ("ssm",):
            if self.name.startswith("rwkv"):
                # time-mix: r,k,v,g,o ~ 5 d^2 + decay lora; channel-mix ~ 2*d*ff
                per_layer = 5 * d * d + 2 * d * self.d_ff
            else:
                di = self.mamba_d_inner
                per_layer = d * (2 * di + 2 * self.mamba_ngroups * self.ssm_state + self.mamba_nheads) + di * d
            n_attn_layers = 0
            total = self.num_layers * per_layer
        elif self.family == "hybrid":
            di = self.mamba_d_inner
            mamba_l = d * (2 * di + 2 * self.mamba_ngroups * self.ssm_state + self.mamba_nheads) + di * d
            total = self.num_layers * mamba_l
            # shared blocks (weight-tied): count once each
            total += self.num_shared_blocks * (attn + mlp)
            n_attn_layers = 0
        elif self.is_mla:
            return self._mla_param_count()
        elif self.is_moe:
            expert = 3 * d * self.d_ff
            router = d * self.num_experts
            total = self.num_layers * (attn + self.num_experts * expert + router)
        else:
            total = self.num_layers * (attn + mlp)
        if self.is_encoder_decoder:
            # encoder self-attn+mlp, decoder gets extra cross-attn
            total += self.num_encoder_layers * (attn + mlp)
            total += self.num_layers * attn  # cross-attention
        total += self.vocab_size * d * (1 if self.tie_embeddings else 2)
        return int(total)

    def _mla_param_count(self) -> int:
        """MLA + DeepSeek-style MoE: every layer's attention and norms,
        the dense layers' MLPs, and in each MoE layer the router, its
        correction bias, the experts held here and the shared experts;
        embedding and head (the image projector is not the LM's)."""
        d, h, r = self.d_model, self.num_heads, self.kv_lora_rank
        qk = self.qk_nope_head_dim + self.qk_rope_head_dim
        attn = (d * h * qk + d * (r + self.qk_rope_head_dim) + r
                + r * h * (self.qk_nope_head_dim + self.v_head_dim)
                + h * self.v_head_dim * d + 2 * d)
        dense = 3 * d * self.d_ff
        moe = (d * self.num_experts + self.num_experts
               + len(self.held_experts) * 3 * d * self.moe_d_ff
               + 3 * d * self.num_shared_experts * self.moe_d_ff)
        k = self.first_k_dense_replace
        total = self.num_layers * attn + k * dense + (self.num_layers - k) * moe
        total += self.vocab_size * d * (1 if self.tie_embeddings else 2) + d
        return int(total)

    def active_param_count(self) -> int:
        if not self.is_moe:
            return self.param_count()
        d = self.d_model
        hd = self.resolved_head_dim
        attn = d * self.num_heads * hd + 2 * d * self.num_kv_heads * hd + self.num_heads * hd * d
        expert = 3 * d * self.d_ff
        router = d * self.num_experts
        total = self.num_layers * (attn + self.num_experts_per_tok * expert + router)
        total += self.vocab_size * d * (1 if self.tie_embeddings else 2)
        return int(total)

    def replace(self, **kw) -> "ArchConfig":
        return dataclasses.replace(self, **kw)


# registry ------------------------------------------------------------
_REGISTRY: dict[str, "ArchEntry"] = {}


@dataclasses.dataclass
class ArchEntry:
    full: ArchConfig
    reduced: ArchConfig


def register(full: ArchConfig, reduced: ArchConfig) -> ArchConfig:
    _REGISTRY[full.name] = ArchEntry(full=full, reduced=reduced)
    return full


def get_arch(name: str, reduced: bool = False) -> ArchConfig:
    import repro.configs as _c  # noqa: F401  (triggers registration)

    if name not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_REGISTRY)}")
    e = _REGISTRY[name]
    return e.reduced if reduced else e.full


def list_archs() -> list[str]:
    import repro.configs as _c  # noqa: F401

    return sorted(_REGISTRY)
