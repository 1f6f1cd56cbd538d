"""Unified decoder-only LM covering dense / vlm / moe / rwkv / hybrid
families.  Layers are stacked on a leading axis and driven by
``jax.lax.scan`` so the HLO holds one block regardless of depth (94-layer
MoE compiles as fast as 2 layers); caches thread through the same scan as
xs/ys.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.distributed.sharding import ShardingCtx
from repro.models import blocks, common, mla, rwkv6
from repro.models.mamba2 import conv_dim


def family_kind(cfg: ArchConfig) -> str:
    if cfg.is_mla:
        return "mla"  # MLA + dense-first MoE (repro.models.mla)
    if cfg.family == "hybrid":
        return "hybrid"
    if cfg.family == "ssm":
        return "rwkv"
    return "tblock"  # dense, vlm, moe


def _stack_init(init_one, key, n):
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: init_one(common.KeyGen(k)))(keys)


def _prepend_axis(tree, name="layers"):
    return jax.tree.map(lambda axes: (name, *axes),
                        tree, is_leaf=lambda x: isinstance(x, tuple))


# ======================================================================
# init
# ======================================================================
def init_lm(key: jax.Array, cfg: ArchConfig, dtype=jnp.float32) -> dict:
    kind = family_kind(cfg)
    if kind == "mla":   # always bfloat16, drawn from a recipe
        return mla.params_from_recipe(cfg, mla.init_recipe(key, cfg))
    kg = common.KeyGen(key)
    p: dict[str, Any] = {
        "embed": common.normal(kg(), (cfg.padded_vocab, cfg.d_model), dtype, std=0.02),
        "final_norm": common.ones((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = common.normal(kg(), (cfg.d_model, cfg.padded_vocab), dtype, std=0.02)
    if kind == "tblock":
        p["blocks"] = _stack_init(
            lambda k: blocks.init_tblock(k, cfg, dtype, use_moe=cfg.is_moe),
            kg(), cfg.num_layers)
    elif kind == "rwkv":
        p["ln0_s"] = common.ones((cfg.d_model,), dtype)
        p["ln0_b"] = common.zeros((cfg.d_model,), dtype)
        p["final_norm_b"] = common.zeros((cfg.d_model,), dtype)
        p["blocks"] = _stack_init(lambda k: rwkv6.init_rwkv6(k, cfg, dtype),
                                  kg(), cfg.num_layers)
    else:  # hybrid (zamba2)
        n_app, group = hybrid_shape(cfg)
        mb = _stack_init(lambda k: blocks.init_mblock(k, cfg, dtype),
                         kg(), n_app * group)
        p["mamba"] = jax.tree.map(
            lambda a: a.reshape(n_app, group, *a.shape[1:]), mb)
        p["shared"] = _stack_init(lambda k: blocks.init_tblock(k, cfg, dtype),
                                  kg(), cfg.num_shared_blocks)
    return p


def lm_axes(cfg: ArchConfig) -> dict:
    kind = family_kind(cfg)
    if kind == "mla":   # one chip: every tensor replicated
        recipe = mla.init_recipe(jax.random.PRNGKey(0), cfg)
        shapes = jax.eval_shape(lambda: mla.params_from_recipe(cfg, recipe))
        return jax.tree.map(lambda a: (None,) * a.ndim, shapes)
    ax: dict[str, Any] = {"embed": ("vocab", "embed"), "final_norm": (None,)}
    if not cfg.tie_embeddings:
        ax["lm_head"] = ("embed", "vocab")
    if kind == "tblock":
        ax["blocks"] = _prepend_axis(blocks.axes_tblock(cfg, use_moe=cfg.is_moe))
    elif kind == "rwkv":
        ax["ln0_s"] = (None,)
        ax["ln0_b"] = (None,)
        ax["final_norm_b"] = (None,)
        ax["blocks"] = _prepend_axis(rwkv6.axes_rwkv6(cfg))
    else:
        ax["mamba"] = _prepend_axis(_prepend_axis(blocks.axes_mblock(cfg)))
        ax["shared"] = _prepend_axis(blocks.axes_tblock(cfg))
    return ax


def hybrid_shape(cfg: ArchConfig) -> tuple[int, int]:
    group = cfg.shared_attn_every
    assert cfg.num_layers % group == 0
    return cfg.num_layers // group, group


# ======================================================================
# caches
# ======================================================================
def init_cache(cfg: ArchConfig, batch: int, max_seq: int, dtype=jnp.float32) -> dict:
    kind = family_kind(cfg)
    hd = cfg.resolved_head_dim
    L = cfg.num_layers
    if kind == "mla":   # the latent and the shared rope key per token
        return {"c": jnp.zeros((L, batch, max_seq, cfg.kv_lora_rank),
                               jnp.bfloat16),
                "k_pe": jnp.zeros((L, batch, max_seq, cfg.qk_rope_head_dim),
                                  jnp.bfloat16)}
    if kind == "tblock":
        kv = (L, batch, max_seq, cfg.num_kv_heads, hd)
        return {"k": jnp.zeros(kv, dtype), "v": jnp.zeros(kv, dtype)}
    if kind == "rwkv":
        H, K = cfg.rwkv_nheads, cfg.rwkv_head_dim
        return {
            "tm_x": jnp.zeros((L, batch, cfg.d_model), dtype),
            "cm_x": jnp.zeros((L, batch, cfg.d_model), dtype),
            "wkv": jnp.zeros((L, batch, H, K, K), jnp.float32),
        }
    n_app, group = hybrid_shape(cfg)
    H, P, N = cfg.mamba_nheads, cfg.mamba_head_dim, cfg.ssm_state
    kv = (n_app, batch, max_seq, cfg.num_kv_heads, hd)
    return {
        "conv": jnp.zeros((n_app, group, batch, cfg.mamba_conv_width - 1, conv_dim(cfg)), dtype),
        "ssm": jnp.zeros((n_app, group, batch, H, P, N), jnp.float32),
        "k": jnp.zeros(kv, dtype),
        "v": jnp.zeros(kv, dtype),
    }


def cache_axes(cfg: ArchConfig) -> dict:
    kind = family_kind(cfg)
    kv_ax = ("layers", "batch", "cache_seq", "cache_heads", None)
    if kind == "mla":
        return {"c": ("layers", "batch", "cache_seq", None),
                "k_pe": ("layers", "batch", "cache_seq", None)}
    if kind == "tblock":
        return {"k": kv_ax, "v": kv_ax}
    if kind == "rwkv":
        return {"tm_x": ("layers", "batch", "embed"),
                "cm_x": ("layers", "batch", "embed"),
                "wkv": ("layers", "batch", "ssm_heads", None, None)}
    return {
        "conv": ("layers", "layers", "batch", None, "ssm_inner"),
        "ssm": ("layers", "layers", "batch", "ssm_heads", None, None),
        "k": kv_ax, "v": kv_ax,
    }


# ======================================================================
# embedding / head
# ======================================================================
def embed_tokens(p, tokens, cfg: ArchConfig, sh: ShardingCtx,
                 extra_embeds=None) -> jax.Array:
    h = jnp.take(p["embed"], tokens, axis=0)
    if cfg.scale_emb != 1.0:
        h = h * cfg.scale_emb
    if extra_embeds is not None:
        h = jnp.concatenate([extra_embeds.astype(h.dtype), h], axis=1)
    if cfg.pos_scheme == "sinusoidal":
        pos = common.sinusoidal_positions(jnp.arange(h.shape[1]), cfg.d_model, h.dtype)
        h = h + pos[None]
    return sh(h, "batch", "seq", "embed")


def _final_norm(p, h, cfg):
    if family_kind(cfg) == "rwkv":
        return common.layer_norm(h, p["final_norm"], p["final_norm_b"], cfg.norm_eps)
    return common.rms_norm(h, p["final_norm"], cfg.norm_eps)


def lm_head(p, h, cfg: ArchConfig, sh: ShardingCtx) -> jax.Array:
    """h (B,S,d) -> logits (B,S,Vp); expects h already final-normed."""
    logits = (h @ p["embed"].T) if cfg.tie_embeddings else (h @ p["lm_head"])
    if cfg.dim_model_base:
        logits = logits / (cfg.d_model / cfg.dim_model_base)
    return sh(logits, "batch", "seq", "vocab")


# ======================================================================
# forward (no cache): training and encoder-style use
# ======================================================================
def forward(params, tokens, cfg: ArchConfig, sh: ShardingCtx,
            *, extra_embeds=None, remat: bool = False) -> tuple[jax.Array, jax.Array]:
    """Returns (logits (B,S,Vp), moe_aux)."""
    kind = family_kind(cfg)
    if kind == "mla":
        logits = mla.forward(params, mla.embed(params, tokens, extra_embeds),
                             cfg)
        return logits, jnp.zeros((), jnp.float32)
    h = embed_tokens(params, tokens, cfg, sh, extra_embeds)
    if kind == "rwkv":
        h = common.layer_norm(h, params["ln0_s"], params["ln0_b"], cfg.norm_eps)
    S = h.shape[1]
    positions = jnp.arange(S)

    if kind == "tblock":
        def body(carry, bp):
            x, aux = carry
            x, _, a = blocks.apply_tblock(bp, x, cfg=cfg, sh=sh, causal=True,
                                          positions=positions, use_moe=cfg.is_moe)
            return (x, aux + a), None
    elif kind == "rwkv":
        def body(carry, bp):
            x, aux = carry
            x, _ = rwkv6.apply_rwkv6(bp, x, cfg=cfg, sh=sh)
            return (x, aux), None
    else:
        shared = params["shared"]

        def body(carry, xs):
            x, aux = carry
            g, group_params = xs
            sp = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
                a, g % cfg.num_shared_blocks, axis=0, keepdims=False), shared)
            x, _, _ = blocks.apply_tblock(sp, x, cfg=cfg, sh=sh, causal=True,
                                          positions=positions)

            def inner(x2, mp):
                x2, _, _ = blocks.apply_mblock(mp, x2, cfg=cfg, sh=sh)
                return x2, None
            x, _ = jax.lax.scan(inner, x, group_params)
            return (x, aux), None

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)

    aux0 = jnp.zeros((), jnp.float32)
    if kind == "hybrid":
        n_app, _ = hybrid_shape(cfg)
        (h, aux), _ = jax.lax.scan(body, (h, aux0),
                                   (jnp.arange(n_app), params["mamba"]))
    else:
        (h, aux), _ = jax.lax.scan(body, (h, aux0), params["blocks"])

    h = _final_norm(params, h, cfg)
    return lm_head(params, h, cfg, sh), aux


# ======================================================================
# prefill: forward + cache construction
# ======================================================================
def prefill(params, tokens, cfg: ArchConfig, sh: ShardingCtx, max_cache: int,
            *, extra_embeds=None, cache_dtype=None) -> tuple[jax.Array, dict]:
    """Returns (last-position logits (B,Vp), cache).  For MLA the cache
    holds the prompt's positions only (``max_cache`` is not used): its
    decode continues in branches (:func:`repro.models.mla.branch_cache`)."""
    kind = family_kind(cfg)
    if kind == "mla":
        logits, cache, _ = mla.prefill(
            params, mla.embed(params, tokens, extra_embeds), cfg)
        return logits, cache
    h = embed_tokens(params, tokens, cfg, sh, extra_embeds)
    if kind == "rwkv":
        h = common.layer_norm(h, params["ln0_s"], params["ln0_b"], cfg.norm_eps)
    B, S = h.shape[0], h.shape[1]
    cache_dtype = cache_dtype or h.dtype
    positions = jnp.arange(S)
    hd = cfg.resolved_head_dim

    def empty_kv():
        kv = {"k": jnp.zeros((B, max_cache, cfg.num_kv_heads, hd), cache_dtype),
              "v": jnp.zeros((B, max_cache, cfg.num_kv_heads, hd), cache_dtype)}
        return {k: sh(v, "batch", "cache_seq", "cache_heads", None)
                for k, v in kv.items()}

    if kind == "tblock":
        def body(x, bp):
            x, kv, _ = blocks.apply_tblock(bp, x, cfg=cfg, sh=sh, causal=True,
                                           positions=positions, use_moe=cfg.is_moe,
                                           kv_cache=empty_kv(), cache_index=0)
            return x, kv
        h, cache = jax.lax.scan(body, h, params["blocks"])
    elif kind == "rwkv":
        H, K = cfg.rwkv_nheads, cfg.rwkv_head_dim

        def body(x, bp):
            zero = {"tm_x": jnp.zeros((B, cfg.d_model), h.dtype),
                    "cm_x": jnp.zeros((B, cfg.d_model), h.dtype),
                    "wkv": jnp.zeros((B, H, K, K), jnp.float32)}
            x, st = rwkv6.apply_rwkv6(bp, x, cfg=cfg, sh=sh, cache=zero)
            return x, st
        h, cache = jax.lax.scan(body, h, params["blocks"])
    else:
        shared = params["shared"]
        n_app, group = hybrid_shape(cfg)
        W, cd = cfg.mamba_conv_width, conv_dim(cfg)
        H, P, N = cfg.mamba_nheads, cfg.mamba_head_dim, cfg.ssm_state

        def body(x, xs):
            g, group_params = xs
            sp = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
                a, g % cfg.num_shared_blocks, axis=0, keepdims=False), shared)
            x, kv, _ = blocks.apply_tblock(sp, x, cfg=cfg, sh=sh, causal=True,
                                           positions=positions,
                                           kv_cache=empty_kv(), cache_index=0)

            def inner(x2, mp):
                x2, nc, ns = blocks.apply_mblock(
                    mp, x2, cfg=cfg, sh=sh,
                    conv_state=jnp.zeros((B, W - 1, cd), x2.dtype),
                    ssm_state=jnp.zeros((B, H, P, N), jnp.float32))
                return x2, {"conv": nc, "ssm": ns}
            x, states = jax.lax.scan(inner, x, group_params)
            return x, {"conv": states["conv"], "ssm": states["ssm"],
                       "k": kv["k"], "v": kv["v"]}
        h, cache = jax.lax.scan(body, h, (jnp.arange(n_app), params["mamba"]))

    h_last = _final_norm(params, h[:, -1:], cfg)
    logits = lm_head(params, h_last, cfg, sh)
    return logits[:, 0], cache


# ======================================================================
# decode: one token against the cache
# ======================================================================
def decode_step(params, tokens, cache, cache_index, cfg: ArchConfig,
                sh: ShardingCtx) -> tuple[jax.Array, dict]:
    """tokens (B,1) int32; cache_index scalar int32 (valid length so far).
    Returns (logits (B,Vp), new cache).  For MLA: tokens (B, C), one per
    branch, ``cache_index`` the branch slot, logits (B, C, Vp)."""
    kind = family_kind(cfg)
    if kind == "mla":
        logits, cache, _ = mla.decode_step(params, tokens, cache,
                                           cache_index, cfg)
        return logits, cache
    h = embed_tokens(params, tokens, cfg, sh)
    if cfg.pos_scheme == "sinusoidal":
        # embed_tokens added position 0; replace with cache_index position
        pos = common.sinusoidal_positions(
            jnp.arange(1) + cache_index, cfg.d_model, h.dtype)
        pos0 = common.sinusoidal_positions(jnp.arange(1), cfg.d_model, h.dtype)
        h = h + (pos - pos0)[None]
    if kind == "rwkv":
        h = common.layer_norm(h, params["ln0_s"], params["ln0_b"], cfg.norm_eps)
    positions = cache_index + jnp.arange(1)

    if kind == "tblock":
        def body(x, xs):
            bp, kv = xs
            x, kv_new, _ = blocks.apply_tblock(bp, x, cfg=cfg, sh=sh, causal=True,
                                               positions=positions, use_moe=cfg.is_moe,
                                               kv_cache=kv, cache_index=cache_index)
            return x, kv_new
        h, new_cache = jax.lax.scan(body, h, (params["blocks"], cache))
    elif kind == "rwkv":
        def body(x, xs):
            bp, st = xs
            x, st_new = rwkv6.apply_rwkv6(bp, x, cfg=cfg, sh=sh, cache=st)
            return x, st_new
        h, new_cache = jax.lax.scan(body, h, (params["blocks"], cache))
    else:
        shared = params["shared"]
        n_app, _ = hybrid_shape(cfg)

        def body(x, xs):
            g, group_params, st = xs
            sp = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
                a, g % cfg.num_shared_blocks, axis=0, keepdims=False), shared)
            x, kv_new, _ = blocks.apply_tblock(
                sp, x, cfg=cfg, sh=sh, causal=True, positions=positions,
                kv_cache={"k": st["k"], "v": st["v"]}, cache_index=cache_index)

            def inner(x2, xs2):
                mp, c, s = xs2
                x2, nc, ns = blocks.apply_mblock(mp, x2, cfg=cfg, sh=sh,
                                                 conv_state=c, ssm_state=s)
                return x2, (nc, ns)
            x, (conv_new, ssm_new) = jax.lax.scan(
                inner, x, (group_params, st["conv"], st["ssm"]))
            return x, {"conv": conv_new, "ssm": ssm_new,
                       "k": kv_new["k"], "v": kv_new["v"]}
        h, new_cache = jax.lax.scan(
            body, h, (jnp.arange(n_app), params["mamba"], cache))

    h = _final_norm(params, h, cfg)
    logits = lm_head(params, h, cfg, sh)
    return logits[:, 0], new_cache
