"""Whisper-style encoder-decoder.  The conv/mel frontend is a STUB per
the assignment: the encoder consumes precomputed frame embeddings
(B, Se, d)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.distributed.sharding import ShardingCtx
from repro.models import attention, blocks, common


def init_encdec(key: jax.Array, cfg: ArchConfig, dtype=jnp.float32) -> dict:
    kg = common.KeyGen(key)

    def enc_one(k):
        return blocks.init_tblock(k, cfg, dtype, mlp_kind="gelu", norm="layer")

    def dec_one(k):
        return blocks.init_tblock(k, cfg, dtype, cross=True, mlp_kind="gelu",
                                  norm="layer")

    ekeys = jax.random.split(kg(), cfg.num_encoder_layers)
    dkeys = jax.random.split(kg(), cfg.num_layers)
    return {
        "embed": common.normal(kg(), (cfg.padded_vocab, cfg.d_model), dtype, std=0.02),
        "enc_blocks": jax.vmap(lambda k: enc_one(common.KeyGen(k)))(ekeys),
        "enc_norm": common.ones((cfg.d_model,), dtype),
        "enc_norm_b": common.zeros((cfg.d_model,), dtype),
        "dec_blocks": jax.vmap(lambda k: dec_one(common.KeyGen(k)))(dkeys),
        "dec_norm": common.ones((cfg.d_model,), dtype),
        "dec_norm_b": common.zeros((cfg.d_model,), dtype),
    }


def encdec_axes(cfg: ArchConfig) -> dict:
    def pre(t):
        return jax.tree.map(lambda axes: ("layers", *axes), t,
                            is_leaf=lambda x: isinstance(x, tuple))
    return {
        "embed": ("vocab", "embed"),
        "enc_blocks": pre(blocks.axes_tblock(cfg, mlp_kind="gelu", norm="layer")),
        "enc_norm": (None,), "enc_norm_b": (None,),
        "dec_blocks": pre(blocks.axes_tblock(cfg, cross=True, mlp_kind="gelu",
                                             norm="layer")),
        "dec_norm": (None,), "dec_norm_b": (None,),
    }


def encode(params, frames, cfg: ArchConfig, sh: ShardingCtx,
           remat: bool = False) -> jax.Array:
    """frames: (B, Se, d) precomputed frontend embeddings."""
    h = frames + common.sinusoidal_positions(
        jnp.arange(frames.shape[1]), cfg.d_model, frames.dtype)[None]
    h = sh(h, "batch", "seq", "embed")

    def body(x, bp):
        x, _, _ = blocks.apply_tblock(bp, x, cfg=cfg, sh=sh, causal=False,
                                      mlp_kind="gelu", norm="layer")
        return x, None
    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    h, _ = jax.lax.scan(body, h, params["enc_blocks"])
    return common.layer_norm(h, params["enc_norm"], params["enc_norm_b"], cfg.norm_eps)


def _dec_embed(params, tokens, cfg, sh, offset=0):
    h = jnp.take(params["embed"], tokens, axis=0)
    pos = common.sinusoidal_positions(
        jnp.arange(tokens.shape[1]) + offset, cfg.d_model, h.dtype)
    return sh(h + pos[None], "batch", "seq", "embed")


def forward(params, frames, tokens, cfg: ArchConfig, sh: ShardingCtx,
            *, remat: bool = False) -> tuple[jax.Array, jax.Array]:
    """Teacher-forced training pass -> (logits (B,S,Vp), aux=0)."""
    enc = encode(params, frames, cfg, sh, remat=remat)
    h = _dec_embed(params, tokens, cfg, sh)

    def body(x, bp):
        x, _, _ = blocks.apply_tblock(bp, x, cfg=cfg, sh=sh, causal=True,
                                      enc=enc, mlp_kind="gelu", norm="layer")
        return x, None
    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    h, _ = jax.lax.scan(body, h, params["dec_blocks"])
    h = common.layer_norm(h, params["dec_norm"], params["dec_norm_b"], cfg.norm_eps)
    logits = h @ params["embed"].T  # whisper ties decoder embedding
    return sh(logits, "batch", "seq", "vocab"), jnp.zeros((), jnp.float32)


def init_cache(cfg: ArchConfig, batch: int, max_seq: int, dtype=jnp.float32) -> dict:
    hd = cfg.resolved_head_dim
    L = cfg.num_layers
    return {
        "k": jnp.zeros((L, batch, max_seq, cfg.num_kv_heads, hd), dtype),
        "v": jnp.zeros((L, batch, max_seq, cfg.num_kv_heads, hd), dtype),
        "xk": jnp.zeros((L, batch, cfg.encoder_seq_len, cfg.num_kv_heads, hd), dtype),
        "xv": jnp.zeros((L, batch, cfg.encoder_seq_len, cfg.num_kv_heads, hd), dtype),
    }


def cache_axes(cfg: ArchConfig) -> dict:
    kv = ("layers", "batch", "cache_seq", "cache_heads", None)
    enc_kv = ("layers", "batch", None, "cache_heads", None)
    return {"k": kv, "v": kv, "xk": enc_kv, "xv": enc_kv}


def prefill(params, frames, tokens, cfg: ArchConfig, sh: ShardingCtx,
            max_cache: int, cache_dtype=None) -> tuple[jax.Array, dict]:
    """Encode audio + prefill decoder tokens -> (last logits (B,Vp), cache)."""
    enc = encode(params, frames, cfg, sh)
    h = _dec_embed(params, tokens, cfg, sh)
    B, S = tokens.shape
    hd = cfg.resolved_head_dim
    cache_dtype = cache_dtype or h.dtype

    def body(x, bp):
        kv0 = {"k": jnp.zeros((B, max_cache, cfg.num_kv_heads, hd), cache_dtype),
               "v": jnp.zeros((B, max_cache, cfg.num_kv_heads, hd), cache_dtype)}
        kv0 = {k: sh(v, "batch", "cache_seq", "cache_heads", None) for k, v in kv0.items()}
        x, kv, _ = blocks.apply_tblock(bp, x, cfg=cfg, sh=sh, causal=True,
                                       enc=enc, mlp_kind="gelu", norm="layer",
                                       kv_cache=kv0, cache_index=0)
        xc = attention.make_cross_cache(bp["xattn"], enc, cfg, sh)
        return x, {"k": kv["k"], "v": kv["v"],
                   "xk": xc["k"].astype(cache_dtype), "xv": xc["v"].astype(cache_dtype)}

    h, cache = jax.lax.scan(body, h, params["dec_blocks"])
    h = common.layer_norm(h[:, -1:], params["dec_norm"], params["dec_norm_b"], cfg.norm_eps)
    return (h @ params["embed"].T)[:, 0], cache


def decode_step(params, tokens, cache, cache_index, cfg: ArchConfig,
                sh: ShardingCtx) -> tuple[jax.Array, dict]:
    h = _dec_embed(params, tokens, cfg, sh, offset=cache_index)

    def body(x, xs):
        bp, st = xs
        x, kv, _ = blocks.apply_tblock(
            bp, x, cfg=cfg, sh=sh, causal=True, mlp_kind="gelu", norm="layer",
            kv_cache={"k": st["k"], "v": st["v"]}, cache_index=cache_index,
            cross_cache={"k": st["xk"], "v": st["xv"]})
        return x, {"k": kv["k"], "v": kv["v"], "xk": st["xk"], "xv": st["xv"]}

    h, new_cache = jax.lax.scan(body, h, (params["dec_blocks"], cache))
    h = common.layer_norm(h, params["dec_norm"], params["dec_norm_b"], cfg.norm_eps)
    return (h @ params["embed"].T)[:, 0], new_cache
