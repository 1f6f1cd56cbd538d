"""ModelAPI: one uniform surface over all 10 assigned architectures.

``get_model(cfg)`` returns callables the training/serving/launch layers
use without knowing the family: init / forward / loss / prefill /
decode_step / init_cache, plus the logical-axis trees for sharding.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.distributed.sharding import ShardingCtx
from repro.models import common, encdec, lm


@dataclasses.dataclass
class ModelAPI:
    cfg: ArchConfig
    init: Callable[..., dict]
    param_axes: Callable[[], dict]
    forward: Callable[..., tuple[jax.Array, jax.Array]]
    loss: Callable[..., tuple[jax.Array, dict]]
    prefill: Callable[..., tuple[jax.Array, dict]]
    decode_step: Callable[..., tuple[jax.Array, dict]]
    init_cache: Callable[..., dict]
    cache_axes: Callable[[], dict]


def _token_start(cfg: ArchConfig) -> int:
    return cfg.num_patches if cfg.frontend == "vit_stub" else 0


def get_model(cfg: ArchConfig) -> ModelAPI:
    if cfg.is_encoder_decoder:
        return _encdec_api(cfg)
    return _lm_api(cfg)


# ----------------------------------------------------------------- LM
def _lm_api(cfg: ArchConfig) -> ModelAPI:
    P = _token_start(cfg)

    def init(key, dtype=jnp.float32):
        return lm.init_lm(key, cfg, dtype)

    def extra(params, batch):
        if cfg.frontend == "patch_merge" and "images" in batch:
            from repro.models import mla
            return mla.image_embeds(params, batch["images"], cfg)
        return batch.get("patch_embeds")

    def forward(params, batch, sh: ShardingCtx, remat=False):
        return lm.forward(params, batch["tokens"], cfg, sh,
                          extra_embeds=extra(params, batch), remat=remat)

    def loss(params, batch, sh: ShardingCtx, remat=True):
        logits, aux = forward(params, batch, sh, remat=remat)
        # next-token prediction over the token region (skips patch slots)
        lg = logits[:, P:-1] if P else logits[:, :-1]
        lbl = batch["tokens"][:, 1:]
        mask = batch.get("mask")
        mask = mask[:, 1:] if mask is not None else None
        ce, ntok = common.cross_entropy_loss(lg, lbl, cfg.vocab_size, mask)
        total = ce + aux
        return total, {"ce": ce, "aux": aux, "ntok": ntok}

    def prefill(params, batch, sh: ShardingCtx, max_cache: int, cache_dtype=None):
        return lm.prefill(params, batch["tokens"], cfg, sh, max_cache,
                          extra_embeds=extra(params, batch),
                          cache_dtype=cache_dtype)

    def decode_step(params, tokens, cache, cache_index, sh: ShardingCtx):
        return lm.decode_step(params, tokens, cache, cache_index, cfg, sh)

    return ModelAPI(
        cfg=cfg,
        init=init,
        param_axes=lambda: lm.lm_axes(cfg),
        forward=forward,
        loss=loss,
        prefill=prefill,
        decode_step=decode_step,
        init_cache=lambda batch, max_seq, dtype=jnp.float32: lm.init_cache(
            cfg, batch, max_seq, dtype),
        cache_axes=lambda: lm.cache_axes(cfg),
    )


# ------------------------------------------------------------- enc-dec
def _encdec_api(cfg: ArchConfig) -> ModelAPI:
    def init(key, dtype=jnp.float32):
        return encdec.init_encdec(key, cfg, dtype)

    def forward(params, batch, sh: ShardingCtx, remat=False):
        return encdec.forward(params, batch["frames"], batch["tokens"], cfg, sh,
                              remat=remat)

    def loss(params, batch, sh: ShardingCtx, remat=True):
        logits, aux = forward(params, batch, sh, remat=remat)
        ce, ntok = common.cross_entropy_loss(
            logits[:, :-1], batch["tokens"][:, 1:], cfg.vocab_size,
            batch.get("mask", None) if batch.get("mask") is None
            else batch["mask"][:, 1:])
        return ce + aux, {"ce": ce, "aux": aux, "ntok": ntok}

    def prefill(params, batch, sh: ShardingCtx, max_cache: int, cache_dtype=None):
        return encdec.prefill(params, batch["frames"], batch["tokens"], cfg, sh,
                              max_cache, cache_dtype=cache_dtype)

    def decode_step(params, tokens, cache, cache_index, sh: ShardingCtx):
        return encdec.decode_step(params, tokens, cache, cache_index, cfg, sh)

    return ModelAPI(
        cfg=cfg,
        init=init,
        param_axes=lambda: encdec.encdec_axes(cfg),
        forward=forward,
        loss=loss,
        prefill=prefill,
        decode_step=decode_step,
        init_cache=lambda batch, max_seq, dtype=jnp.float32: encdec.init_cache(
            cfg, batch, max_seq, dtype),
        cache_axes=lambda: encdec.cache_axes(cfg),
    )
