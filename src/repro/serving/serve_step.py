"""Serving steps: prefill + single-token decode (``serve_step``), plus a
small generate loop used by the examples and the query engine's
model-UDF executor."""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.distributed.sharding import ShardingCtx
from repro.models.registry import ModelAPI


def make_serve_fns(model: ModelAPI, sh: ShardingCtx, cache_dtype=jnp.float32):
    """Returns (prefill_fn, serve_step).

    prefill_fn(params, batch, max_cache) -> (last_logits (B,V), cache)
    serve_step(params, tokens (B,1), cache, cache_index) -> (logits, cache)
    """

    def prefill_fn(params, batch, max_cache: int):
        return model.prefill(params, batch, sh, max_cache, cache_dtype=cache_dtype)

    def serve_step(params, tokens, cache, cache_index):
        return model.decode_step(params, tokens, cache, cache_index, sh)

    return prefill_fn, serve_step


def sample_token(logits: jax.Array, key, temperature: float = 0.0,
                 vocab_size: int | None = None) -> jax.Array:
    """logits (B, Vp) -> (B, 1) int32; temperature 0 = greedy."""
    if vocab_size is not None and logits.shape[-1] > vocab_size:
        mask = jnp.arange(logits.shape[-1]) < vocab_size
        logits = jnp.where(mask, logits, -1e30)
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
    return jax.random.categorical(key, logits / temperature).astype(jnp.int32)[:, None]


def greedy_generate(model: ModelAPI, params, batch: dict, *, steps: int,
                    sh: ShardingCtx, max_cache: int | None = None,
                    temperature: float = 0.0, key=None) -> jnp.ndarray:
    """Prefill then decode ``steps`` tokens; returns (B, steps) int32."""
    cfg = model.cfg
    P = cfg.num_patches if cfg.frontend == "vit_stub" else 0
    prompt_len = batch["tokens"].shape[1] + P
    max_cache = max_cache or (prompt_len + steps + 1)
    key = key if key is not None else jax.random.PRNGKey(0)

    prefill_fn, serve_step = make_serve_fns(model, sh)
    logits, cache = prefill_fn(params, batch, max_cache)
    out = []
    tok = sample_token(logits, key, temperature, cfg.vocab_size)
    idx = jnp.asarray(prompt_len, jnp.int32)
    step_jit = jax.jit(serve_step, donate_argnums=(2,))
    for i in range(steps):
        out.append(tok)
        logits, cache = step_jit(params, tok, cache, idx)
        tok = sample_token(logits, jax.random.fold_in(key, i), temperature,
                           cfg.vocab_size)
        idx = idx + 1
    return jnp.concatenate(out, axis=1)
