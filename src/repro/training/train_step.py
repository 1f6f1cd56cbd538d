"""The jit-compiled training step: mixed precision, remat, grad clipping,
AdamW, and dtype-controlled DP gradient reduction.

Mixed precision: master params are f32; the forward/backward runs in
``compute_dtype`` (bf16 on TPU).  Gradients come out of the backward in
``grad_reduce_dtype`` where safe — under GSPMD the DP all-reduce then
moves half the bytes, which is the "gradient compression" knob.
int8+error-feedback compression for pure-DP meshes lives in
distributed/compression.py.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.distributed.sharding import ShardingCtx
from repro.models.registry import ModelAPI
from repro.training.optimizer import (
    TrainConfig, adamw_update, global_norm, init_moments, lr_schedule)


def init_train_state(model: ModelAPI, key, param_dtype=jnp.float32) -> dict:
    params = model.init(key, dtype=param_dtype)
    m, v = init_moments(params)
    return {"params": params, "m": m, "v": v, "step": jnp.zeros((), jnp.int32)}


def train_state_axes(model: ModelAPI) -> dict:
    ax = model.param_axes()
    return {"params": ax, "m": ax, "v": ax, "step": ()}


def make_train_step(model: ModelAPI, tcfg: TrainConfig, sh: ShardingCtx):
    sched = lr_schedule(tcfg)
    cdtype = jnp.dtype(tcfg.compute_dtype)

    def cast(p):
        return jax.tree.map(
            lambda x: x.astype(cdtype) if x.dtype == jnp.float32 and x.ndim >= 1
            else x, p)

    def train_step(state: dict, batch: dict) -> tuple[dict, dict]:
        def loss_fn(params, b):
            loss, metrics = model.loss(cast(params), b, sh, remat=tcfg.remat)
            return loss, metrics

        mb = max(int(tcfg.microbatches), 1)
        if mb == 1:
            (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                state["params"], batch)
        else:
            # sequential microbatches: grads accumulate in f32; the remat
            # residual stack only ever holds B/mb sequences.
            def split(x):
                y = x.reshape(mb, x.shape[0] // mb, *x.shape[1:])
                return sh(y, None, "batch", *([None] * (y.ndim - 2)))
            mbatch = jax.tree.map(split, batch)
            params = state["params"]

            def micro(carry, b):
                gacc, lacc = carry
                (l, mets), g = jax.value_and_grad(loss_fn, has_aux=True)(params, b)
                gacc = jax.tree.map(
                    lambda a, x: a + x.astype(jnp.float32), gacc, g)
                return (gacc, lacc + l), mets

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, loss_sum), mets = jax.lax.scan(
                micro, (g0, jnp.zeros((), jnp.float32)), mbatch)
            grads = jax.tree.map(lambda g: g / mb, grads)
            loss = loss_sum / mb
            metrics = jax.tree.map(lambda x: x[-1], mets)
        if tcfg.grad_reduce_dtype != "float32":
            rdt = jnp.dtype(tcfg.grad_reduce_dtype)
            grads = jax.tree.map(lambda g: g.astype(rdt), grads)

        gnorm = global_norm(grads)
        scale = jnp.minimum(1.0, tcfg.clip_norm / (gnorm + 1e-9))
        grads = jax.tree.map(lambda g: g.astype(jnp.float32) * scale, grads)

        step = state["step"] + 1
        lr = sched(step)
        new_p, new_m, new_v = adamw_update(
            state["params"], grads, state["m"], state["v"], step, tcfg, lr)
        new_state = {"params": new_p, "m": new_m, "v": new_v, "step": step}
        metrics = dict(metrics)
        metrics.update({"loss": loss, "grad_norm": gnorm, "lr": lr})
        return new_state, metrics

    return train_step
