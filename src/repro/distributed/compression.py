"""Gradient compression for the data-parallel reduction.

Two mechanisms:

1. bf16 gradient reduction (default ON via TrainConfig.grad_reduce_dtype):
   the backward emits bf16 gradients, so GSPMD's DP all-reduce moves half
   the bytes.  Zero code here — it falls out of dtype flow.

2. int8 + error feedback (this module): for pure-DP meshes (model
   replicated, e.g. the paper-style "kappa remote servers" scale-out),
   ``compressed_psum_int8`` implements a two-phase quantized reduction
   inside shard_map: per-chunk int8 quantization -> all_to_all
   (reduce-scatter phase, int8 on the wire) -> local f32 accumulate ->
   re-quantize -> all_gather (int8 on the wire).  Wire bytes ~ 0.5x f32
   all-reduce's 2x payload => ~4x compression.  ``ErrorFeedback`` keeps
   the quantization residual and folds it into the next step (Karimireddy
   et al.), preserving convergence.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _quantize_int8(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric per-tensor int8 quantization; returns (q, scale)."""
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12)
    scale = amax / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _dequantize(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def compressed_psum_int8(x: jax.Array, axis_name: str) -> jax.Array:
    """Mean over ``axis_name`` with int8 wire traffic (call inside
    shard_map).  x: (1, size) member-local gradient vector, size divisible
    by the axis size.  Returns (1, size): every member holds the mean.

    Wire traffic per member: size/4 bytes (all-to-all of int8 chunks) +
    size/4 bytes (all-gather of re-quantized means) vs 2*size*4 bytes for
    a ring f32 all-reduce => ~8x wire compression (4x vs bf16)."""
    n = jax.lax.axis_size(axis_name)
    v = x[0]
    cs = v.shape[0] // n
    chunks = v.reshape(n, cs)
    q, scale = _quantize_int8(chunks)             # one scale per member
    # phase 1 (reduce-scatter shape): peer j receives every member's chunk j
    q_t = jax.lax.all_to_all(q, axis_name, split_axis=0, concat_axis=0,
                             tiled=True)          # (n, cs): row i = peer i's my-chunk
    scales = jax.lax.all_gather(scale, axis_name) # (n,)
    mean_chunk = jnp.sum(q_t.astype(jnp.float32)
                         * scales[:, None], axis=0) / n   # (cs,)
    # phase 2: publish the owned mean chunk
    q2, s2 = _quantize_int8(mean_chunk)
    gathered = jax.lax.all_gather(q2, axis_name)          # (n, cs) int8
    s_all = jax.lax.all_gather(s2, axis_name)             # (n,)
    out = gathered.astype(jnp.float32) * s_all[:, None]
    return out.reshape(1, n * cs)


def make_compressed_grad_reducer(mesh: Mesh, axis: str = "data"):
    """Returns reduce(grads_tree): input leaves are (n, ...) arrays sharded
    ``P(axis)`` — row i is member i's local gradient — output is the same
    shape with every row holding the int8-wire mean.  For pure-DP meshes
    (model replicated over ``axis``), e.g. the paper-style kappa-server
    scale-out in examples/scaleout_train.py."""
    n = mesh.devices.shape[list(mesh.axis_names).index(axis)]

    def reduce_tree(grads):
        def one(g):
            rows, size = g.shape[0], int(np.prod(g.shape[1:]))
            assert rows == n, f"leading dim {rows} != DP size {n}"
            pad = (-size) % n
            flat = g.reshape(n, size).astype(jnp.float32)
            if pad:
                flat = jnp.concatenate(
                    [flat, jnp.zeros((n, pad), jnp.float32)], axis=1)

            fn = jax.shard_map(
                functools.partial(compressed_psum_int8, axis_name=axis),
                mesh=mesh,
                in_specs=P(axis, None),
                out_specs=P(axis, None),
            )
            red = fn(flat)
            return red[:, :size].reshape(g.shape)

        return jax.tree.map(one, grads)

    return reduce_tree


class ErrorFeedback:
    """e_{t} = g_t + e_{t-1} - Q(g_t + e_{t-1}); carried in the train state."""

    @staticmethod
    def init(params) -> Any:
        return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

    @staticmethod
    def apply(grads, ef_state, quantize=_quantize_int8):
        corrected = jax.tree.map(
            lambda g, e: g.astype(jnp.float32) + e, grads, ef_state)

        def q_dq(x):
            q, s = quantize(x)
            return _dequantize(q, s)

        sent = jax.tree.map(q_dq, corrected)
        new_ef = jax.tree.map(lambda c, s: c - s, corrected, sent)
        return sent, new_ef
