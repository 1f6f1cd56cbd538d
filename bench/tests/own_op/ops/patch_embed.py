"""``patch_embed`` in the engine, as a client of the system adds it: a
per-entity UDF and a device UDF that embeds a whole micro-batch in one
compiled call, both over the weights the benchmark drew."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.udf import register_device_udf, register_udf

NAME = "patch_embed"


def install(engine, spec: dict, weights: dict, devices) -> None:
    p = spec["patch"]
    w = jax.device_put(weights["w"], devices[0])
    b = jax.device_put(weights["b"], devices[0])

    @jax.jit
    def embed(batch, w, b):
        n, h, wd, c = batch.shape
        tiles = batch.reshape(n, h // p, p, wd // p, p, c)
        tiles = tiles.transpose(0, 1, 3, 2, 4, 5).reshape(
            n, (h // p) * (wd // p), p * p * c)
        return jnp.dot(tiles, w, precision="highest") + b

    def batched(imgs, **_):
        x = jax.device_put(np.stack([np.asarray(i) for i in imgs]),
                           devices[0])
        return list(np.asarray(embed(x, w, b)))

    register_udf(NAME, lambda img, **_: batched([img])[0])
    register_device_udf(NAME, batched)
