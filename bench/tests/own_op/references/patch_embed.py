"""Plain reference of ``patch_embed``: the linear patch embedding that
opens a vision transformer (arXiv:2010.11929, eq. 1): the image cut into
``patch`` x ``patch`` tiles, each tile's pixels flattened (row, column,
channel) and projected to ``dim`` features by ``w`` plus ``b``.  The
output of one (H, W, C) image is (H/patch * W/patch, dim).  Imports
nothing of ``repro``."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

DISCRETE = False
ON_DEVICE = True
BLOCK = 16


def weights(spec: dict, seed: int) -> dict:
    """``w`` (patch * patch * channels, dim) and ``b`` (dim,), float32,
    drawn from the seed at the scale of a fan-in initialisation."""
    fan_in = spec["patch"] ** 2 * spec["channels"]
    rng = np.random.default_rng([seed, 17])
    return {"w": (rng.standard_normal((fan_in, spec["dim"]))
                  / np.sqrt(fan_in)).astype(np.float32),
            "b": (0.1 * rng.standard_normal(spec["dim"])).astype(np.float32)}


def reference(images, params, *, spec, weights, device, dtype):
    p = spec["patch"]
    out = []
    with jax.default_matmul_precision("highest"):
        w = jax.device_put(weights["w"], device).astype(dtype)
        b = jax.device_put(weights["b"], device).astype(dtype)
        for lo in range(0, len(images), BLOCK):
            x = jax.device_put(np.asarray(images[lo:lo + BLOCK]),
                               device).astype(dtype)
            n, h, wd, c = x.shape
            tiles = x.reshape(n, h // p, p, wd // p, p, c)
            tiles = tiles.transpose(0, 1, 3, 2, 4, 5).reshape(
                n, (h // p) * (wd // p), p * p * c)
            out.append(np.asarray((tiles @ w + b).astype(jnp.float32)))
    return np.concatenate(out)
