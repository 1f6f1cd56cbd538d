"""Seeded LFW-shaped collection: face images and their metadata.

The pixels follow the distribution of ``repro.dataio.synthetic_faces``
(a skin-tone ellipse with eyes and a mouth on a textured background,
values in [0, 1]), drawn on the device a chunk of faces at a time
instead of face by face on the host.  The metadata gives every category
the same histogram of ages, and only the order of the faces depends on
the seed: every seed asks the same amount of work of a query that
selects a category and an age window.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

CHUNK = 64


def generate(seed: int, n: int, size: int, chunk: int = CHUNK) -> np.ndarray:
    """(n, size, size, 3) float32 faces in [0, 1], the same for the same
    seed on the same platform.  Each chunk of faces is drawn on the
    default device by one jitted call and copied to the host."""
    words = np.random.SeedSequence(seed).generate_state(2)
    key = jax.random.wrap_key_data(np.asarray(words, np.uint32))
    out = np.empty((n, size, size, 3), np.float32)
    for i, lo in enumerate(range(0, n, chunk)):
        m = min(chunk, n - lo)
        faces = _draw(jax.random.fold_in(key, i), chunk, size)
        out[lo:lo + m] = np.asarray(faces)[:m]
    return out


@functools.partial(jax.jit, static_argnums=(1, 2))
def _draw(key, m: int, size: int):
    k = jax.random.split(key, 12)
    u = lambda i, lo, hi, shape=(m,): jax.random.uniform(  # noqa: E731
        k[i], shape, jnp.float32, lo, hi)
    img = u(0, 0.05, 0.35, (m, size, size, 3))
    ramp = jnp.linspace(0.0, 1.0, size)[None, :] * u(1, 2.0, 8.0)[:, None]
    img = img + (0.1 * jnp.sin(ramp))[:, None, :, None]
    cy = (u(2, 0.35, 0.65) * size).astype(jnp.int32)
    cx = (u(3, 0.35, 0.65) * size).astype(jnp.int32)
    ry = jnp.maximum((size * u(4, 0.18, 0.3)).astype(jnp.int32), 1)
    rx = jnp.maximum((size * u(5, 0.14, 0.24)).astype(jnp.int32), 1)
    skin = jnp.stack([u(6, 0.55, 0.85), u(7, 0.4, 0.6), u(8, 0.3, 0.45)], -1)
    skin = skin * u(9, 0.9, 1.1)[:, None]
    ys = jnp.arange(size, dtype=jnp.float32)[None, :, None]
    xs = jnp.arange(size, dtype=jnp.float32)[None, None, :]
    c = lambda v: v[:, None, None].astype(jnp.float32)  # noqa: E731
    ellipse = ((ys - c(cy)) / c(ry)) ** 2 + ((xs - c(cx)) / c(rx)) ** 2 <= 1
    img = jnp.where(ellipse[..., None], skin[:, None, None, :], img)
    eye_r2 = float(max(size // 40, 2) ** 2)
    for sign in (-1, 1):
        ey, ex = cy - ry // 3, cx + sign * (rx // 2)
        eye = (ys - c(ey)) ** 2 + (xs - c(ex)) ** 2 <= eye_r2
        img = jnp.where(eye[..., None], 0.08, img)
    mouth = ((jnp.abs(ys - c(cy + ry // 2)) <= max(size // 60, 1))
             & (jnp.abs(xs - c(cx)) <= c(rx // 2)))
    img = jnp.where(mouth[..., None],
                    jnp.asarray([0.5, 0.15, 0.15], jnp.float32), img)
    return jnp.clip(img, 0.0, 1.0)


def properties(seed: int, n: int, categories: list[str], age_min: int,
               age_max: int) -> list[dict]:
    """One metadata row per face: ``category``, ``age`` and ``idx``.

    Category k holds faces with ages ``age_min + (j mod span)`` for its
    j-th member, so the count of faces per (category, age) is the same
    for every seed; the seed only shuffles which face gets which row."""
    span = age_max - age_min + 1
    rows = [(categories[i % len(categories)],
             age_min + (i // len(categories)) % span) for i in range(n)]
    order = np.random.default_rng([seed, 1]).permutation(n)
    return [{"category": rows[k][0], "age": int(rows[k][1]), "idx": i}
            for i, k in enumerate(order)]


def select(props: list[dict], category: str, age_lo: int,
           age_hi: int) -> list[int]:
    """Dataset indices a category/age-window predicate selects."""
    return [p["idx"] for p in props
            if p["category"] == category and age_lo <= p["age"] <= age_hi]
