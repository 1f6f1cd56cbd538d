"""Fused resize→crop→normalize preprocessing Pallas TPU kernel.

The three most common pipeline-prefix ops collapse into ONE kernel
launch by exploiting that bilinear (and any separable-filter) resize is
a *linear* map per axis: ``resize(img) = Ry @ img @ Rx^T`` for
interpolation matrices ``Ry (H_out, H_in)`` / ``Rx (W_out, W_in)``.
The matrices are extracted exactly — antialiasing taps included — by
resizing an identity matrix through ``jax.image.resize`` itself (resize
is separable, so probing each axis with ``eye`` recovers its exact
weights).  Crop then *slices rows out of the matrices* instead of the
image, and normalize folds into a trailing affine:

    out = (Ry[cy:cy+ch] @ img @ Rx[cx:cx+cw]^T - mean) / std

so the fused op is two MXU matmuls plus a VPU affine — no gather, no
intermediate (H_res, W_res) image ever materializes, and the cropped
rows of the resize are never computed at all.

The kernel runs one channel plane per grid step: the wrapper moves
channels off the lane axis — (N, H, W, C) becomes channel-planar
(N, C, H, W), so W sits on the lanes — and grid = (N, C); each step sees
one (H_in, W_in) plane plus the two small matrices (replicated across
the grid) and computes two plain 2-D matmuls, ``Ry @ plane @ Rx^T``.
The matrices are host constants computed once per shape, never traced
values.  VMEM at 250x250 -> 224x224: plane, Ry, Rx^T and output are
each ~0.2 MB, under 2 MB double-buffered; a 1080p plane (8 MB) would
need row bands.

``fused_resize_crop_normalize`` is the public entry; ``impl="auto"``
lowers to Pallas on TPU and to the composed reference ops elsewhere, so
results are bit-identical to running the three native-table ops
separately on CPU hosts (the reference path IS the composed ops).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def resize_matrix(n_in: int, n_out: int, method: str = "bilinear"):
    """Exact (n_out, n_in) interpolation matrix of ``jax.image.resize``
    along one axis, antialiasing taps included — probed by resizing the
    identity (resize is separable and linear per axis).  Evaluated
    eagerly on the host CPU, also when called while a jit is tracing, so
    the matrix is a numpy constant of the program."""
    cpu = jax.devices("cpu")[0]
    with jax.ensure_compile_time_eval():
        eye = jax.device_put(np.eye(n_in, dtype=np.float32), cpu)
        # resize axis 0 only: axis 1 keeps its size (scale 1 == identity)
        return np.asarray(jax.image.resize(eye, (n_out, n_in),
                                           method=method))


@functools.lru_cache(maxsize=64)
def _cropped_matrices(h_in: int, w_in: int, h_res: int, w_res: int,
                      method: str, cx: int, cy: int, cw: int, ch: int):
    """Interpolation matrices with the crop window folded in (clamped
    exactly like ``visual.ops.crop``: dynamic_slice semantics — the
    window is shrunk to the image and the start clamped inside it)."""
    ch = min(ch, h_res)
    cw = min(cw, w_res)
    cy = max(0, min(cy, h_res - ch))
    cx = max(0, min(cx, w_res - cw))
    ry = resize_matrix(h_in, h_res, method)[cy:cy + ch]
    rx = resize_matrix(w_in, w_res, method)[cx:cx + cw]
    return ry, rx


def _preprocess_kernel(img_ref, ry_ref, rxt_ref, o_ref, *, mean, std):
    plane = img_ref[0, 0].astype(jnp.float32)       # (Hi, Wi)
    hp = jax.lax.Precision.HIGHEST                   # f32 MXU passes
    tmp = jnp.dot(ry_ref[...], plane, precision=hp,  # (Hc, Hi) @ (Hi, Wi)
                  preferred_element_type=jnp.float32)
    out = jnp.dot(tmp, rxt_ref[...], precision=hp,   # (Hc, Wi) @ (Wi, Wc)
                  preferred_element_type=jnp.float32)
    o_ref[0, 0] = ((out - mean) / std).astype(o_ref.dtype)


def fused_resize_crop_normalize_pallas(
    img: jax.Array,   # (N, H, W, C) or (H, W, C)
    *,
    resize_h: int, resize_w: int, method: str = "bilinear",
    crop_x: int, crop_y: int, crop_w: int, crop_h: int,
    mean: float = 0.0, std: float = 1.0,
    interpret: bool = False,
) -> jax.Array:
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    squeeze = img.ndim == 3
    if squeeze:
        img = img[None]
    n, hi, wi, c = img.shape
    ry, rx = _cropped_matrices(hi, wi, resize_h, resize_w, method,
                               crop_x, crop_y, crop_w, crop_h)
    hc, wc = ry.shape[0], rx.shape[0]
    kernel = functools.partial(_preprocess_kernel,
                               mean=float(mean), std=float(std))
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"))
    out = pl.pallas_call(
        kernel,
        grid=(n, c),
        in_specs=[
            pl.BlockSpec((1, 1, hi, wi), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((hc, hi), lambda i, j: (0, 0)),
            pl.BlockSpec((wi, wc), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, hc, wc), lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, c, hc, wc), img.dtype),
        interpret=interpret,
        **kwargs,
    )(jnp.transpose(img, (0, 3, 1, 2)), jnp.asarray(ry),
      jnp.asarray(np.ascontiguousarray(rx.T)))
    out = jnp.transpose(out, (0, 2, 3, 1))          # (N, Hc, Wc, C)
    return out[0] if squeeze else out


def fused_resize_crop_normalize_ref(
    img, *, resize_h: int, resize_w: int, method: str = "bilinear",
    crop_x: int, crop_y: int, crop_w: int, crop_h: int,
    mean: float = 0.0, std: float = 1.0,
):
    """Reference path: literally the three composed native-table ops, so
    the fused result matches the per-op pipeline on non-TPU hosts
    exactly (modulo XLA's usual fusion reassociation)."""
    from repro.visual.ops import crop, normalize, resize

    def one(im):
        im = resize(im, width=resize_w, height=resize_h, method=method)
        im = crop(im, x=crop_x, y=crop_y, width=crop_w, height=crop_h)
        return normalize(im, mean=mean, std=std)

    if img.ndim == 4:
        return jax.vmap(one)(img)
    return one(img)
