"""Separable Gaussian blur Pallas TPU kernel.

The wrapper moves channels off the lane axis — (N, H, W, C) becomes
channel-planar (N, C, H, W), so W sits on the 128-wide lanes and H on
the sublanes (channel-last put the trailing 3 on the lanes and padded
every vector 128/3-fold, which asked for more VMEM than the chip has at
250x250).  It then reflect-101 pads each plane by ``ksize//2`` on both
spatial axes (matching OpenCV's default border), and the kernel computes
a *valid* separable convolution over row bands of one plane:

  grid = (batch, channel, H/block_rows); each step sees its own band plus
  the next band (two refs on the same padded input, index_maps i and
  i+1) so the vertical taps never leave VMEM.  Taps are a static unroll
  of shift-multiply-adds — pure VPU work with no gather, which is the
  TPU-native way to express a small stencil.

VMEM at 1080p, block_rows=128, ksize<=31: 2 refs x 2 buffers x 128 x
(1920+30) x 4B ~= 4 MB, plus the double-buffered output band.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.ref import gaussian_kernel_1d, _reflect101_pad


def _blur_kernel(cur_ref, nxt_ref, o_ref, *, ky, kx, block_rows):
    band = jnp.concatenate([cur_ref[0, 0], nxt_ref[0, 0]],
                           axis=0).astype(jnp.float32)
    # vertical pass: rows [0, block_rows) of output need rows [l, l+K) of band
    K = len(ky)
    tmp = ky[0] * band[0:block_rows]
    for t in range(1, K):
        tmp = tmp + ky[t] * band[t:t + block_rows]
    # horizontal pass (width padded by K-1): out cols [0, W)
    W = o_ref.shape[3]
    out = kx[0] * tmp[:, 0:W]
    for t in range(1, K):
        out = out + kx[t] * tmp[:, t:t + W]
    o_ref[0, 0] = out.astype(o_ref.dtype)


def gaussian_blur_pallas(
    img: jax.Array,  # (N, H, W, C) or (H, W, C)
    ksize: int,
    sigma_x: float,
    sigma_y: float | None = None,
    *,
    block_rows: int = 128,
    interpret: bool = False,
) -> jax.Array:
    if sigma_y is None:
        sigma_y = sigma_x
    squeeze = img.ndim == 3
    if squeeze:
        img = img[None]
    N, H, W, C = img.shape
    pad = ksize // 2
    # a band must hold the 2*pad halo rows and be a whole number of
    # (8, 128) f32 tiles on the sublane axis
    block_rows = max(min(block_rows, H), 2 * pad, 1)
    block_rows = -(-block_rows // 8) * 8

    ky = tuple(float(x) for x in gaussian_kernel_1d(ksize, sigma_y))
    kx = tuple(float(x) for x in gaussian_kernel_1d(ksize, sigma_x))

    x = jnp.transpose(img, (0, 3, 1, 2))            # (N, C, H, W)
    x = _reflect101_pad(_reflect101_pad(x, pad, axis=-2), pad, axis=-1)
    nb = -(-H // block_rows)
    # pad rows up to nb bands plus one extra band for `next`
    x = jnp.pad(x, ((0, 0), (0, 0), (0, (nb + 1) * block_rows - x.shape[2]),
                    (0, 0)))
    wp = W + 2 * pad

    kernel = functools.partial(_blur_kernel, ky=ky, kx=kx,
                               block_rows=block_rows)
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"))
    out = pl.pallas_call(
        kernel,
        grid=(N, C, nb),
        in_specs=[
            pl.BlockSpec((1, 1, block_rows, wp),
                         lambda n, c, i: (n, c, i, 0)),
            pl.BlockSpec((1, 1, block_rows, wp),
                         lambda n, c, i: (n, c, i + 1, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_rows, W),
                               lambda n, c, i: (n, c, i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, C, nb * block_rows, W), img.dtype),
        interpret=interpret,
        **kwargs,
    )(x, x)
    out = jnp.transpose(out[:, :, :H], (0, 2, 3, 1))
    return out[0] if squeeze else out
