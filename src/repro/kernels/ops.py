"""Public wrappers around the Pallas kernels.

``impl`` selection:
- "auto"             -> Pallas where the computation is lowered for a
                        TPU, the jnp path elsewhere.  The choice is made
                        per lowering platform (``lax.platform_dependent``),
                        i.e. by the device the work is placed on, not by
                        the process default backend: a computation placed
                        on the CPU of a TPU host never compiles a Pallas
                        call, and one placed on a TPU always runs the
                        kernel.
- "pallas"           -> compiled Pallas kernel (TPU).
- "pallas_interpret" -> Pallas kernel body interpreted on CPU (used by
                        tests to validate kernels against the oracles).
- "naive"/"chunked"  -> explicit jnp paths.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref


def _by_platform(tpu_fn, other_fn, *args):
    """``tpu_fn(*args)`` when lowered for a TPU, ``other_fn(*args)``
    otherwise; both are traced, only the chosen one is compiled."""
    return jax.lax.platform_dependent(*args, tpu=tpu_fn, default=other_fn)


# ----------------------------------------------------------------- attn
def flash_attention(q, k, v, *, causal=True, sm_scale=None, impl="auto",
                    q_block=512, kv_block=1024, q_offset=0):
    """(B,Sq,H,D) x (B,Sk,Hkv,D) -> (B,Sq,H,D); GQA via Hkv | H."""
    if impl == "naive":
        return ref.naive_attention(q, k, v, causal=causal, sm_scale=sm_scale,
                                   q_offset=q_offset)
    chunked = functools.partial(ref.flash_attention_jnp, causal=causal,
                                sm_scale=sm_scale, q_block=q_block,
                                kv_block=kv_block, q_offset=q_offset)
    if impl == "chunked":
        return chunked(q, k, v)
    from repro.kernels import flash_attention as fa
    pallas = functools.partial(fa.flash_attention_pallas, causal=causal,
                               sm_scale=sm_scale, block_q=q_block,
                               block_k=kv_block,
                               interpret=(impl == "pallas_interpret"))
    if impl == "auto":
        return _by_platform(pallas, chunked, q, k, v)
    return pallas(q, k, v)


def decode_attention(q, k_cache, v_cache, cache_len, *, sm_scale=None, impl="auto"):
    """Single-token attention against a cache: q (B,1,H,D)."""
    return ref.decode_attention_ref(q, k_cache, v_cache, cache_len, sm_scale=sm_scale)


# ----------------------------------------------------------------- blur
def gaussian_blur(img, ksize: int, sigma_x: float, sigma_y: float | None = None,
                  *, impl="auto"):
    """img (H, W, C) or (N, H, W, C); OpenCV-compatible separable
    Gaussian blur."""
    from repro.kernels import gaussian_blur as gb
    pallas = functools.partial(gb.gaussian_blur_pallas, ksize=ksize,
                               sigma_x=sigma_x, sigma_y=sigma_y,
                               interpret=(impl == "pallas_interpret"))
    reference = functools.partial(ref.gaussian_blur_ref, ksize=ksize,
                                  sigma_x=sigma_x, sigma_y=sigma_y)
    if impl == "auto":
        return _by_platform(pallas, reference, img)
    if impl == "ref":
        return reference(img)
    return pallas(img)


# ----------------------------------------------------------- preprocess
def fused_preprocess(img, *, resize_h: int, resize_w: int,
                     method: str = "bilinear",
                     crop_x: int, crop_y: int, crop_w: int, crop_h: int,
                     mean: float = 0.0, std: float = 1.0, impl="auto"):
    """img (..., H, W, C): fused resize→crop→normalize in one launch
    (Pallas matmul formulation on TPU, composed reference ops
    elsewhere).  See repro.kernels.preprocess for the folding trick."""
    from repro.kernels import preprocess as pp
    kw = dict(resize_h=resize_h, resize_w=resize_w, method=method,
              crop_x=crop_x, crop_y=crop_y, crop_w=crop_w, crop_h=crop_h,
              mean=mean, std=std)
    pallas = functools.partial(pp.fused_resize_crop_normalize_pallas,
                               interpret=(impl == "pallas_interpret"), **kw)
    reference = functools.partial(pp.fused_resize_crop_normalize_ref, **kw)
    if impl == "auto":
        return _by_platform(pallas, reference, img)
    if impl == "ref":
        return reference(img)
    return pallas(img)


# ----------------------------------------------------------------- rwkv
def rwkv6_scan(r, k, v, w, u, state=None, *, impl="auto", chunk=64):
    if impl == "ref":
        return ref.rwkv6_scan_ref(r, k, v, w, u, state)
    chunked = functools.partial(ref.rwkv6_chunked_jnp, chunk=chunk)
    if impl == "chunked":
        return chunked(r, k, v, w, u, state)
    from repro.kernels import rwkv6_scan as rk
    pallas = functools.partial(rk.rwkv6_scan_pallas, chunk=chunk,
                               interpret=(impl == "pallas_interpret"))
    if impl == "auto":
        return _by_platform(pallas, chunked, r, k, v, w, u, state)
    return pallas(r, k, v, w, u, state)


# ---------------------------------------------------------------- mamba
def mamba2_ssd(x, dt, A, Bm, Cm, D=None, state=None, *, impl="auto", chunk=128):
    if impl == "ref":
        return ref.mamba2_ssd_ref(x, dt, A, Bm, Cm, D, state)
    chunked = functools.partial(ref.mamba2_ssd_chunked_jnp, chunk=chunk)
    if impl == "chunked":
        return chunked(x, dt, A, Bm, Cm, D, state)
    from repro.kernels import mamba2_ssd as mk
    pallas = functools.partial(mk.mamba2_ssd_pallas, chunk=chunk,
                               interpret=(impl == "pallas_interpret"))
    if impl == "auto":
        return _by_platform(pallas, chunked, x, dt, A, Bm, Cm, D, state)
    return pallas(x, dt, A, Bm, Cm, D, state)
