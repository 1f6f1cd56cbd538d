"""Pure-jnp oracles for every Pallas kernel.

These are the ground truth the kernels are validated against
(tests/test_kernels.py sweeps shapes/dtypes with assert_allclose), and
they are also the execution path on non-TPU backends, so compiled
FLOPs match the kernel math.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


# ===================================================================
# attention
# ===================================================================
def naive_attention(
    q: jax.Array,  # (B, Sq, H, D)
    k: jax.Array,  # (B, Sk, Hkv, D)
    v: jax.Array,  # (B, Sk, Hkv, D)
    *,
    causal: bool = True,
    sm_scale: float | None = None,
    kv_len: jax.Array | None = None,  # (B,) valid cache length for decode
    q_offset: int | jax.Array = 0,    # absolute position of q[0] (causal w/ cache)
) -> jax.Array:
    """Exact softmax attention with GQA head repetition.  O(Sq*Sk) memory."""
    B, Sq, H, D = q.shape
    Hkv = k.shape[2]
    if sm_scale is None:
        sm_scale = D ** -0.5
    if Hkv != H:
        rep = H // Hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32))
    logits *= sm_scale
    Sk = k.shape[1]
    qpos = jnp.arange(Sq) + q_offset  # (Sq,)
    kpos = jnp.arange(Sk)
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    mask = jnp.broadcast_to(mask[None, None], (B, 1, Sq, Sk))
    if kv_len is not None:
        mask &= (kpos[None, None, None, :] < kv_len[:, None, None, None])
    logits = jnp.where(mask, logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", w, v.astype(jnp.float32))
    return out.astype(q.dtype)


def flash_attention_jnp(
    q: jax.Array,  # (B, Sq, H, D)
    k: jax.Array,  # (B, Sk, Hkv, D)
    v: jax.Array,
    *,
    causal: bool = True,
    sm_scale: float | None = None,
    q_block: int = 512,
    kv_block: int = 1024,
    kv_len: jax.Array | None = None,
    q_offset: int | jax.Array = 0,
) -> jax.Array:
    """Online-softmax attention, O(q_block * kv_block) logits memory.

    Same math as the Pallas flash kernel; this is what the CPU backend
    lowers and what long-sequence prefill uses under jit.
    """
    B, Sq, H, D = q.shape
    _, Sk, Hkv, _ = k.shape
    if sm_scale is None:
        sm_scale = D ** -0.5
    group = H // Hkv

    # pad seq dims to block multiples
    pq = (-Sq) % q_block
    pk = (-Sk) % kv_block
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    nq, nk = qp.shape[1] // q_block, kp.shape[1] // kv_block

    qb = qp.reshape(B, nq, q_block, H, D).astype(jnp.float32)
    kb = kp.reshape(B, nk, kv_block, Hkv, D).astype(jnp.float32)
    vb = vp.reshape(B, nk, kv_block, Hkv, D).astype(jnp.float32)

    kpos = jnp.arange(nk * kv_block).reshape(nk, kv_block)
    valid_k = kpos < Sk
    if kv_len is not None:
        valid_k = valid_k[None] & (kpos[None] < kv_len[:, None, None])  # (B,nk,kb)
    else:
        valid_k = jnp.broadcast_to(valid_k[None], (B, nk, kv_block))

    def one_q_block(qi, qblk):  # qblk: (B, q_block, H, D)
        qpos = qi * q_block + jnp.arange(q_block) + q_offset

        def kv_step(carry, inputs):
            m, l, acc = carry
            kblk, vblk, kp_blk, vmask = inputs  # (B,kb,Hkv,D),(B,kb,Hkv,D),(kb,),(B,kb)
            s = jnp.einsum("bqhd,bkhd->bhqk", qblk,
                           jnp.repeat(kblk, group, axis=2)) * sm_scale
            mask = vmask[:, None, None, :]
            if causal:
                mask = mask & (kp_blk[None, None, None, :] <= qpos[None, None, :, None])
            s = jnp.where(mask, s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p, jnp.repeat(vblk, group, axis=2))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, H, q_block), -1e30, jnp.float32)
        l0 = jnp.zeros((B, H, q_block), jnp.float32)
        a0 = jnp.zeros((B, H, q_block, D), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (kb.transpose(1, 0, 2, 3, 4), vb.transpose(1, 0, 2, 3, 4),
             kpos, valid_k.transpose(1, 0, 2)),
        )
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return out.transpose(0, 2, 1, 3)  # (B, q_block, H, D)

    outs = jax.lax.map(lambda args: one_q_block(*args),
                       (jnp.arange(nq), qb.transpose(1, 0, 2, 3, 4)))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(B, nq * q_block, H, D)
    return out[:, :Sq].astype(q.dtype)


def decode_attention_ref(
    q: jax.Array,        # (B, 1, H, D)
    k_cache: jax.Array,  # (B, S, Hkv, D)
    v_cache: jax.Array,
    cache_len: jax.Array,  # scalar or (B,) number of valid positions
    *,
    sm_scale: float | None = None,
) -> jax.Array:
    """GQA-aware single-token attention: q-heads grouped per kv-head so
    the cache is NEVER materialized repeated (a 16x read blow-up for
    kv=4 / H=64 archs — see EXPERIMENTS.md section Perf, decode entry)."""
    B, Sq, H, D = q.shape
    Sk, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = H // Hkv
    scale = sm_scale if sm_scale is not None else D ** -0.5
    cache_len = jnp.asarray(cache_len)
    if cache_len.ndim == 0:
        cache_len = jnp.broadcast_to(cache_len, (B,))
    qg = q.reshape(B, Sq, Hkv, G, D).astype(jnp.float32)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg,
                   k_cache.astype(jnp.float32)) * scale
    mask = jnp.arange(Sk)[None, :] < cache_len[:, None]        # (B, Sk)
    s = jnp.where(mask[:, None, None, None, :], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", w, v_cache.astype(jnp.float32))
    return out.reshape(B, Sq, H, D).astype(q.dtype)


# ===================================================================
# gaussian blur (separable, reflect-101 borders a la OpenCV)
# ===================================================================
def gaussian_kernel_1d(ksize: int, sigma: float) -> np.ndarray:
    if sigma <= 0:  # OpenCV convention
        sigma = 0.3 * ((ksize - 1) * 0.5 - 1) + 0.8
    x = np.arange(ksize, dtype=np.float64) - (ksize - 1) / 2
    w = np.exp(-(x ** 2) / (2 * sigma ** 2))
    return (w / w.sum()).astype(np.float32)


def _reflect101_pad(x: jax.Array, pad: int, axis: int) -> jax.Array:
    cfg = [(0, 0)] * x.ndim
    cfg[axis] = (pad, pad)
    return jnp.pad(x, cfg, mode="reflect")


def gaussian_blur_ref(img: jax.Array, ksize: int, sigma_x: float, sigma_y: float | None = None) -> jax.Array:
    """img: (..., H, W, C) float; separable blur along H then W."""
    if sigma_y is None:
        sigma_y = sigma_x
    kx = jnp.asarray(gaussian_kernel_1d(ksize, sigma_x))
    ky = jnp.asarray(gaussian_kernel_1d(ksize, sigma_y))
    pad = ksize // 2
    dtype = img.dtype
    x = img.astype(jnp.float32)
    # vertical (H axis = -3)
    xp = _reflect101_pad(x, pad, axis=-3)
    out = sum(ky[i] * jax.lax.slice_in_dim(xp, i, i + x.shape[-3], axis=-3)
              for i in range(ksize))
    # horizontal (W axis = -2)
    xp = _reflect101_pad(out, pad, axis=-2)
    out = sum(kx[i] * jax.lax.slice_in_dim(xp, i, i + x.shape[-2], axis=-2)
              for i in range(ksize))
    return out.astype(dtype)


# ===================================================================
# RWKV6 WKV scan
# ===================================================================
def rwkv6_scan_ref(
    r: jax.Array,  # (B, T, H, K)
    k: jax.Array,  # (B, T, H, K)
    v: jax.Array,  # (B, T, H, V)
    w: jax.Array,  # (B, T, H, K)  decay in (0,1), data-dependent
    u: jax.Array,  # (H, K)        bonus for current token
    state: jax.Array | None = None,  # (B, H, K, V)
) -> tuple[jax.Array, jax.Array]:
    """Sequential WKV6: S_t = diag(w_t) S_{t-1} + k_t v_t^T;
    out_t = r_t . (S_{t-1} + diag(u) k_t v_t^T)."""
    B, T, H, K = r.shape
    V = v.shape[-1]
    if state is None:
        state = jnp.zeros((B, H, K, V), jnp.float32)
    rf, kf, vf, wf = (a.astype(jnp.float32) for a in (r, k, v, w))

    def step(s, inp):
        rt, kt, vt, wt = inp  # (B,H,K),(B,H,K),(B,H,V),(B,H,K)
        kv = kt[..., :, None] * vt[..., None, :]           # (B,H,K,V)
        out = jnp.einsum("bhk,bhkv->bhv", rt, s + u[..., :, None] * kv)
        s_new = wt[..., :, None] * s + kv
        return s_new, out

    xs = (rf.transpose(1, 0, 2, 3), kf.transpose(1, 0, 2, 3),
          vf.transpose(1, 0, 2, 3), wf.transpose(1, 0, 2, 3))
    state, outs = jax.lax.scan(step, state, xs)
    return outs.transpose(1, 0, 2, 3).astype(r.dtype), state


def rwkv6_chunked_jnp(
    r, k, v, w, u, state=None, chunk: int = 64,
) -> tuple[jax.Array, jax.Array]:
    """Chunked closed form (log-space cumulative decay); same math as the
    Pallas kernel, O(T/c) sequential steps instead of O(T)."""
    B, T, H, K = r.shape
    V = v.shape[-1]
    pad = (-T) % chunk
    if pad:
        r, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0))) for a in (r, k, v))
        w = jnp.pad(w, ((0, 0), (0, pad), (0, 0), (0, 0)), constant_values=1.0)
    Tp = T + pad
    n = Tp // chunk
    if state is None:
        state = jnp.zeros((B, H, K, V), jnp.float32)

    rb, kb, vb, wb = (a.astype(jnp.float32).reshape(B, n, chunk, H, -1).transpose(1, 0, 3, 2, 4)
                      for a in (r, k, v, w))  # (n, B, H, c, K/V)

    def chunk_step(s, inp):
        rc, kc, vc, wc = inp  # (B,H,c,K) etc
        lw = jnp.cumsum(jnp.log(jnp.maximum(wc, 1e-30)), axis=2)  # (B,H,c,K)
        lw_prev = lw - jnp.log(jnp.maximum(wc, 1e-30))            # sum over s<t
        # inter-chunk: r_t decayed against incoming state
        q_in = rc * jnp.exp(lw_prev)                               # (B,H,c,K)
        y_inter = jnp.einsum("bhck,bhkv->bhcv", q_in, s)
        # intra-chunk pairwise (per-channel decay -> einsum over K)
        diff = lw_prev[:, :, :, None, :] - lw[:, :, None, :, :]    # (B,H,c_t,c_s,K)
        tri = jnp.tril(jnp.ones((chunk, chunk), bool), -1)[None, None, :, :, None]
        dec = jnp.exp(jnp.where(tri, diff, -1e30))
        att = jnp.einsum("bhck,bhcsk,bhsk->bhcs", rc, dec, kc)
        y_intra = jnp.einsum("bhcs,bhsv->bhcv", att, vc)
        # current-token bonus
        y_cur = jnp.einsum("bhck,bhck->bhc", rc * u[None, :, None, :], kc)[..., None] * vc
        # state update
        lw_last = lw[:, :, -1:, :]                                 # (B,H,1,K)
        s_new = jnp.exp(lw_last[:, :, 0, :, None]) * s + jnp.einsum(
            "bhck,bhcv->bhkv", kc * jnp.exp(lw_last - lw), vc)
        return s_new, y_inter + y_intra + y_cur

    state, ys = jax.lax.scan(chunk_step, state, (rb, kb, vb, wb))
    out = ys.transpose(1, 0, 3, 2, 4).reshape(B, Tp, H, V)[:, :T]
    return out.astype(r.dtype), state


# ===================================================================
# Mamba2 SSD
# ===================================================================
def mamba2_ssd_ref(
    x: jax.Array,    # (B, T, H, P)
    dt: jax.Array,   # (B, T, H)      softplus-ed already, > 0
    A: jax.Array,    # (H,)           negative
    Bm: jax.Array,   # (B, T, G, N)
    Cm: jax.Array,   # (B, T, G, N)
    D: jax.Array | None = None,  # (H,)
    state: jax.Array | None = None,  # (B, H, P, N)
) -> tuple[jax.Array, jax.Array]:
    """Sequential SSD recurrence:
    h_t = exp(A dt_t) h_{t-1} + dt_t * x_t B_t^T ; y_t = h_t C_t + D x_t."""
    B_, T, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = H // G
    if state is None:
        state = jnp.zeros((B_, H, P, N), jnp.float32)
    xf, dtf = x.astype(jnp.float32), dt.astype(jnp.float32)
    Bf = jnp.repeat(Bm.astype(jnp.float32), rep, axis=2)  # (B,T,H,N)
    Cf = jnp.repeat(Cm.astype(jnp.float32), rep, axis=2)

    def step(h, inp):
        xt, dtt, bt, ct = inp  # (B,H,P),(B,H),(B,H,N),(B,H,N)
        decay = jnp.exp(A[None] * dtt)[..., None, None]          # (B,H,1,1)
        h_new = decay * h + (dtt[..., None, None] * xt[..., :, None] * bt[..., None, :])
        y = jnp.einsum("bhpn,bhn->bhp", h_new, ct)
        return h_new, y

    xs = (xf.transpose(1, 0, 2, 3), dtf.transpose(1, 0, 2),
          Bf.transpose(1, 0, 2, 3), Cf.transpose(1, 0, 2, 3))
    state, ys = jax.lax.scan(step, state, xs)
    y = ys.transpose(1, 0, 2, 3)
    if D is not None:
        y = y + D[None, None, :, None].astype(jnp.float32) * xf
    return y.astype(x.dtype), state


def mamba2_ssd_chunked_jnp(
    x, dt, A, Bm, Cm, D=None, state=None, chunk: int = 128,
) -> tuple[jax.Array, jax.Array]:
    """Chunked SSD (the Mamba2 paper's blocked algorithm), pure jnp.
    Matches mamba2_ssd_ref; the Pallas kernel mirrors this blocking."""
    B_, T, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = H // G
    pad = (-T) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Tp = T + pad
    n = Tp // chunk
    if state is None:
        state = jnp.zeros((B_, H, P, N), jnp.float32)

    xf = x.astype(jnp.float32).reshape(B_, n, chunk, H, P).transpose(1, 0, 3, 2, 4)   # (n,B,H,c,P)
    dtf = dt.astype(jnp.float32).reshape(B_, n, chunk, H).transpose(1, 0, 3, 2)       # (n,B,H,c)
    Bf = jnp.repeat(Bm.astype(jnp.float32), rep, axis=2).reshape(B_, n, chunk, H, N).transpose(1, 0, 3, 2, 4)
    Cf = jnp.repeat(Cm.astype(jnp.float32), rep, axis=2).reshape(B_, n, chunk, H, N).transpose(1, 0, 3, 2, 4)

    def chunk_step(h, inp):
        xc, dtc, bc, cc = inp                      # (B,H,c,*)
        la = jnp.cumsum(A[None, :, None] * dtc, axis=2)          # (B,H,c) log decay cumulative
        # intra-chunk: y_t += sum_{s<=t} exp(la_t - la_s) dt_s (C_t.B_s) x_s
        diff = la[:, :, :, None] - la[:, :, None, :]             # (B,H,c,c)
        tri = jnp.tril(jnp.ones((chunk, chunk), bool))[None, None]
        L = jnp.exp(jnp.where(tri, diff, -1e30))
        cb = jnp.einsum("bhtn,bhsn->bhts", cc, bc)
        att = cb * L * dtc[:, :, None, :]
        y = jnp.einsum("bhts,bhsp->bhtp", att, xc)
        # inter-chunk: y_t += exp(la_t) C_t . h_in
        y = y + jnp.einsum("bhtn,bhpn->bhtp", cc * jnp.exp(la)[..., None], h)
        # state update: h_out = exp(la_last) h_in + sum_s exp(la_last - la_s) dt_s x_s B_s^T
        la_last = la[:, :, -1]
        w = jnp.exp(la_last[:, :, None] - la) * dtc              # (B,H,c)
        h_new = jnp.exp(la_last)[..., None, None] * h + jnp.einsum(
            "bhcp,bhcn->bhpn", xc * w[..., None], bc)
        return h_new, y

    state, ys = jax.lax.scan(chunk_step, state, (xf, dtf, Bf, Cf))
    y = ys.transpose(1, 0, 3, 2, 4).reshape(B_, Tp, H, P)[:, :T]
    if D is not None:
        y = y + D[None, None, :, None].astype(jnp.float32) * x.astype(jnp.float32)[:, :T]
    return y.astype(x.dtype), state
