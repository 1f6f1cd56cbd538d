"""Per-kernel validation: Pallas (interpret=True) and chunked-jnp paths
against the pure-jnp oracles, swept over shapes/dtypes, plus custom-VJP
gradient checks."""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.flash_vjp import flash_attention as flash_vjp
from repro.kernels.gaussian_blur import gaussian_blur_pallas
from repro.kernels.mamba2_ssd import mamba2_ssd_pallas
from repro.kernels.rwkv6_scan import rwkv6_scan_pallas

KEY = jax.random.PRNGKey(0)


def _rand(key, shape, dtype=jnp.float32, scale=1.0):
    return (jax.random.normal(key, shape) * scale).astype(dtype)


# ------------------------------------------------------------ attention
ATTN_CASES = [
    # B, Sq, Sk, H, Hkv, D, causal
    (2, 128, 128, 4, 2, 32, True),
    (1, 96, 96, 4, 4, 16, True),
    (2, 64, 192, 6, 2, 32, False),
    (1, 100, 100, 2, 1, 64, True),   # non-multiple of block
]


@pytest.mark.parametrize("case", ATTN_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_pallas_matches_naive(case, dtype):
    B, Sq, Sk, H, Hkv, D, causal = case
    ks = jax.random.split(jax.random.fold_in(KEY, hash(case) % 2**31), 3)
    q = _rand(ks[0], (B, Sq, H, D), dtype)
    k = _rand(ks[1], (B, Sk, Hkv, D), dtype)
    v = _rand(ks[2], (B, Sk, Hkv, D), dtype)
    want = ref.naive_attention(q, k, v, causal=causal)
    got = flash_attention_pallas(q, k, v, causal=causal, block_q=32,
                                 block_k=64, interpret=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("case", ATTN_CASES[:3])
def test_flash_vjp_forward_and_grads(case):
    B, Sq, Sk, H, Hkv, D, causal = case
    ks = jax.random.split(jax.random.fold_in(KEY, 7 + hash(case) % 2**31), 4)
    q, k, v = (_rand(ks[i], s) for i, s in enumerate(
        [(B, Sq, H, D), (B, Sk, Hkv, D), (B, Sk, Hkv, D)]))
    dout = _rand(ks[3], (B, Sq, H, D))

    def loss_ref(q, k, v):
        return jnp.sum(ref.naive_attention(q, k, v, causal=causal) * dout)

    def loss_fa(q, k, v):
        return jnp.sum(flash_vjp(q, k, v, 0, causal, None, 32, 64) * dout)

    np.testing.assert_allclose(loss_fa(q, k, v), loss_ref(q, k, v), rtol=1e-4)
    g_ref = jax.grad(loss_ref, (0, 1, 2))(q, k, v)
    g_fa = jax.grad(loss_fa, (0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_fa):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=2e-4)


def test_flash_vjp_with_cache_offset():
    """Prefill-into-cache semantics: q at offset, zero tail never attended."""
    B, S, H, D, idx, cache = 1, 24, 2, 16, 16, 64
    ks = jax.random.split(KEY, 3)
    q = _rand(ks[0], (B, S, H, D))
    kfull = jnp.zeros((B, cache, H, D)).at[:, idx:idx + S].set(
        _rand(ks[1], (B, S, H, D)))
    vfull = jnp.zeros((B, cache, H, D)).at[:, idx:idx + S].set(
        _rand(ks[2], (B, S, H, D)))
    got = flash_vjp(q, kfull, vfull, jnp.int32(idx), True, None, 8, 16)
    want = ref.naive_attention(q, kfull[:, : idx + S], vfull[:, : idx + S],
                               causal=True, q_offset=idx)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


# ----------------------------------------------------------------- blur
@pytest.mark.parametrize("shape", [(2, 40, 32, 3), (1, 17, 23, 3)])
@pytest.mark.parametrize("ksize,sigma", [(3, 1.0), (5, 0.0), (7, 2.5)])
def test_gaussian_blur_pallas(shape, ksize, sigma):
    img = jax.random.uniform(jax.random.fold_in(KEY, ksize), shape)
    want = ref.gaussian_blur_ref(img, ksize, sigma)
    got = gaussian_blur_pallas(img, ksize, sigma, block_rows=8, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_gaussian_blur_pallas_at_lfw_shape():
    """The channel-planar kernel at the LFW face shape, with the default
    (128-row) bands: two bands per plane, the second one ragged."""
    img = jax.random.uniform(KEY, (2, 250, 250, 3))
    want = ref.gaussian_blur_ref(img, 5, 1.5)
    got = gaussian_blur_pallas(img, 5, 1.5, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_gaussian_blur_wrapper_reaches_kernel_in_fresh_process():
    """``ops.gaussian_blur`` imports the kernel module by name; a fresh
    interpreter (no test module has imported the submodule first) must
    find the module, not a same-named function re-exported by the
    package."""
    import subprocess
    import sys
    code = (
        "import numpy as np\n"
        "from repro.kernels import ops, ref\n"
        "img = np.random.default_rng(0).uniform(0, 1, (1, 16, 16, 3))"
        ".astype(np.float32)\n"
        "got = ops.gaussian_blur(img, 5, 1.5, impl='pallas_interpret')\n"
        "want = ref.gaussian_blur_ref(img, 5, 1.5)\n"
        "assert np.allclose(got, want, atol=1e-5)\n")
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [src] + [p for p in [os.environ.get("PYTHONPATH")] if p]))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]


@pytest.mark.parametrize("placed", ["default", "jit"])
def test_gaussian_blur_auto_follows_lowering_platform(placed):
    """impl="auto" picks by the platform the work is lowered for: on a
    CPU device that is the jnp reference."""
    from repro.kernels import ops
    img = jax.random.uniform(KEY, (2, 24, 20, 3))
    fn = functools.partial(ops.gaussian_blur, ksize=5, sigma_x=1.5)
    got = jax.jit(fn)(img) if placed == "jit" else fn(img)
    want = ref.gaussian_blur_ref(img, 5, 1.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


def test_gaussian_blur_preserves_mean():
    img = jax.random.uniform(KEY, (1, 32, 32, 3))
    out = ref.gaussian_blur_ref(img, 5, 1.5)
    assert abs(float(out.mean()) - float(img.mean())) < 1e-2


# ----------------------------------------------------------------- rwkv
@pytest.mark.parametrize("B,T,H,K,chunk", [(2, 96, 3, 16, 32), (1, 50, 2, 8, 16)])
def test_rwkv6_chunked_and_pallas(B, T, H, K, chunk):
    ks = jax.random.split(jax.random.fold_in(KEY, T), 6)
    r = _rand(ks[0], (B, T, H, K), scale=0.5)
    k = _rand(ks[1], (B, T, H, K), scale=0.5)
    v = _rand(ks[2], (B, T, H, K))
    w = jax.nn.sigmoid(_rand(ks[3], (B, T, H, K))) * 0.5 + 0.45
    u = _rand(ks[4], (H, K), scale=0.1)
    s0 = _rand(ks[5], (B, H, K, K), scale=0.1)
    o_ref, s_ref = ref.rwkv6_scan_ref(r, k, v, w, u, s0)
    o_ch, s_ch = ref.rwkv6_chunked_jnp(r, k, v, w, u, s0, chunk=chunk)
    o_pl, s_pl = rwkv6_scan_pallas(r, k, v, w, u, s0, chunk=chunk, interpret=True)
    np.testing.assert_allclose(np.asarray(o_ch), np.asarray(o_ref), atol=2e-4)
    np.testing.assert_allclose(np.asarray(o_pl), np.asarray(o_ref), atol=2e-4)
    np.testing.assert_allclose(np.asarray(s_pl), np.asarray(s_ref), atol=2e-4)


def test_rwkv6_state_continuity():
    """Scanning [a;b] equals scanning a then b from a's final state."""
    B, T, H, K = 1, 64, 2, 8
    ks = jax.random.split(KEY, 5)
    r = _rand(ks[0], (B, T, H, K), scale=0.5)
    k = _rand(ks[1], (B, T, H, K), scale=0.5)
    v = _rand(ks[2], (B, T, H, K))
    w = jax.nn.sigmoid(_rand(ks[3], (B, T, H, K))) * 0.5 + 0.45
    u = _rand(ks[4], (H, K), scale=0.1)
    o_full, s_full = ref.rwkv6_scan_ref(r, k, v, w, u)
    h = T // 2
    o1, s1 = ref.rwkv6_scan_ref(r[:, :h], k[:, :h], v[:, :h], w[:, :h], u)
    o2, s2 = ref.rwkv6_scan_ref(r[:, h:], k[:, h:], v[:, h:], w[:, h:], u, s1)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([o1, o2], 1)),
                               np.asarray(o_full), atol=1e-4)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full), atol=1e-4)


# ---------------------------------------------------------------- mamba
@pytest.mark.parametrize("B,T,H,P,G,N,chunk", [(2, 100, 4, 16, 2, 8, 32),
                                               (1, 64, 2, 8, 1, 16, 16)])
def test_mamba2_chunked_and_pallas(B, T, H, P, G, N, chunk):
    ks = jax.random.split(jax.random.fold_in(KEY, T + N), 7)
    x = _rand(ks[0], (B, T, H, P))
    dt = jax.nn.softplus(_rand(ks[1], (B, T, H))) * 0.5
    A = -jnp.exp(_rand(ks[2], (H,), scale=0.3))
    Bm = _rand(ks[3], (B, T, G, N), scale=0.5)
    Cm = _rand(ks[4], (B, T, G, N), scale=0.5)
    D = jnp.abs(_rand(ks[5], (H,), scale=0.1))
    h0 = _rand(ks[6], (B, H, P, N), scale=0.1)
    y_ref, h_ref = ref.mamba2_ssd_ref(x, dt, A, Bm, Cm, D, h0)
    y_ch, h_ch = ref.mamba2_ssd_chunked_jnp(x, dt, A, Bm, Cm, D, h0, chunk=chunk)
    y_pl, h_pl = mamba2_ssd_pallas(x, dt, A, Bm, Cm, D, h0, chunk=chunk,
                                   interpret=True)
    np.testing.assert_allclose(np.asarray(y_ch), np.asarray(y_ref), atol=2e-4)
    np.testing.assert_allclose(np.asarray(y_pl), np.asarray(y_ref), atol=2e-4)
    np.testing.assert_allclose(np.asarray(h_pl), np.asarray(h_ref), atol=2e-4)


def test_mamba2_decode_step_matches_scan():
    """One-token recurrence (serving path) == last step of the full scan."""
    from repro.configs import get_arch
    from repro.distributed.sharding import REPLICATED
    from repro.models.mamba2 import apply_mamba2, init_mamba2
    from repro.models.common import KeyGen

    cfg = get_arch("zamba2-2.7b", reduced=True)
    p = init_mamba2(KeyGen(KEY), cfg, jnp.float32)
    x = _rand(jax.random.fold_in(KEY, 1), (1, 8, cfg.d_model), scale=0.3)
    W = cfg.mamba_conv_width
    from repro.models.mamba2 import conv_dim
    cd = conv_dim(cfg)
    conv0 = jnp.zeros((1, W - 1, cd))
    ssm0 = jnp.zeros((1, cfg.mamba_nheads, cfg.mamba_head_dim, cfg.ssm_state))
    y_full, conv_f, ssm_f = apply_mamba2(p, x, cfg=cfg, sh=REPLICATED,
                                         conv_state=conv0, ssm_state=ssm0)
    # step through one token at a time
    conv, ssm = conv0, ssm0
    outs = []
    for t in range(8):
        y, conv, ssm = apply_mamba2(p, x[:, t:t + 1], cfg=cfg, sh=REPLICATED,
                                    conv_state=conv, ssm_state=ssm)
        outs.append(y)
    y_step = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(y_step), np.asarray(y_full), atol=2e-4)
    np.testing.assert_allclose(np.asarray(ssm), np.asarray(ssm_f), atol=2e-4)


# --------------------------------------------------- bf16 kernel sweeps
@pytest.mark.parametrize("dtype", [jnp.bfloat16])
def test_rwkv6_pallas_bf16(dtype):
    B, T, H, K = 1, 64, 2, 16
    ks = jax.random.split(KEY, 6)
    r = _rand(ks[0], (B, T, H, K), dtype, 0.5)
    k = _rand(ks[1], (B, T, H, K), dtype, 0.5)
    v = _rand(ks[2], (B, T, H, K), dtype)
    w = (jax.nn.sigmoid(_rand(ks[3], (B, T, H, K))) * 0.5 + 0.45).astype(dtype)
    u = _rand(ks[4], (H, K), jnp.float32, 0.1)
    o_ref, s_ref = ref.rwkv6_scan_ref(r, k, v, w, u)
    o_pl, s_pl = rwkv6_scan_pallas(r, k, v, w, u, chunk=32, interpret=True)
    np.testing.assert_allclose(np.asarray(o_pl, np.float32),
                               np.asarray(o_ref, np.float32), atol=5e-2)
    np.testing.assert_allclose(np.asarray(s_pl), np.asarray(s_ref), atol=5e-2)


@pytest.mark.parametrize("dtype", [jnp.bfloat16])
def test_mamba2_pallas_bf16(dtype):
    B, T, H, P, G, N = 1, 64, 2, 16, 1, 8
    ks = jax.random.split(jax.random.fold_in(KEY, 321), 6)
    x = _rand(ks[0], (B, T, H, P), dtype)
    dt = jax.nn.softplus(_rand(ks[1], (B, T, H))) * 0.5
    A = -jnp.exp(_rand(ks[2], (H,), jnp.float32, 0.3))
    Bm = _rand(ks[3], (B, T, G, N), dtype, 0.5)
    Cm = _rand(ks[4], (B, T, G, N), dtype, 0.5)
    y_ref, h_ref = ref.mamba2_ssd_ref(x, dt, A, Bm, Cm)
    y_pl, h_pl = mamba2_ssd_pallas(x, dt, A, Bm, Cm, chunk=32, interpret=True)
    np.testing.assert_allclose(np.asarray(y_pl, np.float32),
                               np.asarray(y_ref, np.float32), atol=5e-2)
    np.testing.assert_allclose(np.asarray(h_pl), np.asarray(h_ref), atol=5e-2)


def test_gqa_decode_attention_matches_naive():
    """The no-repeat grouped decode path (EXPERIMENTS section Perf, item 9)."""
    B, S, H, Hkv, D = 2, 96, 8, 2, 16
    ks = jax.random.split(jax.random.fold_in(KEY, 99), 3)
    q = _rand(ks[0], (B, 1, H, D))
    kc = _rand(ks[1], (B, S, Hkv, D))
    vc = _rand(ks[2], (B, S, Hkv, D))
    lens = jnp.asarray([40, 96])
    want = ref.naive_attention(q, kc, vc, causal=False, kv_len=lens)
    got = ref.decode_attention_ref(q, kc, vc, lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
