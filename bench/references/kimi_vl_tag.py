"""Plain reference of ``kimi_vl_tag``: Kimi-VL-A3B-Instruct's language
model scoring candidate answers about a face.  Imports nothing of
``repro``.

Per image: zero-pad at the bottom and right to a multiple of
``patch_size * patch_merge``, cut into ``patch_size`` patches, join each
``patch_merge`` x ``patch_merge`` block of neighbouring patches into one
token (features ordered merge row, merge column, pixel row, pixel
column, channel), project by ``projector``; then the prompt's token
embeddings.  Each candidate answer (``candidates``, C x T ids) is scored
teacher-forced: the output (C, T) is the log-probability of its t-th
token under the full-vocabulary log-softmax after the image, the prompt
and its first t tokens.

The model, from its published description (DeepSeek-V3 as Kimi-VL's
language model configures it), over each candidate's whole sequence
(image, prompt, candidate) with no cache:

- a layer is ``h += MLA(rms(h)); h += MLP(rms(h))``, RMSNorm with the
  published epsilon;
- MLA without q-LoRA, in its expanded form: ``q = x Wq`` per head split
  into ``qk_nope_head_dim`` and ``qk_rope_head_dim``; ``x Wkv_a`` gives
  the ``kv_lora_rank`` latent, RMS-normed, and one rope key for every
  head; ``latent Wkv_b`` gives each head's key (nope part) and value;
  scores ``(q_nope k_nope + q_rope k_rope) / sqrt(nope + rope)``, causal
  softmax, ``Wo``; RoPE rotates interleaved pairs ``(x[2i], x[2i+1])`` by
  ``pos * theta**(-2i/d)``;
- layers below ``first_k_dense_replace``: a SwiGLU MLP of
  ``intermediate_size``;
- the others: sigmoid scores ``s = sigmoid(x Wg)`` in float32, the top
  ``num_experts_per_tok`` of ``s + e_score_correction_bias`` chosen, each
  weighed by its ``s`` over the chosen ones' sum, times
  ``routed_scaling_factor``; every expert this chip holds
  (``experts_held``: experts 0 .. experts_held-1) applied to every token
  and masked by that routing; experts held elsewhere add nothing, as in
  the deployment's share; plus the shared experts, one SwiGLU MLP of
  ``n_shared_experts * moe_intermediate_size``;
- final RMSNorm and the untied head.

The prompt's positions do not depend on the candidate, so they are
computed once per image and every candidate's positions attend to them:
the same arithmetic as eight separate forwards, a sixth of the work.

Weights are drawn from the seed: each tensor ``offset + scale *
normal(key, shape)`` (``jax.random`` in float32, threefry key from
``(seed, layer, tensor)``) rounded to bfloat16 values, matrices laid out
(in, out).  ``weights`` returns only the keys, shapes and scales; the
reference draws each layer when it needs it, and the program once.
"""
from __future__ import annotations

import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np

DISCRETE = False
ON_DEVICE = True
BLOCK = 16          # images per call
RESIDUAL = 8.0      # scale of the embeddings the residual stream starts from


# ------------------------------------------------------------ weights
def _shapes(spec: dict) -> dict:
    d, h = spec["hidden_size"], spec["num_attention_heads"]
    r, dr = spec["kv_lora_rank"], spec["qk_rope_head_dim"]
    dn, dv = spec["qk_nope_head_dim"], spec["v_head_dim"]
    f, fe = spec["intermediate_size"], spec["moe_intermediate_size"]
    fs = spec["n_shared_experts"] * fe
    pf = spec["patch_merge"] ** 2 * spec["patch_size"] ** 2 * 3
    out = {(0, "embed_tokens"): (spec["vocab_size"], d),
           (0, "lm_head"): (d, spec["vocab_size"]), (0, "norm"): (d,),
           (0, "projector"): (pf, d)}
    for i in range(spec["num_hidden_layers"]):
        names = {"input_layernorm": (d,), "post_attention_layernorm": (d,),
                 "self_attn.q_proj": (d, h * (dn + dr)),
                 "self_attn.kv_a_proj_with_mqa": (d, r + dr),
                 "self_attn.kv_a_layernorm": (r,),
                 "self_attn.kv_b_proj": (r, h * (dn + dv)),
                 "self_attn.o_proj": (h * dv, d)}
        if i < spec["first_k_dense_replace"]:
            names.update({"mlp.gate_proj": (d, f), "mlp.up_proj": (d, f),
                          "mlp.down_proj": (f, d)})
        else:
            names.update({"mlp.gate": (d, spec["n_routed_experts"]),
                          "mlp.e_score_correction_bias":
                              (spec["n_routed_experts"],),
                          "mlp.shared_experts.gate_proj": (d, fs),
                          "mlp.shared_experts.up_proj": (d, fs),
                          "mlp.shared_experts.down_proj": (fs, d)})
            for e in range(spec["experts_held"]):
                names.update({f"mlp.experts.{e}.gate_proj": (d, fe),
                              f"mlp.experts.{e}.up_proj": (d, fe),
                              f"mlp.experts.{e}.down_proj": (fe, d)})
        out.update({(i + 1, f"layers.{i}.{n}"): s for n, s in names.items()})
    return out


def weights(spec: dict, seed: int) -> dict:
    """Name -> {"key" (uint32[2]), "shape", "scale", "offset"} of every
    tensor: matrices at fan-in scale, the output projections (``o_proj``,
    ``down_proj``) also over sqrt(2 * layers), norms 1 + 0.1 N, the
    correction bias 0.05 N.  The token embedding (N(0, 8^2)) and the
    image projector (8x fan-in scale) make the residual stream several
    times what the layers add to it, as the stream outweighs a layer's
    update in trained decoders.  At unit scale a routing near-tie that
    bfloat16 rounding tips the other way moves a log-probability by up
    to 0.1-0.2, as far as computing the whole model in bfloat16 does.  At
    the published widths on a TPU v5e the program read 0.024-0.028 at 4x
    against 0.080 for this reference in bfloat16, and 0.010-0.015 at 8x
    against 0.084-0.091."""
    depth = (2 * spec["num_hidden_layers"]) ** -0.5
    out = {}
    for (layer, name), shape in _shapes(spec).items():
        key = np.random.SeedSequence(
            [seed, layer, zlib.crc32(name.encode())]).generate_state(2)
        if name.endswith("e_score_correction_bias"):
            scale, offset = 0.05, 0.0
        elif len(shape) == 1:
            scale, offset = 0.1, 1.0
        elif name == "embed_tokens":
            scale, offset = RESIDUAL, 0.0
        else:
            scale = shape[0] ** -0.5
            if name.endswith(("o_proj", "down_proj")):
                scale *= depth
            elif name == "projector":
                scale *= RESIDUAL
            offset = 0.0
        out[name] = {"key": key.astype(np.uint32), "shape": shape,
                     "scale": scale, "offset": offset}
    return out


@functools.partial(jax.jit, static_argnums=(1, 4))
def _draw(key_data, shape, scale, offset, dtype):
    key = jax.random.wrap_key_data(key_data, impl="threefry2x32")
    x = offset + scale * jax.random.normal(key, shape, jnp.float32)
    return x.astype(jnp.bfloat16).astype(dtype)


def _get(recipe, name, device, dtype):
    e = recipe[name]
    return _draw(jax.device_put(np.asarray(e["key"], np.uint32), device),
                 tuple(e["shape"]), np.float32(e["scale"]),
                 np.float32(e["offset"]), jnp.dtype(dtype))


# ---------------------------------------------------------------- math
def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + x.dtype.type(eps)) * w


def _rope(x, pos, theta):
    """Rotate interleaved pairs of the last axis; ``pos`` broadcasts
    against x's axes before the last (heads axis included)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, d, 2) / d))
    ang = pos[..., None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    xe, xo = x[..., 0::2], x[..., 1::2]
    return jnp.stack([xe * cos - xo * sin, xo * cos + xe * sin],
                     -1).reshape(x.shape)


def _swiglu(x, g, u, dn):
    return (jax.nn.silu(x @ g) * (x @ u)) @ dn


def _attention(w, xp, xc, spec):
    """MLA over prefix xp (N, S, D) and each candidate's tokens xc
    (N, C, T, D), which see the prefix and their own earlier tokens."""
    h, r = spec["num_attention_heads"], spec["kv_lora_rank"]
    dn, dr, dv = (spec["qk_nope_head_dim"], spec["qk_rope_head_dim"],
                  spec["v_head_dim"])
    eps, theta = spec["rms_norm_eps"], float(spec["rope_theta"])
    S, T = xp.shape[1], xc.shape[2]
    pp = jnp.arange(S)
    pc = S + jnp.arange(T)

    def qkv(x, pos):
        q = (x @ w["self_attn.q_proj"]).reshape(*x.shape[:-1], h, dn + dr)
        q_rope = _rope(q[..., dn:], pos[:, None], theta)
        kv = x @ w["self_attn.kv_a_proj_with_mqa"]
        lat = _rms(kv[..., :r], w["self_attn.kv_a_layernorm"], eps)
        k_rope = _rope(kv[..., r:], pos, theta)
        kvb = (lat @ w["self_attn.kv_b_proj"]).reshape(*x.shape[:-1], h,
                                                        dn + dv)
        k = jnp.concatenate([kvb[..., :dn],
                             jnp.broadcast_to(k_rope[..., None, :],
                                              (*k_rope.shape[:-1], h, dr))],
                            -1)
        q = jnp.concatenate([q[..., :dn], q_rope], -1)
        return q, k, kvb[..., dn:]

    scale = xp.dtype.type((dn + dr) ** -0.5)
    qp, kp, vp = qkv(xp, pp)
    s = jnp.einsum("nqhd,nkhd->nhqk", qp, kp) * scale
    s = jnp.where(pp[:, None] >= pp[None, :], s, -jnp.inf)
    op = jnp.einsum("nhqk,nkhd->nqhd", jax.nn.softmax(s, -1), vp)
    qc, kc, vc = qkv(xc, pc)
    s_pre = jnp.einsum("nctHd,nkHd->ncHtk", qc, kp) * scale
    s_own = jnp.einsum("nctHd,ncuHd->ncHtu", qc, kc) * scale
    s_own = jnp.where(pc[:, None] >= pc[None, :], s_own, -jnp.inf)
    pr = jax.nn.softmax(jnp.concatenate([s_pre, s_own], -1), -1)
    oc = (jnp.einsum("ncHtk,nkHd->nctHd", pr[..., :S], vp)
          + jnp.einsum("ncHtu,ncuHd->nctHd", pr[..., S:], vc))
    wo = w["self_attn.o_proj"]
    return (op.reshape(*op.shape[:2], -1) @ wo,
            oc.reshape(*oc.shape[:3], -1) @ wo)


def _moe(w, x, spec):
    """Held experts' routed part plus the shared experts, x (..., D)."""
    E, K = spec["n_routed_experts"], spec["num_experts_per_tok"]
    held = spec["experts_held"]
    logits = x.astype(jnp.float32) @ w["mlp.gate"].astype(jnp.float32)
    s = jax.nn.sigmoid(logits)
    _, top = jax.lax.top_k(s + w["mlp.e_score_correction_bias"]
                           .astype(jnp.float32), K)
    chosen = jax.nn.one_hot(top, E, dtype=jnp.float32).sum(-2)
    wt = s * chosen
    if spec["norm_topk_prob"]:
        wt = wt / (wt.sum(-1, keepdims=True) + 1e-20)
    wt = (wt * spec["routed_scaling_factor"]).astype(x.dtype)
    out = _swiglu(x, w["mlp.shared_experts.gate_proj"],
                  w["mlp.shared_experts.up_proj"],
                  w["mlp.shared_experts.down_proj"])
    for e in range(held):
        p = f"mlp.experts.{e}."
        out = out + wt[..., e:e + 1] * _swiglu(
            x, w[p + "gate_proj"], w[p + "up_proj"], w[p + "down_proj"])
    return out


@functools.partial(jax.jit, static_argnums=(3, 4))
def _layer(w, xp, xc, spec_items, dense):
    spec = dict(spec_items)
    eps = spec["rms_norm_eps"]
    ap, ac = _attention(w, _rms(xp, w["input_layernorm"], eps),
                        _rms(xc, w["input_layernorm"], eps), spec)
    xp, xc = xp + ap, xc + ac
    ln2 = w["post_attention_layernorm"]
    if dense:
        def mlp(x):
            return _swiglu(x, w["mlp.gate_proj"], w["mlp.up_proj"],
                           w["mlp.down_proj"])
    else:
        def mlp(x):
            return _moe(w, x, spec)
    return xp + mlp(_rms(xp, ln2, eps)), xc + mlp(_rms(xc, ln2, eps))


@functools.partial(jax.jit, static_argnums=(4,))
def _embed(emb, proj, imgs, prompt_cand, spec_items):
    spec = dict(spec_items)
    prompt, cand = prompt_cand
    p, m = spec["patch_size"], spec["patch_merge"]
    side = p * m
    n, H, W, C = imgs.shape
    Hp, Wp = -(-H // side) * side, -(-W // side) * side
    x = jnp.pad(imgs, ((0, 0), (0, Hp - H), (0, Wp - W), (0, 0)))
    x = x.reshape(n, Hp // side, m, p, Wp // side, m, p, C)
    x = x.transpose(0, 1, 4, 2, 5, 3, 6, 7).reshape(n, -1, m * m * p * p * C)
    xp = jnp.concatenate([x @ proj, jnp.broadcast_to(
        emb[prompt], (n, *emb[prompt].shape))], 1)
    xc = jnp.broadcast_to(emb[cand[:, :-1]], (n, *cand[:, :-1].shape,
                                              emb.shape[1]))
    return xp, xc


@functools.partial(jax.jit, static_argnums=(5,))
def _scores(norm, head, xp, xc, cand, eps):
    last = _rms(xp[:, -1], norm, eps) @ head                    # (N, V)
    steps = _rms(xc, norm, eps) @ head                          # (N, C, T-1, V)
    lp0 = jax.nn.log_softmax(last, -1)[:, cand[:, 0]]           # (N, C)
    lps = jax.nn.log_softmax(steps, -1)
    rest = jnp.take_along_axis(
        lps, jnp.broadcast_to(cand[None, :, 1:, None],
                              (*lps.shape[:3], 1)), -1)[..., 0]
    return jnp.concatenate([lp0[..., None], rest], -1).astype(jnp.float32)


def reference(images, params, *, spec, weights, device, dtype):
    """(N, C, T) log-probabilities for images (N, H, W, 3), the query's
    ``prompt`` (P ids) and ``candidates`` (C x T ids), in ``dtype``."""
    keys = ("hidden_size", "num_attention_heads", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "n_routed_experts", "num_experts_per_tok", "experts_held",
            "routed_scaling_factor", "norm_topk_prob", "rms_norm_eps",
            "rope_theta", "patch_size", "patch_merge")
    items = tuple((k, spec[k]) for k in keys)
    prompt = jnp.asarray(np.asarray(params["prompt"], np.int32))
    cand = jnp.asarray(np.asarray(params["candidates"], np.int32))
    prompt, cand = jax.device_put((prompt, cand), device)
    names = {layer: [] for layer in range(spec["num_hidden_layers"] + 1)}
    for layer, name in _shapes(spec):
        names[layer].append(name)
    with jax.default_matmul_precision("highest"):
        emb = _get(weights, "embed_tokens", device, dtype)
        proj = _get(weights, "projector", device, dtype)
        xs = []
        for lo in range(0, len(images), BLOCK):
            imgs = jax.device_put(np.asarray(images[lo:lo + BLOCK],
                                             np.float32), device)
            xs.append(_embed(emb, proj, imgs.astype(dtype), (prompt, cand),
                             items))
        del emb, proj
        for i in range(spec["num_hidden_layers"]):
            pre = f"layers.{i}."
            w = {n[len(pre):]: _get(weights, n, device, dtype)
                 for n in names[i + 1]}
            dense = i < spec["first_k_dense_replace"]
            xs = [_layer(w, xp, xc, items, dense) for xp, xc in xs]
            del w
        norm = _get(weights, "norm", device, dtype)
        head = _get(weights, "lm_head", device, dtype)
        out = [np.asarray(_scores(norm, head, xp, xc, cand,
                                  spec["rms_norm_eps"])) for xp, xc in xs]
    return np.concatenate(out)
