"""Multi-head latent attention (MLA, DeepSeek-V2) in a DeepSeek-V3-style
decoder: the language model of Kimi-VL-A3B.

A layer: RMSNorm -> MLA -> residual -> RMSNorm -> MLP -> residual.  The
first ``first_k_dense_replace`` layers have a dense SwiGLU MLP, the rest
the expert-share MoE of :mod:`repro.models.moe` (sigmoid router, shared
experts, only the experts this chip holds computed).  The residual
stream, norms, router, softmax and log-softmax are float32; weights are
bfloat16 and every matrix product takes its inputs in the weights' dtype
(bfloat16) and accumulates in float32.

MLA (no q-LoRA): ``q = x Wq`` splits per head into a 128-wide part
without position and a 64-wide part under RoPE; ``x Wkv_a`` gives a
512-wide latent (RMS-normed) and one 64-wide RoPE key that every head
shares.  ``Wkv_b`` expands the latent into each head's 128-wide key and
value.  RoPE rotates interleaved pairs, as DeepSeek's checkpoints lay it
out (de-interleave, then rotate halves).

Two paths over one cache of (latent, rope key) per token, 576 numbers:

- **prefill** (``prefill``) expands K/V for the prompt and attends in
  the ordinary form;
- **decode** (``decode_step``) uses the absorbed form: ``Wkv_b``'s key
  half is folded into the query and its value half applied after the
  weighted sum, so attention reads only the latent cache.  Decode runs
  ``C`` branches of every prompt at once: each branch attends to its
  prompt's cache, shared by its branches, and to its own slots.

Weights are given as a *recipe*: for each tensor a PRNG key, a shape, a
scale and an offset; the value is ``offset + scale * normal(key, shape)``
drawn in float32 and rounded to bfloat16 (:func:`draw`), so that a
benchmark can hand the program billions of parameters as a few kilobytes
and draw the same values again, a layer at a time, for its reference.
Matrices are laid out (in, out).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import moe

F32 = jnp.float32
BF16 = jnp.bfloat16


# ----------------------------------------------------------- the recipe
def tensor_shapes(cfg: ArchConfig) -> dict[str, tuple[int, ...]]:
    """Name -> shape of every tensor of the model (the experts this chip
    holds only), in the recipe's naming."""
    d, h, r, dr = (cfg.d_model, cfg.num_heads, cfg.kv_lora_rank,
                   cfg.qk_rope_head_dim)
    dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    out = {"embed_tokens": (cfg.vocab_size, d), "lm_head": (d, cfg.vocab_size),
           "norm": (d,), "projector": (cfg.patch_features, d)}
    for i in range(cfg.num_layers):
        p = f"layers.{i}."
        out.update({
            p + "input_layernorm": (d,), p + "post_attention_layernorm": (d,),
            p + "self_attn.q_proj": (d, h * (dn + dr)),
            p + "self_attn.kv_a_proj_with_mqa": (d, r + dr),
            p + "self_attn.kv_a_layernorm": (r,),
            p + "self_attn.kv_b_proj": (r, h * (dn + dv)),
            p + "self_attn.o_proj": (h * dv, d)})
        if i < cfg.first_k_dense_replace:
            out.update({p + "mlp.gate_proj": (d, cfg.d_ff),
                        p + "mlp.up_proj": (d, cfg.d_ff),
                        p + "mlp.down_proj": (cfg.d_ff, d)})
            continue
        fs = cfg.num_shared_experts * cfg.moe_d_ff
        out.update({p + "mlp.gate": (d, cfg.num_experts),
                    p + "mlp.e_score_correction_bias": (cfg.num_experts,),
                    p + "mlp.shared_experts.gate_proj": (d, fs),
                    p + "mlp.shared_experts.up_proj": (d, fs),
                    p + "mlp.shared_experts.down_proj": (fs, d)})
        for e in cfg.held_experts:
            q = f"{p}mlp.experts.{e}."
            out.update({q + "gate_proj": (d, cfg.moe_d_ff),
                        q + "up_proj": (d, cfg.moe_d_ff),
                        q + "down_proj": (cfg.moe_d_ff, d)})
    return out


@functools.partial(jax.jit, static_argnums=(1,))
def _draw(key_data, shape, scale, offset):
    key = jax.random.wrap_key_data(key_data, impl="threefry2x32")
    x = offset + scale * jax.random.normal(key, shape, F32)
    return x.astype(BF16)


def draw(entry: dict, device=None) -> jax.Array:
    """One tensor of a recipe, as bfloat16 on ``device``:
    ``offset + scale * normal(key, shape)`` in float32, rounded."""
    key = jax.device_put(np.asarray(entry["key"], np.uint32), device)
    return _draw(key, tuple(entry["shape"]), F32(entry["scale"]),
                 F32(entry["offset"]))


def init_recipe(key: jax.Array, cfg: ArchConfig) -> dict:
    """A recipe of fan-in-scaled weights from one PRNG key: norms near 1,
    output projections scaled down by the depth, the embedding N(0, 1)."""
    out = {}
    depth = (2 * cfg.num_layers) ** -0.5
    for i, (name, shape) in enumerate(tensor_shapes(cfg).items()):
        k = np.asarray(jax.random.key_data(jax.random.fold_in(key, i)))
        if len(shape) == 1:
            scale, offset = (0.02, 0.0) if name.endswith("bias") else (0.1, 1.0)
        elif name == "embed_tokens":
            scale, offset = 1.0, 0.0
        else:
            scale = shape[0] ** -0.5 * (depth if name.endswith(
                ("o_proj", "down_proj")) else 1.0)
            offset = 0.0
        out[name] = {"key": k, "shape": shape, "scale": scale,
                     "offset": offset}
    return out


def params_from_recipe(cfg: ArchConfig, recipe: dict, device=None) -> dict:
    """The model's parameters drawn on ``device``: dense layers and MoE
    layers each stacked on a leading axis for ``lax.scan``."""
    shapes = tensor_shapes(cfg)
    missing = sorted(set(shapes) - set(recipe))
    if missing:
        raise KeyError(f"recipe lacks {len(missing)} tensors, "
                       f"first {missing[0]!r}")
    for name, shape in shapes.items():
        if tuple(recipe[name]["shape"]) != shape:
            raise ValueError(f"{name}: recipe shape "
                             f"{tuple(recipe[name]['shape'])} != {shape}")

    def get(name):
        return draw(recipe[name], device)

    def stacked(layers, names):
        out = {}
        for key, suffix in names.items():
            parts = [get(f"layers.{i}.{suffix}") for i in layers]
            out[key] = jnp.stack(parts)
            del parts
        return out

    attn = {"ln1": "input_layernorm", "ln2": "post_attention_layernorm",
            "wq": "self_attn.q_proj", "wkv_a": "self_attn.kv_a_proj_with_mqa",
            "kv_norm": "self_attn.kv_a_layernorm",
            "wkv_b": "self_attn.kv_b_proj", "wo": "self_attn.o_proj"}
    k = cfg.first_k_dense_replace
    dense_layers = range(k)
    moe_layers = range(k, cfg.num_layers)
    params = {"embed": get("embed_tokens"), "lm_head": get("lm_head"),
              "final_norm": get("norm"), "projector": get("projector")}
    params["dense"] = stacked(dense_layers, {
        **attn, "w_gate": "mlp.gate_proj", "w_up": "mlp.up_proj",
        "w_down": "mlp.down_proj"})
    layers = stacked(moe_layers, {
        **attn, "router": "mlp.gate", "bias": "mlp.e_score_correction_bias",
        "s_gate": "mlp.shared_experts.gate_proj",
        "s_up": "mlp.shared_experts.up_proj",
        "s_down": "mlp.shared_experts.down_proj"})
    for key, proj in (("w_gate", "gate_proj"), ("w_up", "up_proj"),
                      ("w_down", "down_proj")):
        per_layer = []
        for i in moe_layers:
            per_layer.append(jnp.stack([get(f"layers.{i}.mlp.experts.{e}."
                                            f"{proj}")
                                        for e in cfg.held_experts]))
        layers[key] = jnp.stack(per_layer)
        del per_layer
    params["moe"] = layers
    return params


# ------------------------------------------------------------ pieces
def mm(x, w):
    """Inputs in the weight's dtype (bfloat16 as deployed), float32
    accumulation."""
    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=F32)


def rms_norm(x, scale, eps):
    x = x.astype(F32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * scale.astype(F32)


def rope(x, positions, theta, heads: bool):
    """DeepSeek's RoPE: interleaved pairs (x[2i], x[2i+1]) rotated by
    ``positions * theta**(-2i/d)``, returned de-interleaved (evens then
    odds), which keeps every dot product of two rotated vectors.
    ``x`` (..., S, [H,] d) float32 (``heads``: with the H axis);
    ``positions`` (S,), or a scalar for every row."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.asarray(positions).astype(F32)[..., None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if heads:
        cos, sin = cos[..., None, :], sin[..., None, :]
    xe, xo = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([xe * cos - xo * sin, xo * cos + xe * sin], -1)


def swiglu(x, w_gate, w_up, w_down):
    h = jax.nn.silu(mm(x, w_gate)) * mm(x, w_up)
    return mm(h, w_down)


def _split_q(p, xn, positions, cfg):
    """Per-head query: (no-position part, RoPE part)."""
    h, dn, dr = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    q = mm(xn, p["wq"]).reshape(*xn.shape[:-1], h, dn + dr)
    return q[..., :dn], rope(q[..., dn:], positions, cfg.rope_theta, True)


def _latent(p, xn, positions, cfg):
    """The cached latent (normed) and the shared RoPE key, in the
    weights' dtype."""
    r, dt = cfg.kv_lora_rank, p["wkv_a"].dtype
    kv = mm(xn, p["wkv_a"])
    c = rms_norm(kv[..., :r], p["kv_norm"], cfg.norm_eps)
    k_pe = rope(kv[..., r:], positions, cfg.rope_theta, False)
    return c.astype(dt), k_pe.astype(dt)


def _kv_b(p, cfg):
    """Wkv_b as (latent, head, key | value)."""
    h, dn = cfg.num_heads, cfg.qk_nope_head_dim
    w = p["wkv_b"].reshape(cfg.kv_lora_rank, h, dn + cfg.v_head_dim)
    return w[..., :dn], w[..., dn:]


def _scale(cfg):
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5


def attn_prefill(p, xn, positions, cfg):
    """Causal MLA over a whole prompt with expanded keys and values.
    xn (B, S, D) -> (out (B, S, D), latent (B, S, R), k_pe (B, S, dr))."""
    dt = p["wkv_b"].dtype
    q_nope, q_pe = _split_q(p, xn, positions, cfg)
    c, k_pe = _latent(p, xn, positions, cfg)
    wk, wv = _kv_b(p, cfg)
    k_nope = jnp.einsum("bsr,rhn->bshn", c, wk, preferred_element_type=F32)
    v = jnp.einsum("bsr,rhv->bshv", c, wv, preferred_element_type=F32)
    s = (jnp.einsum("bqhn,bkhn->bhqk", q_nope.astype(dt), k_nope.astype(dt),
                    preferred_element_type=F32)
         + jnp.einsum("bqhp,bkp->bhqk", q_pe.astype(dt), k_pe,
                      preferred_element_type=F32)) * _scale(cfg)
    S = xn.shape[1]
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    pr = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhv->bqhv", pr.astype(dt), v.astype(dt),
                   preferred_element_type=F32)
    o = o.reshape(*o.shape[:2], -1)
    return mm(o, p["wo"]), c, k_pe


def attn_decode(p, xn, cache, step, positions, cfg):
    """One token of each of C branches of each of B prompts, absorbed.
    xn (B, C, D); cache: the prompts' ``c`` (B, S, R) and ``k_pe``
    (B, S, dr), the branches' ``bc`` (B, C, T, R) and ``bk_pe``
    (B, C, T, dr), of which slots < ``step`` are written; this token goes
    to slot ``step``.  Returns (out (B, C, D), new bc, new bk_pe)."""
    dt = p["wkv_b"].dtype
    q_nope, q_pe = _split_q(p, xn, positions, cfg)
    c_new, k_new = _latent(p, xn, positions, cfg)
    bc = jax.lax.dynamic_update_slice_in_dim(cache["bc"], c_new[:, :, None],
                                             step, axis=2)
    bk = jax.lax.dynamic_update_slice_in_dim(cache["bk_pe"], k_new[:, :, None],
                                             step, axis=2)
    wk, wv = _kv_b(p, cfg)
    q_lat = jnp.einsum("bchn,rhn->bchr", q_nope.astype(dt), wk,
                       preferred_element_type=F32).astype(dt)
    q_pe = q_pe.astype(dt)
    s_pre = (jnp.einsum("bchr,bsr->bchs", q_lat, cache["c"],
                        preferred_element_type=F32)
             + jnp.einsum("bchp,bsp->bchs", q_pe, cache["k_pe"],
                          preferred_element_type=F32))
    s_own = (jnp.einsum("bchr,bctr->bcht", q_lat, bc,
                        preferred_element_type=F32)
             + jnp.einsum("bchp,bctp->bcht", q_pe, bk,
                          preferred_element_type=F32))
    written = jnp.arange(bc.shape[2]) <= step
    s_own = jnp.where(written, s_own, -jnp.inf)
    s = jnp.concatenate([s_pre, s_own], axis=-1) * _scale(cfg)
    pr = jax.nn.softmax(s, axis=-1).astype(dt)
    S = cache["c"].shape[1]
    o_lat = (jnp.einsum("bchs,bsr->bchr", pr[..., :S], cache["c"],
                        preferred_element_type=F32)
             + jnp.einsum("bcht,bctr->bchr", pr[..., S:], bc,
                          preferred_element_type=F32))
    o = jnp.einsum("bchr,rhv->bchv", o_lat.astype(dt), wv,
                   preferred_element_type=F32)
    o = o.reshape(*o.shape[:2], -1)
    return mm(o, p["wo"]), bc, bk


def _mlp(p, xn, cfg, dense: bool):
    if dense:
        return swiglu(xn, p["w_gate"], p["w_up"], p["w_down"])
    flat = xn.reshape(-1, xn.shape[-1])
    return moe.apply_moe_share(p, flat, cfg=cfg).reshape(xn.shape)


def _layers(params, x, attn, cfg, caches=None):
    """Dense layers, then a scan over the MoE layers.  ``attn(p, xn,
    cache)`` -> (out, new cache); ``caches`` = (dense, moe) per-layer
    caches stacked like the weights (None where attn needs none)."""
    def body(dense):
        def step(h, xs):
            p, cache = xs
            a, new = attn(p, rms_norm(h, p["ln1"], cfg.norm_eps), cache)
            h = h + a
            h = h + _mlp(p, rms_norm(h, p["ln2"], cfg.norm_eps), cfg, dense)
            return h, new
        return step
    dense_c, moe_c = caches if caches is not None else (None, None)
    x, new_dense = jax.lax.scan(body(True), x, (params["dense"], dense_c))
    x, new_moe = jax.lax.scan(body(False), x, (params["moe"], moe_c))
    return x, (new_dense, new_moe)


def _concat_layers(dense, moe_part):
    return jax.tree.map(lambda a, b: jnp.concatenate([a, b]), dense, moe_part)


def _split_layers(cache, cfg):
    k = cfg.first_k_dense_replace
    return (jax.tree.map(lambda a: a[:k], cache),
            jax.tree.map(lambda a: a[k:], cache))


def head(params, h, cfg):
    """Final norm and the untied head: float32 logits."""
    return mm(rms_norm(h, params["final_norm"], cfg.norm_eps),
              params["lm_head"])


def log_probs(logits, tokens):
    """log softmax(logits)[tokens], float32."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, tokens[..., None], axis=-1)[..., 0]
    return picked - lse


# ------------------------------------------------------------- inputs
def patches(images, cfg):
    """(B, H, W, C) float32 images -> (B, N, patch_features): zero-padded
    at the bottom and right to a multiple of patch * merge, cut into
    patch x patch patches, merge x merge neighbours concatenated.  A
    token's features run (merge row, merge col, pixel row, pixel col,
    channel)."""
    B, H, W, C = images.shape
    p, m = cfg.patch_size, cfg.patch_merge
    side = p * m
    Hp, Wp = -(-H // side) * side, -(-W // side) * side
    x = jnp.pad(images, ((0, 0), (0, Hp - H), (0, Wp - W), (0, 0)))
    x = x.reshape(B, Hp // side, m, p, Wp // side, m, p, C)
    x = x.transpose(0, 1, 4, 2, 5, 3, 6, 7)
    return x.reshape(B, (Hp // side) * (Wp // side), m * m * p * p * C)


def image_embeds(params, images, cfg):
    """The image tokens: projected patches, (B, N, D) float32."""
    return mm(patches(images, cfg), params["projector"])


def embed(params, tokens, extra_embeds=None):
    """Token embeddings (B, S) -> (B, S, D) float32, after any image
    embeddings (B, N, D)."""
    h = jnp.take(params["embed"], tokens, axis=0).astype(F32)
    if extra_embeds is not None:
        h = jnp.concatenate([extra_embeds.astype(F32), h], axis=1)
    return h


def embed_inputs(params, images, prompt, cfg):
    """The image tokens followed by the embeddings of the prompt (P,)
    shared by every image: (B, N + P, D) float32."""
    tokens = jnp.broadcast_to(prompt, (images.shape[0], prompt.shape[0]))
    return embed(params, tokens, image_embeds(params, images, cfg))


# --------------------------------------------------------- entry points
def forward(params, x, cfg):
    """Causal forward over whole sequences x (B, S, D) float32 embeddings:
    float32 logits (B, S, V), no cache."""
    positions = jnp.arange(x.shape[1])

    def attn(p, xn, _):
        out, _, _ = attn_prefill(p, xn, positions, cfg)
        return out, None
    h, _ = _layers(params, x, attn, cfg)
    return head(params, h, cfg)


def prefill(params, x, cfg):
    """x (B, S, D) float32 embeddings -> (logits of the last position
    (B, V), cache {"c": (L, B, S, R), "k_pe": (L, B, S, dr)} bfloat16)."""
    positions = jnp.arange(x.shape[1])

    def attn(p, xn, _):
        out, c, k_pe = attn_prefill(p, xn, positions, cfg)
        return out, {"c": c, "k_pe": k_pe}
    h, (dense, moe_part) = _layers(params, x, attn, cfg)
    return head(params, h[:, -1], cfg), _concat_layers(dense, moe_part)


def branch_cache(cfg: ArchConfig, cache: dict, branches: int,
                 steps: int) -> dict:
    """``cache`` with room for ``steps`` tokens in each of ``branches``
    branches of every prompt."""
    L, B = cache["c"].shape[:2]
    dt = cache["c"].dtype
    return {**cache,
            "bc": jnp.zeros((L, B, branches, steps, cfg.kv_lora_rank), dt),
            "bk_pe": jnp.zeros((L, B, branches, steps, cfg.qk_rope_head_dim),
                               dt)}


def decode_step(params, tokens, cache, step, cfg):
    """tokens (B, C) int32, the next token of each branch; ``step`` the
    slot it takes (its position is the prompt's length + step).  Returns
    (float32 logits (B, C, V), the cache with that slot written)."""
    positions = cache["c"].shape[2] + step
    x = embed(params, tokens)

    def attn(p, xn, layer_cache):
        out, bc, bk = attn_decode(p, xn, layer_cache, step, positions, cfg)
        return out, {"bc": bc, "bk_pe": bk}
    h, (dense, moe_part) = _layers(params, x, attn, cfg,
                                   caches=_split_layers(cache, cfg))
    new = _concat_layers(dense, moe_part)
    return head(params, h, cfg), {**cache, **new}


# ------------------------------------------------------ answer scoring
def score_prefill(params, images, ids, steps, *, cfg):
    """Prefill images (B, H, W, C) and the prompt, ``ids`` = (prompt (P,),
    answers (C, T)); returns (log-probability of each answer's first
    token (B, C), the cache with room for ``steps`` tokens a branch)."""
    prompt, answers = ids
    logits, cache = prefill(params, embed_inputs(params, images, prompt, cfg),
                            cfg)
    lp = log_probs(logits[:, None], answers[None, :, 0])
    return lp, branch_cache(cfg, cache, answers.shape[0], steps)


def score_decode(params, cache, answers, step, *, cfg):
    """Feed each answer's token ``step`` to its branch; returns (the
    log-probability of its token ``step + 1`` (B, C), the cache)."""
    B = cache["c"].shape[1]
    fed = jnp.broadcast_to(answers[:, step], (B, answers.shape[0]))
    nxt = jnp.broadcast_to(answers[:, step + 1], fed.shape)
    logits, cache = decode_step(params, fed, cache, step, cfg)
    return log_probs(logits, nxt), cache
