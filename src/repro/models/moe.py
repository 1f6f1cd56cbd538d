"""Mixture-of-Experts FFN (top-k routing, sort-based capacity dispatch),
and the dropless expert-share layer of DeepSeek-V3-style models
(``apply_moe_share``).

TPU-native formulation: instead of the GShard one-hot dispatch einsum
(whose one-hot matmul FLOPs would dwarf the expert FFN at 128 experts)
or a dense all-experts pass (8-16x wasted compute), tokens are routed via
argsort + fixed-capacity gather/scatter:

  assignments -> stable argsort by expert -> position-in-expert by
  segment arithmetic -> scatter into an (E, C, D) buffer (overflow
  dropped) -> one grouped einsum over experts -> gather back with
  combine weights.

All shapes are static (C = capacity_factor * T * k / E), so this lowers
cleanly under pjit; expert weights are 2D-sharded (experts -> 'data',
expert_ff -> 'model'), making the dispatch an all-to-all across the DP
axis — the paper's "offload to kappa remote servers" in collective form.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.distributed.sharding import ShardingCtx
from repro.models import common


def init_moe(kg: common.KeyGen, cfg: ArchConfig, dtype) -> dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    depth_std = (f ** -0.5) / max(cfg.num_layers, 1) ** 0.5
    return {
        "router": common.normal(kg(), (d, e), jnp.float32),
        "w_gate": common.normal(kg(), (e, d, f), dtype),
        "w_up": common.normal(kg(), (e, d, f), dtype),
        "w_down": common.normal(kg(), (e, f, d), dtype, std=depth_std),
    }


def axes_moe(cfg: ArchConfig) -> dict:
    return {
        "router": ("embed", None),
        "w_gate": ("experts", "embed", "expert_ff"),
        "w_up": ("experts", "embed", "expert_ff"),
        "w_down": ("experts", "expert_ff", "embed"),
    }


def _local_topk_route(xf, router, E, K, cf, aux_coef, dtype):
    """Shared routing math on a (T, D) token block; returns
    (top_w, top_e, aux).  Router logits accumulate in f32 via
    preferred_element_type WITHOUT materializing an f32 copy of the
    hidden states (a 536 MB/layer/microbatch copy at 4096 width —
    EXPERIMENTS.md section Perf, iteration 4)."""
    logits = jax.lax.dot(xf, router.astype(xf.dtype),
                         preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, K)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    me = jnp.mean(probs, axis=0)
    one_hot_top = jax.nn.one_hot(top_e, E, dtype=jnp.float32)
    fe = jnp.mean(jnp.sum(one_hot_top, axis=1), axis=0)
    aux = aux_coef * E * jnp.sum(fe * me)
    return top_w.astype(dtype), top_e, aux


def apply_moe_ep_shardmap(p, x, *, cfg: ArchConfig, sh: ShardingCtx,
                          capacity_factor=None) -> tuple[jax.Array, jax.Array]:
    """Expert parallelism on the TP axis with an EXPLICIT collective
    schedule (hillclimbed — EXPERIMENTS.md section Perf, moe_train cell).

    Under pure pjit, GSPMD lowers the sharded dispatch gather/scatter by
    materializing (T*k, D) cross products and all-reducing them (observed:
    8.6 GB all-reduces per layer).  Inside shard_map everything is local:

    - tokens stay on their data shard (and are replicated over 'model',
      as after any TP all-reduce);
    - each model column owns E/TP experts (weights arrive pre-sliced;
      their ZeRO'd expert_ff dim is re-gathered over 'data' per layer —
      small: E/TP x 3 x D x F/DP);
    - every column routes its LOCAL tokens to its OWN experts only
      (local sort, per-shard capacity) — no dispatch collective at all;
    - partial outputs are combined with one psum over 'model', the same
      volume as a dense TP MLP's all-reduce.
    """
    mesh = sh.mesh
    E, K = cfg.num_experts, cfg.num_experts_per_tok
    cf = capacity_factor or cfg.moe_capacity_factor
    B, S, D = x.shape
    axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    tp = axes.get("model", 1)
    dp_axes = tuple(a for a in ("pod", "data") if a in axes)
    E_loc = E // tp

    def local(xl, router, wg, wu, wd):
        # xl: (B_loc, S, D); w*: (E_loc, D, F_loc) with the ZeRO'd
        # expert_ff dim sharded over 'data' only — regather it per layer
        if axes.get("data", 1) > 1:
            wg = jax.lax.all_gather(wg, "data", axis=2, tiled=True)
            wu = jax.lax.all_gather(wu, "data", axis=2, tiled=True)
            wd = jax.lax.all_gather(wd, "data", axis=1, tiled=True)
        Bl = xl.shape[0]
        T = Bl * S
        xf = xl.reshape(T, D)
        top_w, top_e, aux = _local_topk_route(xf, router, E, K, cf,
                                              cfg.router_aux_loss_coef, xl.dtype)
        col = jax.lax.axis_index("model")
        # keep only assignments owned by this column
        owner = top_e // E_loc
        local_e = top_e - col * E_loc
        mine = owner == col
        flat_e = jnp.where(mine, local_e, E_loc).reshape(-1)  # E_loc = drop slot
        flat_w = (top_w * mine.astype(top_w.dtype)).reshape(-1)
        C = max(8, int(-(-cf * T * K // E)))
        order = jnp.argsort(flat_e, stable=True)
        sorted_e = flat_e[order]
        token_of = order // K
        counts = jnp.bincount(sorted_e, length=E_loc + 1)
        starts = jnp.cumsum(counts) - counts
        pos = jnp.arange(T * K, dtype=jnp.int32) - starts[sorted_e]
        keep = (pos < C) & (sorted_e < E_loc)
        buf = jnp.zeros((E_loc, C, D), xl.dtype)
        buf = buf.at[jnp.where(keep, sorted_e, E_loc),
                     jnp.where(keep, pos, C)].set(xf[token_of], mode="drop")
        h = common.swiglu(jnp.einsum("ecd,edf->ecf", buf, wg),
                          jnp.einsum("ecd,edf->ecf", buf, wu))
        out_buf = jnp.einsum("ecf,efd->ecd", h, wd)
        contrib = out_buf[jnp.minimum(sorted_e, E_loc - 1), jnp.minimum(pos, C - 1)]
        contrib = contrib * (flat_w[order] * keep.astype(xl.dtype))[:, None]
        y = jnp.zeros((T, D), xl.dtype).at[token_of].add(contrib)
        # combine across expert columns — the one collective of this layer
        y = jax.lax.psum(y, "model")
        aux = jax.lax.pmean(aux, "model")
        if dp_axes:
            aux = jax.lax.pmean(aux, dp_axes)
        return y.reshape(Bl, S, D), aux

    batch_spec = P(dp_axes if dp_axes else None, None, None)
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(batch_spec, P(None, None),
                  P("model", None, "data"), P("model", None, "data"),
                  P("model", "data", None)),
        out_specs=(batch_spec, P()),
        check_vma=False,
    )
    y, aux = fn(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    return y, aux


def _use_shardmap_ep(cfg: ArchConfig, sh: ShardingCtx) -> bool:
    if sh.mesh is None or sh.rules.get("experts") != "model":
        return False
    axes = dict(zip(sh.mesh.axis_names, sh.mesh.devices.shape))
    return ("model" in axes and cfg.num_experts % axes["model"] == 0
            and sh.rules.get("expert_ff") == "data")


def apply_moe(p: dict, x: jax.Array, *, cfg: ArchConfig, sh: ShardingCtx,
              capacity_factor: float | None = None) -> tuple[jax.Array, jax.Array]:
    """Returns (output (B,S,D), aux load-balancing loss scalar)."""
    if _use_shardmap_ep(cfg, sh):
        return apply_moe_ep_shardmap(p, x, cfg=cfg, sh=sh,
                                     capacity_factor=capacity_factor)
    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.num_experts_per_tok
    T = B * S
    cf = capacity_factor or cfg.moe_capacity_factor
    C = max(8, int(-(-cf * T * K // E)))  # static capacity per expert

    xf = x.reshape(T, D)
    logits = jax.lax.dot(xf, p["router"].astype(x.dtype),
                         preferred_element_type=jnp.float32)               # (T,E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, K)                                 # (T,K)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    # aux loss (Switch-style): E * sum_e f_e * p_e
    me = jnp.mean(probs, axis=0)                                 # (E,)
    one_hot_top = jax.nn.one_hot(top_e, E, dtype=jnp.float32)    # (T,K,E)
    fe = jnp.mean(jnp.sum(one_hot_top, axis=1), axis=0)          # (E,)
    aux = cfg.router_aux_loss_coef * E * jnp.sum(fe * me)

    # ---- sort-based dispatch ------------------------------------------
    flat_e = top_e.reshape(-1)                       # (T*K,)
    flat_w = top_w.reshape(-1).astype(x.dtype)
    order = jnp.argsort(flat_e, stable=True)         # (T*K,)
    sorted_e = flat_e[order]
    token_of = order // K                            # original token per slot
    counts = jnp.bincount(sorted_e, length=E)
    starts = jnp.cumsum(counts) - counts             # (E,)
    pos = jnp.arange(T * K, dtype=jnp.int32) - starts[sorted_e]

    keep = pos < C
    buf = jnp.zeros((E, C, D), x.dtype)
    buf = buf.at[sorted_e, jnp.where(keep, pos, C)].set(
        xf[token_of], mode="drop")
    buf = sh(buf, "experts", None, "embed")

    # ---- grouped expert FFN (SwiGLU) ----------------------------------
    # NOTE: the hidden activation is constrained with "act_ff" (a compute
    # axis), NOT "expert_ff" (the weight-STORAGE axis).  When the perf
    # rules store expert weights ZeRO-style (expert_ff -> 'data'),
    # constraining h with the storage axis would shard different tokens'
    # f-slices across data shards — semantically invalid; with act axes
    # GSPMD instead all-gathers the (small) weights per layer.
    h = common.swiglu(
        jnp.einsum("ecd,edf->ecf", buf, p["w_gate"]),
        jnp.einsum("ecd,edf->ecf", buf, p["w_up"]))
    h = sh(h, "experts", None, "act_ff")
    out_buf = jnp.einsum("ecf,efd->ecd", h, p["w_down"])
    out_buf = sh(out_buf, "experts", None, "embed")

    # ---- combine -------------------------------------------------------
    contrib = out_buf[sorted_e, jnp.minimum(pos, C - 1)]          # (T*K, D)
    contrib = contrib * (flat_w[order] * keep.astype(x.dtype))[:, None]
    y = jnp.zeros((T, D), x.dtype).at[token_of].add(contrib)
    return y.reshape(B, S, D), aux


# ------------------------------------------------- expert share (MLA/Kimi)
def route_sigmoid_noaux_tc(x, router, bias, *, k: int, norm: bool,
                           scale: float):
    """DeepSeek-V3's ``noaux_tc`` router with one group, in float32:
    scores ``sigmoid(x W)``; the top ``k`` of ``scores + bias`` chosen;
    each chosen expert weighed by its unbiased score, renormalised over
    the ``k`` (``norm``) and times ``scale``.  x (T, D) -> (weights (T, k)
    float32, experts (T, k) int32)."""
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, experts = jax.lax.top_k(scores + bias.astype(jnp.float32), k)
    w = jnp.take_along_axis(scores, experts, axis=-1)
    if norm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * scale, experts


def row_tiers(tokens: int, k: int, n_held: int, n_experts: int
              ) -> tuple[int, ...]:
    """The row counts, ascending, that one MoE layer's held-expert part
    may run over for ``tokens`` tokens of ``k`` assignments each: 2x and
    4x the rows the held share ``n_held / n_experts`` of the T*k
    assignments expects, rounded up to 128 rows, and last the most that
    can be held, ``tokens * min(k, n_held)`` (a token picks an expert at
    most once), so that no routing drops an assignment.  Where this chip
    holds every expert, that last count alone."""
    cap = tokens * min(k, n_held)
    tiers = {cap}
    for m in (2, 4):
        rows = -(-tokens * k * m * n_held // n_experts)
        tiers.add(min(cap, -(-rows // 128) * 128))
    return tuple(sorted(tiers))


def apply_moe_share(p: dict, x: jax.Array, *, cfg: ArchConfig
                    ) -> tuple[jax.Array, jax.Array]:
    """One MoE layer's output on this chip: the held experts' part of the
    routed sum plus the shared experts, for x (T, D) float32 -> (T, D)
    float32, and the rows the held experts' grouped matmuls ran (int32).
    Routing is over all ``cfg.num_experts``; a token's assignments to
    experts held elsewhere add nothing here.  No token is dropped: the
    assignments are sorted held first, by expert, and the held ones run
    as one grouped (ragged) matmul per projection, whose groups are
    exactly as long as the tokens routed to each expert.

    The gather, the grouped matmuls, the weighting and the scatter run
    over the first rows of that order only: the least of
    :func:`row_tiers` that covers this layer's held assignments, chosen
    on the device.  Rows within that tier past the held groups are
    masked out.

    ``p``: ``router`` (D, E), ``bias`` (E,), the held experts' ``w_gate``,
    ``w_up`` (E_h, D, F), ``w_down`` (E_h, F, D), and the shared experts
    merged into one MLP, ``s_gate``, ``s_up`` (D, F_s), ``s_down``."""
    T, D = x.shape
    K = cfg.num_experts_per_tok
    n_held = p["w_gate"].shape[0]
    w, experts = route_sigmoid_noaux_tc(
        x, p["router"], p["bias"], k=K, norm=cfg.norm_topk_prob,
        scale=cfg.routed_scaling_factor)
    local = experts - cfg.first_held_expert
    held = (local >= 0) & (local < n_held)
    slot = jnp.where(held, local, n_held).reshape(-1)        # n_held: elsewhere
    order = jnp.argsort(slot, stable=True)
    sizes = jnp.bincount(slot, length=n_held + 1)[:n_held].astype(jnp.int32)
    dt = p["w_gate"].dtype
    xb = x.astype(dt)
    w = w.reshape(-1)

    def routed(rows):
        def run(_):
            o = order[:rows]
            token_of = o // K
            lhs = xb[token_of]

            def grouped(a, rhs):
                return jax.lax.ragged_dot(a, rhs, sizes,
                                          preferred_element_type=jnp.float32)
            h = jax.nn.silu(grouped(lhs, p["w_gate"])) \
                * grouped(lhs, p["w_up"])
            y = grouped(h.astype(dt), p["w_down"])
            mine = (slot[o] < n_held)[:, None]
            y = jnp.where(mine, y * w[o][:, None], 0.0)
            return jnp.zeros((T, D), jnp.float32).at[token_of].add(y)
        return run

    tiers = row_tiers(T, K, n_held, cfg.num_experts)
    tier = jnp.searchsorted(jnp.asarray(tiers, jnp.int32), sizes.sum())
    out = jax.lax.switch(tier, [routed(r) for r in tiers], None)
    shared = jax.nn.silu(jnp.dot(xb, p["s_gate"],
                                 preferred_element_type=jnp.float32)) \
        * jnp.dot(xb, p["s_up"], preferred_element_type=jnp.float32)
    out = out + jnp.dot(shared.astype(dt), p["s_down"],
                        preferred_element_type=jnp.float32)
    return out, jnp.asarray(tiers, jnp.int32)[tier]
