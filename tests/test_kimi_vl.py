"""Kimi-VL-A3B's language model (MLA, sigmoid-routed expert-share MoE
with shared experts) at a reduced size with every mechanism present,
against the plain reference the benchmark checks it with
(``bench/references/kimi_vl_tag.py``), on seeded random weights.

Two tolerances: with the weights in float32 the program's arithmetic is
the reference's, up to float32 rounding (1e-4); as deployed, with
bfloat16 matmul inputs, log-probabilities stay within 0.02 (the program
reads 0.006-0.008 here, the reference computed in bfloat16 0.04-0.06)."""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.configs.kimi_vl_a3b import FULL, PUBLISHED, REDUCED_HF, from_hf
from repro.core import udf
from repro.models import mla, moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2**31 + 11
HELD = 4
SPEC = {**REDUCED_HF, "experts_held": HELD, "patch_size": 4,
        "patch_merge": 2}
PROMPT = [3, 40, 500, 7]
ANSWERS = [[9, 10, 11], [12, 300, 14], [15, 16, 511]]
F32_TOL = 1e-4
BF16_TOL = 0.02


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load(os.path.join(ROOT, "bench", "references", "kimi_vl_tag.py"),
                 "kimi_vl_tag_reference")


@pytest.fixture(scope="module")
def recipe(ref):
    return ref.weights(SPEC, SEED)


@pytest.fixture(scope="module")
def images():
    return np.random.default_rng(5).random((3, 20, 20, 3), dtype=np.float32)


def _cfg(**kw):
    return from_hf(SPEC, experts_held=HELD, patch_size=4, **kw)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _want(ref, recipe, images, spec=SPEC):
    return ref.reference(images, {"prompt": PROMPT, "candidates": ANSWERS},
                         spec=spec, weights=recipe, device=jax.devices()[0],
                         dtype=jnp.float32)


def _program_scores(params, images, cfg):
    ids = (jnp.asarray(PROMPT, jnp.int32), jnp.asarray(ANSWERS, jnp.int32))
    steps = len(ANSWERS[0]) - 1
    lp, cache, _ = mla.score_prefill(params, jnp.asarray(images), ids,
                                     steps, cfg=cfg)
    out = [lp]
    for t in range(steps):
        lp, cache, _ = mla.score_decode(params, cache, ids[1], jnp.int32(t),
                                        cfg=cfg)
        out.append(lp)
    return np.stack(out, -1)


# ---------------------------------------------------------- the config
def test_published_widths_and_the_cut():
    assert FULL == get_arch("kimi-vl-a3b-instruct")
    assert (FULL.d_model, FULL.num_heads, FULL.num_layers) == (2048, 16, 27)
    assert (FULL.qk_nope_head_dim, FULL.qk_rope_head_dim,
            FULL.v_head_dim, FULL.kv_lora_rank) == (128, 64, 128, 512)
    assert (FULL.d_ff, FULL.moe_d_ff, FULL.num_experts,
            FULL.num_experts_per_tok, FULL.num_shared_experts) == \
        (11264, 1408, 64, 6, 2)
    assert FULL.vocab_size == 163840 and FULL.first_k_dense_replace == 1
    assert FULL.param_count() == 15_960_110_208
    cut = FULL.replace(experts_held=8)
    assert cut.held_experts == tuple(range(8))
    assert cut.param_count() == 3_364_615_296
    assert sum(np.prod(s) for s in mla.tensor_shapes(cut).values()) == \
        cut.param_count() + cut.patch_features * cut.d_model


def test_benchmark_configuration_states_the_published_keys():
    cfg = json.load(open(os.path.join(ROOT, "bench", "configs",
                                      "lfw_kimi_vl.json")))
    op = cfg["ops"]["kimi_vl_tag"]
    for key, value in PUBLISHED.items():
        assert cfg[key] == value == op[key], key
    assert cfg["experts_held"] == op["experts_held"] == 8
    assert sorted(cfg["reduced"]) == ["experts_held"]
    built = from_hf(op, experts_held=op["experts_held"],
                    patch_size=op["patch_size"], patch_merge=op["patch_merge"])
    assert built == FULL.replace(experts_held=8)
    assert max(op["group_buckets"]) == cfg["engine"]["device_batch_size"]


def test_unimplemented_variants_are_refused():
    with pytest.raises(ValueError):
        from_hf({**PUBLISHED, "scoring_func": "softmax"})
    with pytest.raises(ValueError):
        from_hf({**PUBLISHED, "q_lora_rank": 1536})


def test_recipe_shapes_are_the_programs(recipe):
    shapes = mla.tensor_shapes(_cfg())
    assert {k: tuple(v["shape"]) for k, v in recipe.items()} == shapes


# ------------------------------------------------------ the whole model
def test_prefill_and_branch_decode_agree_with_the_reference(ref, recipe,
                                                            images):
    """Prefill, then each answer teacher-forced through a branch of the
    latent cache (absorbed MLA), against the reference's expanded full
    forward over image, prompt and answer."""
    cfg = _cfg()
    want = _want(ref, recipe, images)
    assert want.shape == (3, 3, 3) and np.all(want < 0)
    params = mla.params_from_recipe(cfg, recipe)
    got = _program_scores(_f32(params), images, cfg)
    np.testing.assert_allclose(got, want, atol=F32_TOL, rtol=0)
    deployed = _program_scores(params, images, cfg)
    err = np.abs(deployed - want).max()
    assert F32_TOL < err < BF16_TOL


def test_the_device_udf_is_the_model_over_a_micro_batch(ref, recipe,
                                                       images):
    """The registered op: one device UDF over a padded group, and the
    per-entity UDF its group of one."""
    udf.register_model_udf("kimi_test", _cfg(), weights=recipe,
                           buckets=(2, 4))
    try:
        want = _want(ref, recipe, images)
        opts = dict(prompt=tuple(PROMPT),
                    candidates=tuple(map(tuple, ANSWERS)))
        group = udf.get_device_udf("kimi_test")(list(images), **opts)
        assert len(group) == 3
        assert all(g.shape == (3, 3) and g.dtype == np.float32 for g in group)
        assert np.abs(np.stack(group) - want).max() < BF16_TOL
        one = udf.get_udf("kimi_test")(images[1], **opts)
        np.testing.assert_allclose(one, group[1], atol=1e-5)
        assert not udf.has_batched_udf("kimi_test")
    finally:
        udf.unregister_udf("kimi_test")
    assert not udf.has_device_udf("kimi_test")


def test_absorbed_decode_agrees_with_the_expanded_form(recipe):
    """Decode through the latent cache, in branches, gives the logits
    the expanded causal forward gives at the same positions."""
    cfg = _cfg()
    params = _f32(mla.params_from_recipe(cfg, recipe))
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((2, 7, cfg.d_model)), jnp.float32)
    nxt = jnp.asarray([[5, 6], [7, 8]], jnp.int32)      # (B, C) step 0
    after = jnp.asarray([[9, 10], [11, 12]], jnp.int32)  # step 1
    _, cache, _ = mla.prefill(params, x, cfg)
    cache = mla.branch_cache(cfg, cache, 2, 2)
    lg0, cache, _ = mla.decode_step(params, nxt, cache, jnp.int32(0), cfg)
    lg1, _, _ = mla.decode_step(params, after, cache, jnp.int32(1), cfg)
    for c in range(2):
        seq = jnp.concatenate(
            [x, jnp.take(params["embed"], jnp.stack([nxt[:, c], after[:, c]],
                                                    1), axis=0)], 1)
        full = mla.forward(params, seq, cfg)
        np.testing.assert_allclose(lg0[:, c], full[:, 7], atol=1e-4)
        np.testing.assert_allclose(lg1[:, c], full[:, 8], atol=1e-4)


# ---------------------------------------------------------- the router
def test_router_weighs_unbiased_scores_of_the_biased_choice():
    x = jnp.eye(4, dtype=jnp.float32)
    router = jnp.asarray([[2.0, 1.0, 0.0, -1.0]] * 4, jnp.float32)
    bias = jnp.asarray([0.0, 0.0, 0.9, 0.0], jnp.float32)
    w, e = moe.route_sigmoid_noaux_tc(x, router, bias, k=2, norm=True,
                                      scale=2.5)
    s = 1 / (1 + np.exp(-np.array([2.0, 1.0, 0.0, -1.0])))
    # the bias lifts expert 2 (0.5 + 0.9) over expert 1 (0.73) ...
    assert np.all(np.sort(np.asarray(e), 1) == [0, 2])
    # ... but its weight is its unbiased score, renormalised and scaled
    want = 2.5 * np.array([s[0], s[2]]) / (s[0] + s[2])
    np.testing.assert_allclose(np.sort(np.asarray(w), 1), np.sort(
        np.broadcast_to(want, (4, 2)), 1), rtol=1e-6)
    w, _ = moe.route_sigmoid_noaux_tc(x, router, bias, k=2, norm=False,
                                      scale=1.0)
    np.testing.assert_allclose(np.sort(np.asarray(w), 1)[0],
                               np.sort([s[0], s[2]]), rtol=1e-6)


def _moe_params(cfg, recipe, layer):
    p = mla.params_from_recipe(cfg, recipe)["moe"]
    return _f32(jax.tree.map(lambda a: a[layer - cfg.first_k_dense_replace],
                             p))


def test_moe_layer_agrees_with_the_reference(ref, recipe):
    """Router, held experts and shared experts: the program's dropless
    grouped matmul against the reference's every-expert-masked sum."""
    cfg = _cfg()
    layer = 2
    x = jnp.asarray(np.random.default_rng(2).standard_normal(
        (40, cfg.d_model)), jnp.float32)
    got, _ = moe.apply_moe_share(_moe_params(cfg, recipe, layer), x,
                                 cfg=cfg)
    pre = f"layers.{layer}."
    w = {n[len(pre):]: jnp.asarray(ref._get(recipe, n, jax.devices()[0],
                                            jnp.float32))
         for n in recipe if n.startswith(pre)}
    with jax.default_matmul_precision("highest"):
        want = ref._moe(w, x, SPEC)
    np.testing.assert_allclose(got, want, atol=F32_TOL, rtol=0)


def test_expert_shares_add_up_to_the_uncut_layer(ref):
    """Each chip's share of a layer (its experts' part plus the shared
    experts every chip computes alike), summed over the chips with the
    shared experts counted once, is the layer with every expert."""
    n_exp = SPEC["n_routed_experts"]
    full_spec = {**SPEC, "experts_held": n_exp}
    recipe = ref.weights(full_spec, SEED)
    whole = from_hf(full_spec, experts_held=n_exp, patch_size=4)
    x = jnp.asarray(np.random.default_rng(4).standard_normal(
        (50, whole.d_model)), jnp.float32)
    uncut, _ = moe.apply_moe_share(_moe_params(whole, recipe, 1), x,
                                   cfg=whole)
    held = n_exp // 4
    parts = []
    for chip in range(4):
        cfg = dataclasses.replace(whole, experts_held=held,
                                  first_held_expert=chip * held)
        parts.append(moe.apply_moe_share(_moe_params(cfg, recipe, 1), x,
                                         cfg=cfg)[0])
    p = _moe_params(whole, recipe, 1)
    no_routed = {**p, "w_down": jnp.zeros_like(p["w_down"])}
    shared, _ = moe.apply_moe_share(no_routed, x, cfg=whole)
    total = sum(parts) - 3 * shared
    np.testing.assert_allclose(total, uncut, atol=F32_TOL, rtol=0)
    # each share differs from the whole: the cut is real
    assert all(np.abs(np.asarray(part - uncut)).max() > 1e-3
               for part in parts)


# 64 experts, top-6 as published, at the reduced widths; 400 tokens are
# 2,400 assignments, whose tiers at 8 of 64 held are 640, 1,280, 2,400
WIDE = {**SPEC, "n_routed_experts": 64, "num_experts_per_tok": 6}
TOKENS = 400


@functools.lru_cache(maxsize=None)
def _wide(held):
    ref = _load(os.path.join(ROOT, "bench", "references", "kimi_vl_tag.py"),
                "kimi_vl_tag_reference")
    spec = {**WIDE, "experts_held": held}
    return spec, ref.weights(spec, SEED), from_hf(spec, experts_held=held,
                                                  patch_size=4)


@pytest.mark.parametrize("held,lifted,tier", [
    (8, 0, 640), (8, 2, 1280), (8, 6, 2400), (64, 0, 2400)],
    ids=["quarter", "half", "whole", "every_expert_held"])
def test_moe_layer_runs_the_least_tier_that_holds_its_assignments(
        ref, monkeypatch, held, lifted, tier):
    """The held experts' part runs over the first rows of the held-first
    order, the least tier that covers the held assignments, and gives
    what the whole T x K rows give.  A bias that lifts ``lifted`` held
    experts into every token's top 6 fills the tiers above the least:
    with 6, every assignment is held and the whole tier runs, dropping
    none.  A chip holding every expert has the whole tier alone, and
    its program no branch."""
    spec, recipe, cfg = _wide(held)
    tiers = moe.row_tiers(TOKENS, 6, held, 64)
    assert tiers == ((640, 1280, 2400) if held < 64 else (2400,))
    p = _moe_params(cfg, recipe, 1)
    p = {**p, "bias": p["bias"].at[:lifted].add(10.0)}
    x = jnp.asarray(np.random.default_rng(6).standard_normal(
        (TOKENS, cfg.d_model)), jnp.float32)
    _, experts = moe.route_sigmoid_noaux_tc(
        x, p["router"], p["bias"], k=6, norm=True,
        scale=cfg.routed_scaling_factor)
    n_held = int((np.asarray(experts) < held).sum())
    below = ([0] + [t for t in tiers if t < tier])[-1]
    assert below < n_held <= tier
    apply = functools.partial(moe.apply_moe_share, cfg=cfg)
    assert ("cond" in str(jax.make_jaxpr(apply)(p, x))) == (len(tiers) > 1)
    got, rows = apply(p, x)
    assert int(rows) == tier
    # the whole T x K rows, as every tier but the last leaves out
    monkeypatch.setattr(moe, "row_tiers", lambda t, k, h, e: (t * k,))
    whole, rows = apply(p, x)
    assert int(rows) == TOKENS * 6
    np.testing.assert_allclose(got, whole, atol=1e-6, rtol=0)
    # and the reference's every-held-expert-masked sum
    pre = "layers.1."
    w = {n[len(pre):]: jnp.asarray(ref._get(recipe, n, jax.devices()[0],
                                            jnp.float32))
         for n in recipe if n.startswith(pre)}
    w["mlp.e_score_correction_bias"] = p["bias"]
    with jax.default_matmul_precision("highest"):
        want = ref._moe(w, x, spec)
    np.testing.assert_allclose(got, want, atol=F32_TOL, rtol=0)


def test_the_device_udf_counts_the_moe_rows_it_ran(images):
    """One call of the registered op over a group of three, padded to
    four: the assignments are tokens x 6 x the MoE layers of the prefill
    and the decode steps, padding included, and the rows run are fewer
    where 8 of 64 experts are held."""
    from repro.core.trace import Tracer
    _, recipe, cfg = _wide(8)
    tracer = Tracer()
    udf.register_model_udf("kimi_rows", cfg, weights=recipe, tracer=tracer,
                           buckets=(4,))
    try:
        udf.get_device_udf("kimi_rows")(
            list(images), prompt=tuple(PROMPT),
            candidates=tuple(map(tuple, ANSWERS)))
    finally:
        udf.unregister_udf("kimi_rows")
    counts = tracer.stats()["counts"]
    steps = len(ANSWERS[0]) - 1
    prefix = 3 * 3 + len(PROMPT)        # 3x3 merged 8x8 patches, the prompt
    tokens = 4 * (prefix + len(ANSWERS) * steps)
    moe_layers = cfg.num_layers - cfg.first_k_dense_replace
    assert counts["model_moe_assignments"] == tokens * 6 * moe_layers
    assert 0 < counts["model_moe_rows"] < counts["model_moe_assignments"]



def test_list_options_keep_an_operation_hashable():
    """Token ids arrive as JSON lists; the parsed operation holds them as
    tuples, so that the device backend can key a group by it."""
    from repro.core.pipeline import parse_operations
    from repro.core.result_cache import op_signature
    op, = parse_operations([{"type": "udf", "port": 0, "options": {
        "id": "kimi_vl_tag", "prompt": PROMPT, "candidates": ANSWERS}}])
    assert op.kwargs["prompt"] == tuple(PROMPT)
    assert op.kwargs["candidates"] == tuple(map(tuple, ANSWERS))
    assert hash(op_signature(op)) == hash(op_signature(op))


def test_the_model_api_serves_the_latent_model():
    """The registry's uniform surface over the reduced preset: init,
    forward over image and prompt, prefill into the latent cache and a
    decode step that continues it, and replicated axes for every
    parameter."""
    from repro.distributed.sharding import REPLICATED
    from repro.models import get_model
    cfg = get_arch("kimi-vl-a3b-instruct", reduced=True)
    api = get_model(cfg)
    params = _f32(api.init(jax.random.PRNGKey(0)))
    imgs = jnp.asarray(np.random.default_rng(3).random((2, 8, 8, 3)),
                       jnp.float32)
    toks = jnp.asarray([[5, 6, 7, 8], [9, 10, 11, 12]], jnp.int32)
    full, aux = api.forward(params, {"tokens": toks, "images": imgs},
                            REPLICATED)
    assert full.shape == (2, 1 + 4, cfg.vocab_size) and float(aux) == 0.0
    last, cache = api.prefill(params, {"tokens": toks[:, :3], "images": imgs},
                              REPLICATED, 0)
    np.testing.assert_allclose(last, full[:, 3], atol=1e-4)
    assert cache["c"].shape == (cfg.num_layers, 2, 4, cfg.kv_lora_rank)
    assert jax.tree.map(jnp.shape, api.init_cache(2, 4)) == \
        jax.tree.map(jnp.shape, cache)
    cache = mla.branch_cache(cfg, cache, 1, 1)
    nxt, _ = api.decode_step(params, toks[:, 3:], cache, jnp.int32(0),
                             REPLICATED)
    np.testing.assert_allclose(nxt[:, 0], full[:, 4], atol=1e-4)
    axes = api.param_axes()
    assert jax.tree.structure(axes, is_leaf=lambda a: isinstance(a, tuple)) \
        == jax.tree.structure(params)
