"""Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture x input-shape) cell, lower + compile the step
function (train_step / prefill / serve_step) against the production mesh
with ShapeDtypeStruct inputs (no allocation), then record:

- memory_analysis()  — proves the cell fits per-device HBM;
- cost_analysis()    — raw XLA FLOPs/bytes (loop bodies counted once);
- loop-corrected FLOPs / HBM bytes / collective bytes from the HLO text
  (repro.launch.hlo_costs) — the roofline inputs;
- exact per-device input bytes (params + optimizer state + caches) from
  the shardings.

Usage:
  python -m repro.launch.dryrun --arch granite-8b --shape train_4k
  python -m repro.launch.dryrun --all --multi-pod
  python -m repro.launch.dryrun --all --mesh both --out experiments/dryrun
"""
# The dry-run (and ONLY the dry-run) needs 512 placeholder devices; jax
# locks the device count at first init, so this precedes every import.
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))
os.environ["JAX_PLATFORMS"] = "cpu"   # placeholder devices: never a chip

import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ALL_ARCHS, SHAPES, get_arch
from repro.configs.base import ArchConfig, ShapeConfig
from repro.distributed.sharding import (
    ShardingCtx, default_rules, tree_to_shardings, safe_spec)
from repro.launch import hlo_costs
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import input_specs
from repro.models import get_model
from repro.training import TrainConfig, make_train_step
from repro.training.train_step import init_train_state, train_state_axes

# TPU v5e-class hardware constants (per chip)
PEAK_FLOPS = 197e12   # bf16
HBM_BW = 819e9        # B/s
ICI_BW = 50e9         # B/s per link


def _batch_axes(tree):
    return jax.tree.map(lambda _: ("batch", None, None, None), tree,
                        is_leaf=lambda x: hasattr(x, "shape"))


def _shardings_for(tree, axes, mesh, rules):
    return tree_to_shardings(tree, axes, mesh, rules)


def _batch_shardings(batch, mesh, rules):
    def one(leaf):
        axes = ("batch",) + (None,) * (len(leaf.shape) - 1)
        return NamedSharding(mesh, safe_spec(leaf.shape, axes, rules, mesh))
    return jax.tree.map(one, batch)


def build_cell(cfg: ArchConfig, shape: ShapeConfig, mesh, *,
               dtype=jnp.bfloat16, rules=None):
    """Returns (fn, args, in_shardings, out_shardings, donate)."""
    rules = dict(rules or default_rules())
    if cfg.sharding_overrides:
        rules.update(cfg.sharding_overrides)
    if shape.kind == "train" and cfg.train_sharding_overrides:
        rules.update(cfg.train_sharding_overrides)
    if shape.kind == "prefill" and cfg.prefill_sharding_overrides:
        rules.update(cfg.prefill_sharding_overrides)
    sh = ShardingCtx(mesh=mesh, rules=rules)
    model = get_model(cfg)
    ax = model.param_axes()

    if shape.kind == "train":
        # pick microbatch count so the remat residual stack stays ~<1.5 GB
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        dp = sizes.get("data", 1) * sizes.get("pod", 1)
        per_dev_seqs = max(shape.global_batch // dp, 1)
        stack_per_seq = shape.seq_len * cfg.d_model * 2 * max(cfg.num_layers, 1)
        mb = 1
        while (per_dev_seqs // mb) * stack_per_seq > 1.5e9 and mb * 2 <= per_dev_seqs:
            mb *= 2
        tcfg = TrainConfig(compute_dtype="bfloat16", remat=True, microbatches=mb)
        step = make_train_step(model, tcfg, sh)
        state = jax.eval_shape(
            lambda k: init_train_state(model, k, param_dtype=jnp.float32),
            jax.random.PRNGKey(0))
        st_ax = train_state_axes(model)
        batch = input_specs(cfg, shape, dtype)
        st_sh = _shardings_for(state, st_ax, mesh, rules)
        b_sh = _batch_shardings(batch, mesh, rules)
        return (step, (state, batch), (st_sh, b_sh), (st_sh, None), (0,))

    cache_dtype = jnp.dtype(cfg.serve_cache_dtype)
    if shape.kind == "prefill":
        def prefill_fn(params, batch):
            return model.prefill(params, batch, sh, max_cache=shape.seq_len,
                                 cache_dtype=cache_dtype)
        params = jax.eval_shape(lambda k: model.init(k, dtype=dtype),
                                jax.random.PRNGKey(0))
        p_sh = _shardings_for(params, ax, mesh, rules)
        batch = input_specs(cfg, shape, dtype)
        b_sh = _batch_shardings(batch, mesh, rules)
        cache = jax.eval_shape(lambda: model.init_cache(
            shape.global_batch, shape.seq_len, cache_dtype))
        c_sh = _shardings_for(cache, model.cache_axes(), mesh, rules)
        return (prefill_fn, (params, batch), (p_sh, b_sh), (None, c_sh), ())

    # decode
    def serve_step(params, tokens, cache, cache_index):
        return model.decode_step(params, tokens, cache, cache_index, sh)

    params = jax.eval_shape(lambda k: model.init(k, dtype=dtype),
                            jax.random.PRNGKey(0))
    p_sh = _shardings_for(params, ax, mesh, rules)
    specs = input_specs(cfg, shape, cache_dtype)
    tokens, cache, idx = specs["tokens"], specs["cache"], specs["cache_index"]
    t_sh = _batch_shardings({"t": tokens}, mesh, rules)["t"]
    c_sh = _shardings_for(cache, model.cache_axes(), mesh, rules)
    i_sh = NamedSharding(mesh, P())
    return (serve_step, (params, tokens, cache, idx),
            (p_sh, t_sh, c_sh, i_sh), (None, c_sh), (2,))


def _sharded_bytes(tree, shardings) -> int:
    """Exact per-device bytes of the inputs under their shardings."""
    total = 0
    for leaf, shd in zip(jax.tree.leaves(tree), jax.tree.leaves(
            shardings, is_leaf=lambda x: isinstance(x, NamedSharding))):
        shard_shape = shd.shard_shape(leaf.shape)
        n = 1
        for d in shard_shape:
            n *= d
        total += n * jnp.dtype(leaf.dtype).itemsize
    return total


def run_cell(arch: str, shape_name: str, *, multi_pod: bool, mesh=None,
             verbose: bool = True, rules=None, dtype=jnp.bfloat16) -> dict:
    cfg = get_arch(arch)
    shape = SHAPES[shape_name]
    ok, reason = cfg.supports_shape(shape)
    rec: dict = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "kind": shape.kind,
    }
    if not ok:
        rec["status"] = "skipped"
        rec["reason"] = reason
        return rec
    mesh = mesh if mesh is not None else make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    t0 = time.time()
    try:
        fn, args, in_sh, out_sh, donate = build_cell(cfg, shape, mesh,
                                                     dtype=dtype, rules=rules)
        with mesh:
            lowered = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                              donate_argnums=donate).lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

        ca = compiled.cost_analysis() or {}
        ma = compiled.memory_analysis()
        mem = {k: getattr(ma, k) for k in dir(ma)
               if not k.startswith("_") and isinstance(getattr(ma, k), int)}
        hlo = compiled.as_text()
        costs = hlo_costs.analyze_hlo(hlo)

        input_bytes = _sharded_bytes(args, in_sh)
        rec.update({
            "status": "ok",
            "chips": chips,
            "lower_s": round(t_lower, 2),
            "compile_s": round(t_compile, 2),
            "ca_flops": ca.get("flops"),
            "ca_bytes": ca.get("bytes accessed"),
            "flops_per_device": costs.flops,
            "hbm_bytes_per_device": costs.hbm_bytes,
            "collective_bytes_per_device": costs.collective_bytes,
            "collective_breakdown": costs.collective_breakdown,
            "while_trips": costs.while_trips,
            "input_bytes_per_device": input_bytes,
            "memory_analysis": mem,
            "compute_term_s": costs.flops / PEAK_FLOPS,
            "memory_term_s": costs.hbm_bytes / HBM_BW,
            "collective_term_s": costs.collective_bytes / ICI_BW,
        })
        terms = {"compute": rec["compute_term_s"],
                 "memory": rec["memory_term_s"],
                 "collective": rec["collective_term_s"]}
        rec["bottleneck"] = max(terms, key=terms.get)
        if verbose:
            print(f"[{rec['mesh']}] {arch} x {shape_name}: OK "
                  f"compile={t_compile:.1f}s input={input_bytes/2**30:.2f} GiB/dev "
                  f"compute={rec['compute_term_s']*1e3:.2f}ms "
                  f"memory={rec['memory_term_s']*1e3:.2f}ms "
                  f"collective={rec['collective_term_s']*1e3:.2f}ms "
                  f"-> {rec['bottleneck']}-bound")
            if mem:
                print(f"  memory_analysis: {mem}")
    except Exception as e:
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
        if verbose:
            print(f"[{rec['mesh']}] {arch} x {shape_name}: FAILED {rec['error']}")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="architecture id (or --all)")
    ap.add_argument("--shape", default=None, choices=list(SHAPES), help="one shape")
    ap.add_argument("--all", action="store_true", help="all archs x shapes")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--mesh", default=None, choices=["single", "multi", "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    meshes = []
    if args.mesh == "both":
        meshes = [False, True]
    elif args.mesh == "multi" or args.multi_pod:
        meshes = [True]
    else:
        meshes = [False]

    archs = ALL_ARCHS if (args.all or not args.arch) else [args.arch]
    shapes = [args.shape] if args.shape else list(SHAPES)

    os.makedirs(args.out, exist_ok=True)
    results = []
    for mp in meshes:
        mesh = make_production_mesh(multi_pod=mp)
        for arch in archs:
            for shape in shapes:
                rec = run_cell(arch, shape, multi_pod=mp, mesh=mesh)
                results.append(rec)
                tag = f"{arch}__{shape}__{rec['mesh'].replace('x','_')}"
                with open(os.path.join(args.out, tag + ".json"), "w") as f:
                    json.dump(rec, f, indent=2)
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_err = sum(r["status"] == "error" for r in results)
    print(f"\ndry-run: {n_ok} ok, {n_skip} skipped-by-design, {n_err} errors "
          f"of {len(results)} cells")
    with open(os.path.join(args.out, "summary.json"), "w") as f:
        json.dump(results, f, indent=2)
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
