"""End-to-end training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --reduced \
      --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

Full production shapes go through the same path with ``--mesh
production``; on a CPU host use reduced configs and the host mesh.
Features: sharded init, pjit train step with microbatching, WSD/cosine
schedules, prefetching loader, periodic atomic checkpoints, automatic
restart from the latest checkpoint.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch
from repro.dataio import ShardedLoader, lm_token_stream
from repro.distributed.fault import TrainSupervisor
from repro.distributed.sharding import ShardingCtx, default_rules, tree_to_shardings
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import get_model
from repro.training import TrainConfig, make_train_step
from repro.training.train_step import init_train_state, train_state_axes


def make_batch_fn(cfg, batch, seq):
    P = cfg.num_patches if cfg.frontend == "vit_stub" else 0

    def make(step):
        b = {"tokens": lm_token_stream(batch, seq - P if P else seq,
                                       cfg.vocab_size, step)}
        if P:
            b["patch_embeds"] = (np.ones((batch, P, cfg.d_model), np.float32)
                                 * 0.01)
        if cfg.is_encoder_decoder:
            b["frames"] = np.ones((batch, cfg.encoder_seq_len, cfg.d_model),
                                  np.float32) * 0.01
        return b
    return make


def run(arch: str, *, reduced=True, steps=100, batch=8, seq=128,
        lr=3e-3, ckpt_dir=None, save_every=50, mesh_kind="host",
        model_par=1, microbatches=1, compute_dtype="float32",
        log_every=10, schedule="wsd") -> dict:
    cfg = get_arch(arch, reduced=reduced)
    mesh = (make_production_mesh() if mesh_kind == "production"
            else make_host_mesh(model=model_par))
    rules = dict(default_rules())
    if cfg.sharding_overrides:
        rules.update(cfg.sharding_overrides)
    sh = ShardingCtx(mesh=mesh if mesh.size > 1 else None, rules=rules)
    model = get_model(cfg)
    tcfg = TrainConfig(learning_rate=lr, total_steps=steps, warmup_steps=max(steps // 20, 5),
                       schedule=schedule, compute_dtype=compute_dtype,
                       microbatches=microbatches, remat=True)
    step_fn = make_train_step(model, tcfg, sh)

    state = init_train_state(model, jax.random.PRNGKey(0))
    st_ax = train_state_axes(model)
    start = 0
    sup = None
    if ckpt_dir:
        sup = TrainSupervisor(ckpt_dir, save_every=save_every)
        state, start = sup.resume(state)
        if start:
            print(f"[train] resumed from step {start}")
    if mesh.size > 1:
        shardings = tree_to_shardings(state, st_ax, mesh, rules)
        state = jax.device_put(state, shardings)
        jit_step = jax.jit(step_fn, in_shardings=(shardings, None),
                           out_shardings=(shardings, None), donate_argnums=(0,))
    else:
        jit_step = jax.jit(step_fn, donate_argnums=(0,))

    loader = ShardedLoader(make_batch_fn(cfg, batch, seq), start_step=start)
    losses = []
    t0 = time.time()
    ctx = mesh if mesh.size > 1 else _nullctx()
    with ctx:
        for i, (step_idx, np_batch) in zip(range(start, steps), loader):
            batch_j = {k: jnp.asarray(v) for k, v in np_batch.items()}
            state, metrics = jit_step(state, batch_j)
            loss = float(metrics["loss"])
            losses.append(loss)
            if (i + 1) % log_every == 0 or i == start:
                dt = time.time() - t0
                print(f"[train] step {i+1}/{steps} loss={loss:.4f} "
                      f"lr={float(metrics['lr']):.2e} "
                      f"gnorm={float(metrics['grad_norm']):.2f} ({dt:.1f}s)")
            if sup:
                sup.maybe_save(i + 1, state)
    loader.stop()
    return {"losses": losses, "final_loss": losses[-1] if losses else None,
            "steps": len(losses), "seconds": time.time() - t0}


class _nullctx:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--reduced", action="store_true", default=False)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--save-every", type=int, default=50)
    ap.add_argument("--mesh", default="host", choices=["host", "production"])
    ap.add_argument("--model-par", type=int, default=1)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--schedule", default="wsd",
                    choices=["wsd", "cosine", "linear", "constant"])
    ap.add_argument("--dtype", default="float32")
    a = ap.parse_args()
    out = run(a.arch, reduced=a.reduced, steps=a.steps, batch=a.batch,
              seq=a.seq, lr=a.lr, ckpt_dir=a.ckpt_dir, save_every=a.save_every,
              mesh_kind=a.mesh, model_par=a.model_par,
              microbatches=a.microbatches, compute_dtype=a.dtype,
              schedule=a.schedule)
    print(f"[train] done: {out['steps']} steps, final loss {out['final_loss']:.4f}")


if __name__ == "__main__":
    main()
