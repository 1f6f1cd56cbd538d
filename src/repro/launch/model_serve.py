"""Model-serve launcher: batched prefill + decode over an assigned arch.

  PYTHONPATH=src python -m repro.launch.model_serve --arch qwen3-0.6b \
      --reduced --requests 16 --prompt-len 32 --gen 16

This is the device-side half of the query engine's model-UDF path: the
engine's Thread_3 coalesces entities into request batches and this layer
runs prefill once + a decode loop with a donated KV cache.  (It lived at
``repro.launch.serve`` until the network front-end took that name —
``serve`` now starts the wire endpoint, which is what "serve" means for
a client-server system.)
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch
from repro.distributed.sharding import ShardingCtx, default_rules
from repro.launch.mesh import make_host_mesh
from repro.models import get_model
from repro.serving import make_serve_fns
from repro.serving.serve_step import sample_token


def run(arch: str, *, reduced=True, requests=16, prompt_len=32, gen=16,
        model_par=1, temperature=0.0) -> dict:
    cfg = get_arch(arch, reduced=reduced)
    mesh = make_host_mesh(model=model_par)
    sh = ShardingCtx(mesh=mesh if mesh.size > 1 else None)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(
        rng.integers(1, cfg.vocab_size, (requests, prompt_len)), jnp.int32)}
    P = cfg.num_patches if cfg.frontend == "vit_stub" else 0
    if P:
        batch["patch_embeds"] = jnp.ones((requests, P, cfg.d_model)) * 0.01
    if cfg.is_encoder_decoder:
        batch["frames"] = jnp.ones((requests, cfg.encoder_seq_len, cfg.d_model)) * 0.01

    prefill_fn, serve_step = make_serve_fns(model, sh)
    max_cache = P + prompt_len + gen + 1
    t0 = time.time()
    logits, cache = jax.jit(
        lambda p, b: prefill_fn(p, b, max_cache))(params, batch)
    logits.block_until_ready()
    t_prefill = time.time() - t0

    step_jit = jax.jit(serve_step, donate_argnums=(2,))
    key = jax.random.PRNGKey(0)
    tok = sample_token(logits, key, temperature, cfg.vocab_size)
    idx = jnp.asarray(P + prompt_len, jnp.int32)
    toks = []
    t1 = time.time()
    for i in range(gen):
        toks.append(tok)
        logits, cache = step_jit(params, tok, cache, idx + i)
        tok = sample_token(logits, jax.random.fold_in(key, i), temperature,
                           cfg.vocab_size)
    jax.block_until_ready(logits)
    t_decode = time.time() - t1
    out = jnp.concatenate(toks, axis=1)
    return {
        "prefill_s": t_prefill,
        "decode_s": t_decode,
        "tokens_per_s": requests * gen / max(t_decode, 1e-9),
        "generated": np.asarray(out),
    }


def main():
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--model-par", type=int, default=1)
    a = ap.parse_args()
    out = run(a.arch, reduced=a.reduced, requests=a.requests,
              prompt_len=a.prompt_len, gen=a.gen, model_par=a.model_par)
    print(f"[serve] {a.arch}: prefill {out['prefill_s']*1e3:.1f} ms, "
          f"decode {out['decode_s']*1e3:.1f} ms "
          f"({out['tokens_per_s']:.1f} tok/s incl. compile)")


if __name__ == "__main__":
    main()
