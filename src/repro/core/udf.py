"""User-defined operations (paper section 4.1).

UDFs plug into the engine with *no engine code changes*: register a
callable under a name; queries reference it with
``{"type": "udf", "port": ..., "options": {"id": "<name>", ...}}``.
In-process transport models the paper's message queue: the UDF executor
(repro.core.remote.UDFProcess) pulls requests off a queue.Queue — the
same decoupling as the paper's separate-process design, minus the wire.

Model UDFs: ``register_model_udf`` wraps an assigned-architecture LM
as a pipeline operation — the realistic "run ML inference inside the
query" case the paper motivates.  A latent-attention model
(Kimi-VL-A3B) scores candidate answers about each image as one device
UDF over the micro-batch; the older presets label images through the
serving layer.
"""
from __future__ import annotations

import threading
from typing import Any, Callable

_REGISTRY: dict[str, Callable] = {}
_BATCHED: dict[str, Callable] = {}
_DEVICE: dict[str, Callable] = {}
_LOCK = threading.Lock()


def register_udf(name: str, fn: Callable) -> None:
    """fn(img_or_frames, **options) -> transformed array."""
    with _LOCK:
        _REGISTRY[name] = fn


def register_batched_udf(name: str, fn: Callable) -> None:
    """Group-execution variant of a UDF: ``fn(list_of_images, **options)
    -> list_of_images``.  Registering one makes the op eligible for the
    batcher backend (repro.serving.batcher.UDFBatcherBackend), which the
    cost router can then pick when amortizing a group beats per-entity
    execution.  MUST be result-equivalent to the per-entity UDF of the
    same name — the router treats backends as interchangeable."""
    with _LOCK:
        _BATCHED[name] = fn


def get_batched_udf(name: str) -> Callable:
    with _LOCK:
        return _BATCHED[name]


def has_batched_udf(name: str) -> bool:
    with _LOCK:
        return name in _BATCHED


def register_device_udf(name: str, fn: Callable) -> None:
    """Device-execution variant of a UDF: ``fn(list_of_images, **options)
    -> list_of_images``, where ``fn`` runs its math as jit-compiled JAX
    on the accelerator (the function owns its own jit/device placement —
    typically one compiled call over the whole micro-batch).  Registering
    one makes the op eligible for the device backend
    (:class:`repro.query.device_backend.DeviceBackend`), which the cost
    router can then pick when device compute + transfer beats the other
    backends.  MUST be result-equivalent to the per-entity UDF of the
    same name — the router treats backends as interchangeable.  Native
    table ops (crop/resize/...) need no registration: the device backend
    vmaps them automatically."""
    with _LOCK:
        _DEVICE[name] = fn


def get_device_udf(name: str) -> Callable:
    with _LOCK:
        return _DEVICE[name]


def has_device_udf(name: str) -> bool:
    with _LOCK:
        return name in _DEVICE


def get_udf(name: str) -> Callable:
    from repro.core.pipeline import BUILTIN_UDFS
    with _LOCK:
        if name in _REGISTRY:
            return _REGISTRY[name]
    if name in BUILTIN_UDFS:
        return BUILTIN_UDFS[name]
    raise KeyError(f"UDF {name!r} not registered")


def list_udfs() -> list[str]:
    from repro.core.pipeline import BUILTIN_UDFS
    with _LOCK:
        return sorted(set(_REGISTRY) | set(BUILTIN_UDFS))


def unregister_udf(name: str) -> None:
    """Forget every variant registered under ``name`` (and so release
    what they hold, a model's weights on the device among it)."""
    with _LOCK:
        for table in (_REGISTRY, _BATCHED, _DEVICE):
            table.pop(name, None)


def register_model_udf(name: str, arch="qwen3-0.6b", *,
                       steps: int = 4, reduced: bool = True,
                       labels=("WALK", "RUN", "JUMP", "SIT"),
                       weights: dict | None = None, tracer=None,
                       device=None, buckets=(8, 16, 32)) -> None:
    """Register an assigned-architecture LM as a UDF.

    ``arch`` is a registered name or an :class:`ArchConfig`.  A
    latent-attention model (``cfg.is_mla``) becomes a scoring op (see
    :func:`_register_scoring_model`; ``weights`` is its recipe, ``tracer``
    the engine's, ``device`` where it runs, ``buckets`` the group sizes
    it compiles).  Any other preset becomes a classification UDF: the
    image is hashed into a short token prompt; the LM decodes a few
    tokens and the argmax bucket picks a label stamped onto the image.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_arch
    from repro.configs.base import ArchConfig
    from repro.distributed.sharding import ShardingCtx
    from repro.models import get_model
    from repro.serving import greedy_generate
    from repro.visual.font import draw_text

    cfg = arch if isinstance(arch, ArchConfig) else get_arch(arch,
                                                             reduced=reduced)
    if cfg.is_mla:
        _register_scoring_model(name, cfg, weights=weights, tracer=tracer,
                                device=device, buckets=buckets)
        return
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    sh = ShardingCtx(mesh=None)
    lock = threading.Lock()

    def feats_of(img):
        return jnp.clip((img * 255).astype(jnp.int32).mean(axis=(0, 1)),
                        0, cfg.vocab_size - 1).astype(jnp.int32)

    def udf(img, **_):
        prompt = {"tokens": feats_of(img)[None, :]}
        if cfg.frontend == "vit_stub":
            P = cfg.num_patches
            pe = jax.image.resize(img, (P, 8, 3), "linear").reshape(P, -1)
            pe = jnp.tile(pe, (1, cfg.d_model // pe.shape[-1] + 1))[:, :cfg.d_model]
            prompt["patch_embeds"] = pe[None] * 0.02
        with lock:  # model params shared across engine threads
            toks = greedy_generate(model, params, prompt, steps=steps, sh=sh)
        label = labels[int(jax.device_get(toks[0, -1])) % len(labels)]
        return draw_text(img, label, 4, 4)

    register_udf(name, udf)

    if cfg.frontend != "vit_stub":
        # Grouped serving path: the same model behind a GroupBatcher, so
        # the dispatch router can amortize prefill+decode over a group
        # instead of paying full inference per entity.  Greedy decoding
        # (temperature 0) makes batched == sequential token-for-token
        # (tests/test_batcher.py), so the label — the argmax bucket of
        # the LAST decoded token — is identical to the per-entity UDF.
        # vit_stub frontends are excluded: the per-entity prompt carries
        # image-derived patch embeds the group prefill does not.
        from repro.serving.batcher import GroupBatcher

        batcher = GroupBatcher(model, params, group_size=8,
                               max_new_default=steps, sh=sh, temperature=0.0)

        def batched(imgs, **_):
            with lock:
                reqs = [batcher.submit(np.asarray(feats_of(img)),
                                       max_new=steps) for img in imgs]
                batcher.run_until_idle()
            return [draw_text(img, labels[int(r.result(30)[-1]) % len(labels)],
                              4, 4) for img, r in zip(imgs, reqs)]

        register_batched_udf(name, batched)

        # Device-backend path: the same model as ONE jit-compiled
        # prefill + decode over the whole micro-batch, built on the
        # serving layer's serve_step fns (repro.serving.serve_step).
        # Greedy decoding again keeps the device result token-for-token
        # identical to the per-entity UDF, and the compiled fns are
        # shared across calls so the device cost model's one-time
        # compile term amortizes away with use.
        from repro.serving.serve_step import make_serve_fns, sample_token

        prefill_fn, serve_step = make_serve_fns(model, sh)
        prefill_jit = jax.jit(prefill_fn, static_argnums=(2,))
        step_jit = jax.jit(serve_step)

        def device_batched(imgs, **_):
            with lock:
                toks = jnp.stack([feats_of(img) for img in imgs])
                batch = {"tokens": toks}
                if cfg.is_encoder_decoder:
                    batch["frames"] = jnp.zeros(
                        (len(imgs), cfg.encoder_seq_len, cfg.d_model),
                        jnp.float32)
                prompt_len = toks.shape[1]
                logits, cache = prefill_jit(params, batch,
                                            prompt_len + steps + 1)
                key = jax.random.PRNGKey(0)   # unused: greedy
                tok = sample_token(logits, key, 0.0, cfg.vocab_size)
                idx = jnp.asarray(prompt_len, jnp.int32)
                for i in range(steps - 1):
                    logits, cache = step_jit(params, tok, cache, idx + i)
                    tok = sample_token(logits, jax.random.fold_in(key, i),
                                       0.0, cfg.vocab_size)
                last = np.asarray(jax.device_get(tok))[:, 0]
            return [draw_text(img, labels[int(t) % len(labels)], 4, 4)
                    for img, t in zip(imgs, last)]

        register_device_udf(name, device_batched)


def _register_scoring_model(name: str, cfg, *, weights, tracer, device,
                            buckets) -> None:
    """A latent-attention VLM (:mod:`repro.models.mla`) as an op that
    scores candidate answers about each image.

    Query options: ``prompt`` (P token ids) and ``candidates`` (C answers
    of T ids each).  Per image: its merged patches and the prompt are
    prefilled once; then the C answers are decoded teacher-forced, T - 1
    steps, as C branches of that image's latent cache.  The result is a
    float32 (C, T) array: the log-probability of each answer's t-th
    token under the full-vocabulary log-softmax.

    One device UDF runs a whole micro-batch: padded to the next of
    ``buckets`` (so that at most that many programs of each kind
    compile), one prefill call and T - 1 decode calls, no lock.  The
    per-entity UDF is that over a list of one.  ``weights`` is a recipe
    (:func:`repro.models.mla.draw`), drawn once here on ``device``; a
    model registered before under ``name`` is dropped first.  Records
    into ``tracer``: spans ``model_host`` (stack and put; fetch and
    split), ``model_prefill`` and ``model_decode`` (each call's
    dispatch), counts ``model_calls``, ``model_prefill_tokens`` and
    ``model_decode_tokens`` (the group's real entities, not padding).
    """
    import functools
    import gc

    import jax
    import numpy as np
    from repro.core.trace import Tracer
    from repro.models import mla

    unregister_udf(name)
    gc.collect()
    if device is None:
        device = jax.devices()[0]
    tracer = tracer or Tracer()
    if weights is None:
        weights = mla.init_recipe(jax.random.PRNGKey(0), cfg)
    params = mla.params_from_recipe(cfg, weights, device)
    sizes = tuple(sorted(buckets))

    prefill = jax.jit(functools.partial(mla.score_prefill, cfg=cfg),
                      static_argnums=(3,))
    decode = jax.jit(functools.partial(mla.score_decode, cfg=cfg),
                     donate_argnums=(1,))

    @functools.lru_cache(maxsize=16)
    def ids_on_device(prompt, candidates):
        return jax.device_put((np.asarray(prompt, np.int32),
                               np.asarray(candidates, np.int32)), device)

    def run(imgs, *, prompt, candidates, **_):
        n = len(imgs)
        size = next((b for b in sizes if b >= n), n)
        steps = len(candidates[0]) - 1
        with tracer.span("model_host", n=n):
            batch = np.stack([np.asarray(im, np.float32) for im in imgs])
            if size > n:
                batch = np.concatenate(
                    [batch, np.repeat(batch[-1:], size - n, axis=0)])
            x = jax.device_put(batch, device)
            ids = ids_on_device(tuple(prompt), tuple(map(tuple, candidates)))
        with tracer.span("model_prefill", n=n):
            lp, cache = prefill(params, x, ids, steps)
        prefix = cache["c"].shape[2]
        lps = [lp]
        for t in range(steps):
            with tracer.span("model_decode", n=n):
                lp, cache = decode(params, cache, ids[1], np.int32(t))
            lps.append(lp)
        jax.block_until_ready(lps)
        with tracer.span("model_host", n=n):
            out = np.stack(jax.device_get(lps), axis=-1)[:n]
            results = [out[i] for i in range(n)]
        tracer.count("model_calls", 1 + steps)
        tracer.count("model_prefill_tokens", n * prefix)
        tracer.count("model_decode_tokens", n * len(candidates) * steps)
        return results

    register_udf(name, lambda img, **kw: run([img], **kw)[0])
    register_device_udf(name, run)
