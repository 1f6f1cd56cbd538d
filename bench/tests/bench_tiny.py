"""A throwaway benchmark at a size a test run holds on the CPU: its own
BENCHMARK.json, configurations, traffic and limits in a temporary
directory, the committed per-layer readers, and the harness driving it
with the chip check left out.  One configuration owns an operation
(``own_op/``: a patch embedding with weights drawn from the seed), added
as files only, as a model configuration would be."""
from __future__ import annotations

import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness  # noqa: E402

SIZE = 64
COLLECTION = {"faces": 48, "size": SIZE, "channels": 3, "dtype": "float32",
              "categories": ["celebrity", "athlete", "politician",
                             "musician"],
              "age_min": 18, "age_max": 29}
DEVICE_ENGINE = {
    "dispatch": "cost", "device_backend": "cpu", "num_device_workers": 1,
    "device_batch_size": 4, "device_max_wait_ms": 5.0,
    "cost_overrides": {op: {"device": 1e-9, "native": 10.0, "remote": 10.0,
                            "batcher": 10.0}
                       for op in ("resize", "crop", "normalize", "blur")},
    "num_remote_servers": 1, "admission": "queue",
    "max_inflight_entities": 64, "num_native_workers": 2}
STATIC_ENGINE = {
    "dispatch": "static", "num_remote_servers": 2,
    "transport": {"network_latency_s": 0.001, "service_time_s": 0.0,
                  "execute_ops": True},
    "admission": "queue", "max_inflight_entities": 64,
    "num_native_workers": 2}
OWN_OP = os.path.join(os.path.dirname(os.path.abspath(__file__)), "own_op")
OWNED_OPS = {"patch_embed": {"patch": 8, "channels": 3, "dim": 32,
                             "source": "arXiv:2010.11929 eq. 1"}}
OWNED_ENGINE = {**DEVICE_ENGINE, "cost_overrides": {
    **DEVICE_ENGINE["cost_overrides"],
    "patch_embed": {"device": 1e-9, "native": 10.0, "remote": 10.0,
                    "batcher": 10.0}}}


def remote(name, **opts):
    return {"type": "remote", "url": f"http://udf/{name}",
            "options": {"id": name, **opts}}


TRAFFIC = {
    "tiny_blur": {"loop": "closed", "clients": 2, "age_window": [1, 3],
                  "check_share": 1.0,
                  "queries": {"blur": [{"type": "blur", "ksize": 5,
                                        "sigma_x": 1.5}]}},
    "tiny_iq": {"loop": "closed", "clients": 2, "age_window": [1, 3],
                "check_share": 1.0,
                "queries": {
                    "crop": [remote("crop", x=4, y=4, width=32, height=32)],
                    "mask": [remote("facedetect_mask", r=12)],
                    "fig8": [{"type": "resize", "width": 80, "height": 96},
                             remote("facedetect_box"),
                             {"type": "threshold", "value": 0.4}]}},
    "tiny_embed": {"loop": "closed", "clients": 2, "age_window": [1, 3],
                   "check_share": 1.0,
                   "queries": {
                       "embed": [{"type": "udf", "port": 0,
                                  "options": {"id": "patch_embed"}}],
                       "resize_embed": [
                           {"type": "resize", "width": 48, "height": 40},
                           {"type": "udf", "port": 0,
                            "options": {"id": "patch_embed"}}]}},
}
CELLS = {"tiny_device.tiny_blur": ("tiny_device", "tiny_blur"),
         "tiny_static.tiny_iq": ("tiny_static", "tiny_iq"),
         "tiny_owned.tiny_embed": ("tiny_owned", "tiny_embed")}
LIMITS = {"failed_queries": 0, "wrong_selections": 0, "wrong_deliveries": 0,
          "max_abs_err": 1e-4, "mismatch_share": 0.01}


def make(tmp_path, engine_overrides=None) -> str:
    """Write the throwaway benchmark under ``tmp_path``; returns the
    path of its BENCHMARK.json.  ``engine_overrides`` maps a
    configuration's name to engine settings that replace its own."""
    bench = tmp_path / "bench"
    for sub in ("configs", "traffic", "limits"):
        (bench / sub).mkdir(parents=True)
    shutil.copytree(os.path.join(ROOT, "bench", "metrics"), bench / "metrics")
    for sub in ("ops", "references"):
        shutil.copytree(os.path.join(OWN_OP, sub), bench / sub)
    for name, engine in (("tiny_device", DEVICE_ENGINE),
                         ("tiny_static", STATIC_ENGINE),
                         ("tiny_owned", OWNED_ENGINE)):
        cfg = {"collection": COLLECTION,
               "engine": {**engine, **(engine_overrides or {}).get(name, {})},
               "warmup_sizes": [1, 2, 4]}
        if name == "tiny_owned":
            cfg["ops"] = OWNED_OPS
        (bench / "configs" / f"{name}.json").write_text(json.dumps(cfg))
    for name, mix in TRAFFIC.items():
        (bench / "traffic" / f"{name}.json").write_text(json.dumps(mix))
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    spec["configs"] = [{"name": c, "source": "test", "file": "",
                        "reduced": [], "why": "test"}
                       for c in ("tiny_device", "tiny_static", "tiny_owned")]
    spec["workloads"] = [{"name": cell, "config": c, "traffic": t,
                          "chips": 1, "why": "test"}
                         for cell, (c, t) in CELLS.items()]
    spec["per_layer"] = [{**m, "workloads": list(CELLS)}
                         for m in spec["per_layer"]]
    for cell in CELLS:
        (bench / "limits" / f"{cell}.json").write_text(json.dumps(LIMITS))
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(spec))
    return str(path)


def run(tmp_path, cell: str, *, seed: int = 2**31 + 5, seconds: float = 1.0,
        trace: bool = False, engine_overrides=None, control=False,
        log=lambda m: None) -> dict:
    import jax
    spec = make(tmp_path, engine_overrides)
    c = harness.load_cell(cell, benchmark=spec,
                          bench_dir=str(tmp_path / "bench"))
    return harness.run(c, seed, seconds, trace, t_process=time.monotonic(),
                       devices=jax.devices(), log=log,
                       trace_seconds=0.3, control=control)
