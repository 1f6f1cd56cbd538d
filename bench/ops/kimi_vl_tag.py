"""``kimi_vl_tag`` in the engine, as a client of the system adds it:
Kimi-VL-A3B-Instruct's language model at the configuration's widths,
with the experts this chip holds, over the weights the benchmark drew,
registered through ``repro.core.udf.register_model_udf`` as one device
UDF per micro-batch (and the per-entity UDF over a list of one)."""
from __future__ import annotations

from repro.configs.kimi_vl_a3b import from_hf
from repro.core.udf import register_model_udf

NAME = "kimi_vl_tag"


def install(engine, spec: dict, weights: dict, devices) -> None:
    cfg = from_hf(spec, experts_held=spec["experts_held"],
                  patch_size=spec["patch_size"],
                  patch_merge=spec["patch_merge"])
    register_model_udf(NAME, cfg, weights=weights, tracer=engine.tracer,
                       device=devices[0], buckets=spec["group_buckets"])
