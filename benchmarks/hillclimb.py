"""Perf hillclimb driver: run a (arch x shape) cell under named variants
(sharding-rule overrides, cache dtypes), re-lower, re-analyze, and emit
before/after roofline terms.

  PYTHONPATH=src python -m benchmarks.hillclimb --cell moe_train
  PYTHONPATH=src python -m benchmarks.hillclimb --all

The iteration log (hypothesis -> change -> before -> after) is written to
experiments/hillclimb/<cell>.json and summarized in EXPERIMENTS.md
section Perf.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# the driver relaunches each variant in a subprocess so jax device-count
# state stays clean and OOM/compile failures can't kill the sweep
import subprocess

VARIANTS = {
    # ------------------------------------------------------------------
    # Cell 1: qwen3-moe-235b-a22b x train_4k — most collective-bound.
    # Baseline: GSPMD reshards the (E,C,D) dispatch buffers across the
    # data axis (experts stored experts->data), observed as giant
    # all-gathers: collective term 1902 s.
    # ------------------------------------------------------------------
    "moe_train": {
        "arch": "qwen3-moe-235b-a22b",
        "shape": "train_4k",
        "variants": {
            "baseline": {},
            # H1: ride EP on the TP axis — every model shard owns E/16
            # experts and processes its (model-replicated) local tokens;
            # combine becomes the standard per-layer TP all-reduce.
            # Expert weights stored 2D (E->model, F->data) ZeRO-style and
            # re-gathered per layer (small: E/16 x 3 x D x F x bf16).
            "ep_over_model": {"rules": {"experts": "model",
                                        "expert_ff": "data"}},
            # H2: as H1 plus bf16 dispatch buffers are already bf16;
            # drop capacity factor to 1.0 (fewer padded slots moved).
            "ep_model_cf1": {"rules": {"experts": "model",
                                       "expert_ff": "data"},
                             "capacity_factor": 1.0},
        },
    },
    # ------------------------------------------------------------------
    # Cell 2: qwen1.5-32b x decode_32k — worst memory feasibility:
    # MHA KV cache at 32k x batch 128 is 5.5 TB global (21.5 GB/dev) in
    # bf16 — exceeds HBM before params.
    # ------------------------------------------------------------------
    "dense_decode": {
        "arch": "qwen1.5-32b",
        "shape": "decode_32k",
        "variants": {
            "baseline": {},
            # H1: f8 KV cache (e4m3) halves cache bytes and the decode
            # memory term; attention math upcasts on read.
            "kv_cache_f8": {"dtype": "float8_e4m3fn"},
        },
    },
    # ------------------------------------------------------------------
    # Cell 3: zamba2-2.7b x prefill_32k — representative cell (hybrid
    # arch through the serving path that backs the paper's model-UDF
    # queries).  Baseline keeps the shared-attention KV cache replicated
    # across the model axis (cache_seq->model wins the axis; zamba2's 32
    # kv heads ARE divisible by 16, unlike most archs).
    # ------------------------------------------------------------------
    "hybrid_prefill": {
        "arch": "zamba2-2.7b",
        "shape": "prefill_32k",
        "variants": {
            "baseline": {},
            # H1: shard cache on HEADS not seq: k/v are produced
            # head-sharded (kv_fused->model), so head-sharded cache writes
            # need no resharding collective, and per-dev cache drops 16x.
            "cache_heads_sharded": {"rules": {"cache_seq": None,
                                              "cache_heads": "model"}},
            # H2: + f8 cache on top.
            "cache_heads_f8": {"rules": {"cache_seq": None,
                                         "cache_heads": "model"},
                               "dtype": "float8_e4m3fn"},
        },
    },
}

_RUN_TEMPLATE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"   # a CPU dry-run: never takes a chip
import json, sys
sys.path.insert(0, "src")
import jax.numpy as jnp
from repro.launch.dryrun import run_cell
from repro.distributed.sharding import default_rules

spec = json.loads({spec_json!r})
rules = default_rules()
rules.update(spec.get("rules") or {{}})
if spec.get("capacity_factor"):
    # applied via config replace through a registry patch
    from repro.configs import base as cb
    e = cb._REGISTRY[spec["arch"]]
    e.full = e.full.replace(moe_capacity_factor=spec["capacity_factor"])
    cb._REGISTRY[spec["arch"]] = e
dtype = getattr(jnp, spec.get("dtype") or "bfloat16")
rec = run_cell(spec["arch"], spec["shape"], multi_pod=False,
               rules=rules, dtype=dtype, verbose=False)
rec.pop("traceback", None)
print("RESULT_JSON:" + json.dumps(rec))
"""


def run_variant(arch, shape, variant: dict, timeout=900) -> dict:
    spec = {"arch": arch, "shape": shape, **variant}
    code = _RUN_TEMPLATE.format(spec_json=json.dumps(spec))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=timeout,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    for line in out.stdout.splitlines():
        if line.startswith("RESULT_JSON:"):
            return json.loads(line[len("RESULT_JSON:"):])
    return {"status": "error", "error": (out.stderr or out.stdout)[-1500:]}


def run_cell_variants(name: str) -> list[dict]:
    cell = VARIANTS[name]
    rows = []
    for vname, v in cell["variants"].items():
        rec = run_variant(cell["arch"], cell["shape"], v)
        rec["variant"] = vname
        rec["cell"] = name
        rows.append(rec)
        if rec.get("status") == "ok":
            print(f"[{name}/{vname}] compute={rec['compute_term_s']:.2f}s "
                  f"memory={rec['memory_term_s']:.2f}s "
                  f"collective={rec['collective_term_s']:.2f}s "
                  f"input={rec['input_bytes_per_device']/2**30:.2f}GiB "
                  f"-> {rec['bottleneck']}")
        else:
            print(f"[{name}/{vname}] FAILED: {rec.get('error','?')[:300]}")
    os.makedirs("experiments/hillclimb", exist_ok=True)
    with open(f"experiments/hillclimb/{name}.json", "w") as f:
        json.dump(rows, f, indent=2)
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", default=None, choices=list(VARIANTS))
    ap.add_argument("--all", action="store_true")
    a = ap.parse_args()
    cells = list(VARIANTS) if (a.all or not a.cell) else [a.cell]
    for c in cells:
        run_cell_variants(c)


if __name__ == "__main__":
    main()
