"""The model layer of the benchmark at a size a test run holds on the
CPU.  A throwaway configuration beside ``bench_tiny``'s owns the
committed ``kimi_vl_tag`` (``bench/ops``, ``bench/references``) at a
reduced size with every mechanism present, and the harness drives it
end to end with limits of its own; and the model layer's per-layer
readers, fed from counter deltas and a reduced trace."""
from __future__ import annotations

import json
import os
import shutil
import time

import pytest

import bench_tiny
from bench import counts, harness
from bench.harness import BENCH, Readings, load_cell, load_reader

# the reduced Kimi-VL language model (repro.configs.kimi_vl_a3b.REDUCED_HF)
# holding 4 of its 8 experts; 8x8 merged patches of the 64x64 faces
OP = {"vocab_size": 512, "hidden_size": 64, "intermediate_size": 96,
      "moe_intermediate_size": 32, "num_hidden_layers": 3,
      "num_attention_heads": 4, "num_key_value_heads": 4,
      "n_shared_experts": 2, "n_routed_experts": 8,
      "routed_scaling_factor": 2.446, "kv_lora_rank": 32,
      "q_lora_rank": None, "qk_rope_head_dim": 8, "v_head_dim": 16,
      "qk_nope_head_dim": 16, "topk_method": "noaux_tc", "n_group": 1,
      "topk_group": 1, "num_experts_per_tok": 3,
      "first_k_dense_replace": 1, "norm_topk_prob": True,
      "scoring_func": "sigmoid", "rms_norm_eps": 1e-05,
      "rope_theta": 800000, "rope_scaling": None, "attention_bias": False,
      "tie_word_embeddings": False, "hidden_act": "silu",
      "moe_layer_freq": 1, "experts_held": 4, "patch_size": 4,
      "patch_merge": 2, "group_buckets": [2, 4], "source": "test"}
ENGINE = {**bench_tiny.DEVICE_ENGINE, "cost_overrides": {
    "kimi_vl_tag": {"device": 1e-9, "native": 10.0, "remote": 10.0,
                    "batcher": 10.0}}}
TRAFFIC = {"loop": "closed", "clients": 2, "age_window": [1, 3],
           "check_share": 1.0,
           "queries": {"tag": [{"type": "udf", "port": 0, "options": {
               "id": "kimi_vl_tag", "prompt": [3, 40, 500, 7],
               "candidates": [[9, 10, 11], [12, 300, 14], [15, 16, 511]]}}]}}
CELL = "tiny_model.tiny_tag"
# the program reads 0.006-0.008 here and the bfloat16 control 0.04-0.06
LIMITS = {"failed_queries": 0, "wrong_selections": 0, "wrong_deliveries": 0,
          "max_abs_err": 0.02}


def make(tmp_path) -> str:
    """``bench_tiny``'s throwaway benchmark with the model configuration
    and its cell added; returns the path of its BENCHMARK.json."""
    path = bench_tiny.make(tmp_path)
    bench = tmp_path / "bench"
    for sub in ("ops", "references"):
        shutil.copy(os.path.join(bench_tiny.ROOT, "bench", sub,
                                 "kimi_vl_tag.py"), bench / sub)
    (bench / "configs" / "tiny_model.json").write_text(json.dumps({
        "collection": bench_tiny.COLLECTION, "engine": ENGINE,
        "warmup_sizes": [1, 2, 4], "ops": {"kimi_vl_tag": OP}}))
    (bench / "traffic" / "tiny_tag.json").write_text(json.dumps(TRAFFIC))
    (bench / "limits" / f"{CELL}.json").write_text(json.dumps(LIMITS))
    spec = json.load(open(path))
    spec["configs"].append({"name": "tiny_model", "source": "test",
                            "file": "", "reduced": [], "why": "test"})
    spec["workloads"].append({"name": CELL, "config": "tiny_model",
                              "traffic": "tiny_tag", "chips": 1,
                              "why": "test"})
    for m in spec["per_layer"]:
        m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(spec, f)
    return path


def run(tmp_path, *, seed: int = 2**31 + 5, seconds: float = 1.0,
        control=False) -> dict:
    import jax
    c = harness.load_cell(CELL, benchmark=make(tmp_path),
                          bench_dir=str(tmp_path / "bench"))
    return harness.run(c, seed, seconds, False, t_process=time.monotonic(),
                       devices=jax.devices(), log=lambda m: None,
                       trace_seconds=0.3, control=control)


# ------------------------------------------------------ the model cell
def test_owned_model_cell_passes_and_its_control_fails(tmp_path):
    """The model cell end to end through the harness: correct within its
    limit, and the reference computed in bfloat16 in its place reads
    past it."""
    from bench import check
    res = run(tmp_path, control=True)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    err = res["checks"]["max_abs_err"]["value"]
    assert 0 < err <= LIMITS["max_abs_err"]
    ok, table = check.decide(res["_control"], LIMITS)
    assert not ok
    assert table["max_abs_err"]["value"] > 2 * err


def test_owned_model_weights_perturbed_fail(tmp_path, monkeypatch):
    """The model op installed with weights 1% off those the reference
    draws."""
    load = harness.load_module

    def perturbed(bench_dir, sub, name):
        mod = load(bench_dir, sub, name)
        if sub == "ops":
            install = mod.install

            def broken(engine, spec, weights, devices):
                off = {k: {**v, "scale": v["scale"] * 1.01}
                       for k, v in weights.items()}
                return install(engine, spec, off, devices)
            mod.install = broken
        return mod
    monkeypatch.setattr(harness, "load_module", perturbed)
    res = run(tmp_path)
    assert not res["correct"]
    assert res["failed"] == 0
    assert res["checks"]["max_abs_err"]["value"] > \
        res["checks"]["max_abs_err"]["limit"]


# ------------------------------------------------------------- readers
FACE_TAG = "lfw_kimi_vl.face_tag"
TRACE = {"busy_s": 2.0, "window_s": 3.0, "devices": 1}


def _snaps(**deltas):
    start = {"time": 100.0}
    end = {"time": 151.0}
    for key, (a, b) in deltas.items():
        start[key], end[key] = a, b
    return {"start": start, "end": end,
            "trace_start": dict(start), "trace_end": dict(end)}


def _read(metric, snaps, cell=FACE_TAG, trace=TRACE, peaks=None):
    return load_reader(BENCH, metric)(Readings(load_cell(cell), snaps,
                                               trace, peaks))


def _t(kind, name, key=None):
    return f"util.trace.{kind}.{name}" + (f".{key}" if key else "")


def test_kimi_vl_mfu():
    """The model FLOPs of the tokens run in the traced window over the
    chip's peak: 294 GFLOP an entity at the published widths with 8 of
    64 experts held (by hand: 2 x 1,062,731,776 matrix parameters a
    token over 105 prefilled and 24 decoded tokens, attention at each
    context, the 163,840-wide head at 25 positions, the projector over 81
    image tokens)."""
    peaks = counts.peaks("TPU v5 lite")
    per_token = 2 * 1_062_731_776
    prefill = (105 * per_token + 2 * 16 * 320 * 27 * (105 * 106 // 2)
               + 2 * 2048 * 163840 + 2 * 81 * 2352 * 2048)
    decode = 8 * (3 * (per_token + 2 * 2048 * 163840)
                  + 2 * 16 * 320 * 27 * (106 + 107 + 108))
    assert prefill + decode == 293_990_963_200
    snaps = _snaps(**{_t("counts", "model_prefill_tokens"): (0, 105 * 100),
                      _t("counts", "model_decode_tokens"): (0, 24 * 100)})
    assert _read("kimi_vl_mfu", snaps, peaks=peaks) == \
        pytest.approx(100 * 100 * (prefill + decode) / (197e12 * 3.0))
    # nothing run, no trace, no peaks, or a cell without the op: nothing
    still = _snaps(**{_t("counts", "model_prefill_tokens"): (7, 7),
                      _t("counts", "model_decode_tokens"): (3, 3)})
    assert _read("kimi_vl_mfu", still, peaks=peaks) is None
    assert _read("kimi_vl_mfu", snaps, trace=None, peaks=peaks) is None
    assert _read("kimi_vl_mfu", snaps) is None
    assert _read("kimi_vl_mfu", _snaps(), peaks=peaks) is None
    assert _read("kimi_vl_mfu", snaps, cell="lfw_device.blur",
                 peaks=peaks) is None


# (metric, its deltas, the value they give, the delta that is its
# denominator)
CASES = [
    ("model_tokens_per_call",
     {_t("counts", "model_prefill_tokens"): (0, 3360),
      _t("counts", "model_decode_tokens"): (0, 768),
      _t("counts", "model_calls"): (10, 14)},
     1032.0, _t("counts", "model_calls")),
    ("model_host_ms_per_entity",
     {_t("spans", "model_host", "s"): (1.0, 1.064),
      "device.entities_run": (100, 132)},
     2.0, "device.entities_run"),
]


@pytest.mark.parametrize("metric,deltas,value,denominator", CASES,
                         ids=[c[0] for c in CASES])
def test_reader_arithmetic(metric, deltas, value, denominator):
    assert _read(metric, _snaps(**deltas)) == pytest.approx(value)
    # the same numbers in another cell give the same value
    assert _read(metric, _snaps(**deltas),
                 cell="lfw_device.blur") == pytest.approx(value)


@pytest.mark.parametrize("metric,deltas,value,denominator", CASES,
                         ids=[c[0] for c in CASES])
def test_reader_is_silent_where_its_denominator_did_not_move(
        metric, deltas, value, denominator):
    still = dict(deltas)
    a, _ = still[denominator]
    still[denominator] = (a, a)
    assert _read(metric, _snaps(**still)) is None


@pytest.mark.parametrize("metric,deltas,value,denominator", CASES,
                         ids=[c[0] for c in CASES])
def test_reader_is_silent_without_the_programs_record(
        metric, deltas, value, denominator):
    # a program without the model op's spans and counts (the parent of
    # this cell's program) gives nothing to read, never 0
    kept = {k: v for k, v in deltas.items()
            if not k.startswith("util.trace.")}
    assert _read(metric, _snaps(**kept)) is None
    assert _read(metric, _snaps()) is None


@pytest.mark.parametrize("metric,deltas,value,denominator", CASES,
                         ids=[c[0] for c in CASES])
def test_reader_is_silent_where_no_device_trace_was_reduced(
        metric, deltas, value, denominator):
    assert _read(metric, _snaps(**deltas), trace=None) is None
