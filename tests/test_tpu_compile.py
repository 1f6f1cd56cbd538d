"""Compile the query path's Pallas kernels for a described TPU v5e.

The TPU compiler is installed without a chip attached: lowering for the
devices of a described ``v5e:2x2`` topology and compiling raises what the
chip's compiler would raise (tiling, VMEM, layout refusals), at no chip
time.  Nothing runs, so these tests say nothing about results or speed.

The topology and everything built from it live in module-scoped
fixtures, never at import: only one process may load the TPU library,
and pytest-xdist workers all import this file.  The persistent
compilation cache is off around these compiles (a described-topology
entry cannot be read back without a chip).
"""
from __future__ import annotations

import os

import numpy as np
import pytest

# LFW faces, one device micro-batch (DeviceBackend's default batch_size)
FACES = (8, 250, 250, 3)
PREPROCESS = dict(resize_h=256, resize_w=256, crop_x=16, crop_y=16,
                  crop_w=224, crop_h=224, mean=0.5, std=0.25)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        # needs libtpu installed (no chip); a missing compiler fails here
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def faces(topo):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    return jax.ShapeDtypeStruct(FACES, jnp.float32,
                                sharding=SingleDeviceSharding(topo.devices[0]))


def _compiled_text(fn, arg) -> str:
    import jax
    return jax.jit(fn).lower(arg).compile().as_text()


def test_blur_kernel_compiles_at_lfw_shape(faces):
    """impl="auto" lowered for a TPU selects the Pallas kernel."""
    from repro.kernels import ops
    text = _compiled_text(
        lambda x: ops.gaussian_blur(x, 5, 1.5), faces)
    assert "tpu_custom_call" in text


def test_fused_preprocess_kernel_compiles_at_lfw_shape(faces):
    from repro.kernels import ops
    text = _compiled_text(
        lambda x: ops.fused_preprocess(x, **PREPROCESS), faces)
    assert "tpu_custom_call" in text


def test_device_segment_program_compiles_with_kernels(faces):
    """The jitted program a DeviceBackend builds for the segment
    resize -> crop -> normalize -> blur: the chain fast path and the
    blur fast path both lower to Pallas calls."""
    import jax
    from repro.core.pipeline import make_op
    from repro.query.device_backend import DeviceBackend
    seg = (make_op("resize", {"width": PREPROCESS["resize_w"],
                              "height": PREPROCESS["resize_h"]}),
           make_op("crop", {"x": PREPROCESS["crop_x"],
                            "y": PREPROCESS["crop_y"],
                            "width": PREPROCESS["crop_w"],
                            "height": PREPROCESS["crop_h"]}),
           make_op("normalize", {"mean": PREPROCESS["mean"],
                                 "std": PREPROCESS["std"]}),
           make_op("blur", {"ksize": 5, "sigma_x": 1.5}))
    dev = DeviceBackend(calibrate=False, fuse_segments=True,
                        device=jax.devices("cpu")[0])
    program = dev._build_segment_fn(seg)
    compiled = program.lower(faces).compile()
    calls = compiled.as_text().count('custom_call_target="tpu_custom_call"')
    assert calls == 2
    out = jax.eval_shape(program, np.zeros(FACES, np.float32))
    assert out.shape == (8, 224, 224, 3)


def test_expert_share_layer_compiles_at_published_widths(topo):
    """One Kimi-VL MoE layer on this chip (8 of 64 experts, 2 shared) over
    a prefill group of 32 x 105 tokens: the dropless grouped matmul
    lowers to the TPU's ragged-dot kernel in each of the three row tiers
    the held count chooses among, and the layer fits."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from repro.configs.kimi_vl_a3b import FULL
    from repro.models import moe
    cfg = FULL.replace(experts_held=8)
    one = SingleDeviceSharding(topo.devices[0])
    d, f, e = cfg.d_model, cfg.moe_d_ff, cfg.num_experts
    fs = cfg.num_shared_experts * f

    def arg(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)
    p = {"router": arg(d, e), "bias": arg(e), "w_gate": arg(8, d, f),
         "w_up": arg(8, d, f), "w_down": arg(8, f, d), "s_gate": arg(d, fs),
         "s_up": arg(d, fs), "s_down": arg(fs, d)}
    x = arg(32 * 105, d, dtype=jnp.float32)
    compiled = jax.jit(functools.partial(moe.apply_moe_share, cfg=cfg)) \
        .lower(p, x).compile()
    text = compiled.as_text()
    assert "ragged-dot" in text and "conditional" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
