"""GroupBatcher: batched serving must equal per-request greedy decoding."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.distributed.sharding import REPLICATED
from repro.models import get_model
from repro.serving import greedy_generate
from repro.serving.batcher import GroupBatcher


@pytest.fixture(scope="module")
def setup():
    cfg = get_arch("qwen3-0.6b", reduced=True)
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    return cfg, api, params


def test_batched_equals_sequential(setup):
    cfg, api, params = setup
    b = GroupBatcher(api, params, group_size=4, max_new_default=5)
    prompts = [np.arange(1, 9) + i for i in range(6)]
    reqs = [b.submit(p) for p in prompts]
    b.run_until_idle()
    for p, r in zip(prompts, reqs):
        got = r.result(timeout=5)
        want = greedy_generate(
            api, params,
            {"tokens": jnp.asarray(p)[None].astype(jnp.int32)},
            steps=5, sh=REPLICATED)
        np.testing.assert_array_equal(got, np.asarray(want)[0])
    assert b.groups_run == 2  # 6 requests / group_size 4


def test_mixed_prompt_lengths_grouped(setup):
    cfg, api, params = setup
    b = GroupBatcher(api, params, group_size=8, max_new_default=3)
    reqs = ([b.submit(np.arange(1, 7)) for _ in range(3)]
            + [b.submit(np.arange(1, 11)) for _ in range(3)])
    b.run_until_idle()
    for r in reqs:
        assert len(r.result(timeout=5)) == 3
    assert b.groups_run >= 2  # two length classes cannot share a group


def test_eos_frees_early(setup):
    cfg, api, params = setup
    b = GroupBatcher(api, params, group_size=2, max_new_default=8)
    # find what the first generated token is, then use it as eos
    probe = b.submit(np.arange(1, 9))
    b.run_until_idle()
    first = int(probe.result()[0])
    b2 = GroupBatcher(api, params, group_size=2, max_new_default=8)
    r = b2.submit(np.arange(1, 9), eos_id=first)
    b2.run_until_idle()
    assert len(r.result()) == 1  # stopped at EOS immediately


def test_elastic_remesh_roundtrip():
    import subprocess, sys, os
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
import sys; sys.path.insert(0, "src")
import jax, jax.numpy as jnp, numpy as np
from repro.distributed.elastic import remesh_tree, shrink_batch_for_mesh
from repro.distributed.sharding import default_rules
tree = {"w": jnp.arange(64.0).reshape(8, 8), "b": jnp.arange(8.0)}
axes = {"w": ("embed", "ff"), "b": (None,)}
m8 = jax.make_mesh((2, 4), ("data", "model"))
m4 = jax.make_mesh((1, 4), ("data", "model"))
t8 = remesh_tree(tree, axes, m8, default_rules())
t4 = remesh_tree(t8, axes, m4, default_rules())
np.testing.assert_array_equal(np.asarray(t4["w"]), np.asarray(tree["w"]))
assert shrink_batch_for_mesh(100, m8) == 100
assert shrink_batch_for_mesh(7, m8) == 6
print("REMESH_OK")
"""
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120,
                         cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert "REMESH_OK" in out.stdout, out.stdout + out.stderr
