"""The loop-aware HLO cost parser must agree with cost_analysis() on
unrolled graphs and correctly scale scanned bodies by trip count."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hlo_costs import analyze_hlo

L, M, K = 8, 64, 96


def f_scan(x, w):
    def body(h, wi):
        return jnp.tanh(h @ wi), None
    h, _ = jax.lax.scan(body, x, w)
    return h


def f_unroll(x, w):
    h = x
    for i in range(L):
        h = jnp.tanh(h @ w[i])
    return h


@pytest.fixture(scope="module")
def compiled_pair():
    x = jax.ShapeDtypeStruct((M, K), jnp.float32)
    w = jax.ShapeDtypeStruct((L, K, K), jnp.float32)
    return {name: jax.jit(f).lower(x, w).compile()
            for name, f in [("scan", f_scan), ("unroll", f_unroll)]}


def test_parsed_flops_match_analytic(compiled_pair):
    want = 2 * M * K * K * L
    for name, comp in compiled_pair.items():
        got = analyze_hlo(comp.as_text()).flops
        assert got == pytest.approx(want, rel=0.01), name


def test_parsed_flops_match_cost_analysis_on_unrolled(compiled_pair):
    comp = compiled_pair["unroll"]
    ca = comp.cost_analysis()["flops"]
    got = analyze_hlo(comp.as_text()).flops
    assert got == pytest.approx(ca, rel=0.05)


def test_scan_trip_count_detected(compiled_pair):
    costs = analyze_hlo(compiled_pair["scan"].as_text())
    assert list(costs.while_trips.values()) == [L]


def test_hbm_bytes_consistent_across_loop_forms(compiled_pair):
    a = analyze_hlo(compiled_pair["scan"].as_text()).hbm_bytes
    b = analyze_hlo(compiled_pair["unroll"].as_text()).hbm_bytes
    assert a == pytest.approx(b, rel=0.35)  # same math, similar traffic


def test_nested_scan_multiplicity():
    def f(x, w):
        def outer(h, wi):
            def inner(h2, _):
                return jnp.tanh(h2 @ wi), None
            h2, _ = jax.lax.scan(inner, h, None, length=3)
            return h2, None
        h, _ = jax.lax.scan(outer, x, w)
        return h

    x = jax.ShapeDtypeStruct((32, 32), jnp.float32)
    w = jax.ShapeDtypeStruct((4, 32, 32), jnp.float32)
    comp = jax.jit(f).lower(x, w).compile()
    got = analyze_hlo(comp.as_text()).flops
    want = 2 * 32 * 32 * 32 * 4 * 3
    assert got == pytest.approx(want, rel=0.01)


def test_collective_bytes_counted():
    import os
    import subprocess
    import sys
    # needs >1 device -> subprocess with forced host device count
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
import jax, jax.numpy as jnp, sys
sys.path.insert(0, "src")
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.hlo_costs import analyze_hlo
mesh = jax.make_mesh((4,), ("data",))
def f(x):
    return x.sum(axis=0)
xs = jax.ShapeDtypeStruct((64, 128), jnp.float32)
sh = NamedSharding(mesh, P("data", None))
comp = jax.jit(f, in_shardings=sh, out_shardings=NamedSharding(mesh, P())).lower(xs).compile()
c = analyze_hlo(comp.as_text())
assert c.collective_bytes > 0, c
print("COLLECTIVE_OK", c.collective_bytes)
"""
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd=os.path.dirname(os.path.dirname(__file__)),
                         timeout=120)
    assert "COLLECTIVE_OK" in out.stdout, out.stdout + out.stderr
