"""The comparison that decides ``correct``.

What a run's window produced is held to the guarantee the configuration
states: every acknowledged query returns, exactly once, every entity its
metadata predicate selects, with no entity failed, and the pixels of
each returned entity are what the plain reference (``reference.py``,
float32 on the host CPU, or on the cell's first chip for a
configuration's own op whose reference says ``ON_DEVICE``) computes from
the same input.

Numbers, each with its limit in ``bench/limits/<cell>.json``:

- ``failed_queries``: queries that raised, timed out, or returned a
  failed entity (limit 0);
- ``wrong_selections``: queries whose returned entities are not exactly
  the ones the predicate selects on the seeded metadata (limit 0);
- ``wrong_deliveries``: queries whose ``on_entity`` calls did not deliver
  each returned entity exactly once (limit 0);
- ``max_abs_err``: the largest absolute difference from the reference
  over the compared entities of queries whose output is continuous;
- ``mismatch_share``: over the compared entities of queries whose output
  holds a discrete choice (a threshold, a detector's argmax), the share
  of output elements that differ from the reference by more than
  ``ELEMENT_TOL``.
"""
from __future__ import annotations

import collections
import json

import numpy as np

from bench import reference

# an element "differs" past this: float32 rounding stays orders below it,
# a bfloat16 rounding of a value in [0.05, 1] lands above it almost always
ELEMENT_TOL = 1e-4


def output_numbers(checked, faces: np.ndarray, control_device=None, *,
                   bench_dir: str = reference.BENCH, op_data=None,
                   chip=None) -> dict:
    """``max_abs_err`` and ``mismatch_share`` of ``checked``: a list of
    (operations, [(dataset index, output array)]).  With
    ``control_device`` the outputs are replaced by the control: the
    reference computed in bfloat16 on that device.  The reference itself
    runs in float32 on the host CPU, a configuration's own op (its spec
    and weights in ``op_data``) on ``chip`` where its reference file says
    ``ON_DEVICE``; the control runs every op on ``control_device``."""
    import jax
    import jax.numpy as jnp
    cpu = jax.devices("cpu")[0]
    by_ops = collections.defaultdict(list)
    for ops, items in checked:
        by_ops[_key(ops)].append((ops, items))
    err = None
    diff = total = 0
    for groups in by_ops.values():
        ops = groups[0][0]
        items = [it for _, its in groups for it in its]
        if not items:
            continue
        idx = np.array([i for i, _ in items])
        kw = dict(bench_dir=bench_dir, op_data=op_data)
        want = reference.run(ops, faces[idx], cpu, jnp.float32, chip=chip,
                             **kw)
        if control_device is not None:
            got = reference.run(ops, faces[idx], control_device,
                                jnp.bfloat16, **kw)
        else:
            got = [np.asarray(a) for _, a in items]
        for g, w in zip(got, want):
            g = np.asarray(g, np.float64)
            d = (np.abs(g - w) if g.shape == w.shape
                 else np.full(w.shape, np.inf))
            if reference.is_discrete(ops, bench_dir):
                diff += int(np.count_nonzero(~(d <= ELEMENT_TOL)))
                total += d.size
            else:
                m = float(np.max(d)) if d.size else 0.0
                err = m if err is None else max(err, m)
    out = {}
    if err is not None:
        out["max_abs_err"] = err
    if total:
        out["mismatch_share"] = diff / total
    return out


def _key(ops) -> str:
    return json.dumps(ops, sort_keys=True)


def decide(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}): correct when every number
    is at or under its limit.  A number with no limit is an error of the
    benchmark, not of the program."""
    table = {}
    ok = True
    for name, value in numbers.items():
        if name not in limits:
            raise KeyError(f"no limit for {name!r}; limits: {sorted(limits)}")
        limit = limits[name]
        table[name] = {"value": value, "limit": limit}
        ok = ok and value <= limit
    return ok, table
