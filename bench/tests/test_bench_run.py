"""The command's refusal without a TPU, and the shape of a run's last
lines."""
from __future__ import annotations

import io
import json
import os
import subprocess
import sys

import bench_tiny


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "lfw_device.blur",
         "--seed", str(2**31 + 9), "--seconds", "1", "--trace", "0"],
        cwd=bench_tiny.ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    for line in proc.stdout.splitlines():
        assert not line.lstrip().startswith("{")


def _last_lines(result):
    out, err = io.StringIO(), io.StringIO()
    from bench.harness import print_result
    print_result(result, out, err)
    return json.loads(out.getvalue().splitlines()[-1]), err.getvalue()


def test_last_lines_of_a_run(tmp_path):
    for trace in (False, True):
        res = bench_tiny.run(tmp_path / str(trace), "tiny_device.tiny_blur",
                             trace=trace)
        line, err = _last_lines(res)
        assert list(line)[:5] == ["correct", "attempted", "failed",
                                  "metrics", "device"]
        assert list(line)[-1] == "checks"
        assert line["correct"] is True
        assert line["attempted"] >= line["failed"] == 0
        dev = line["device"]
        assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
        assert dev["platform"] == "cpu" and dev["count"] >= 1
        names = set(line["metrics"])
        if trace:
            # on the CPU no trace is reduced: only counter readers speak,
            # and the remote pool's is silent where no remote op ran
            assert names == {"segment_h2d_bytes_per_entity",
                             "segment_padding_waste", "thread3_busy_share",
                             "native_busy_share"}
        else:
            assert names == {"entities_per_s", "query_p95_ms",
                             "first_entity_p95_ms", "setup_s"}
        for m in line["metrics"].values():
            assert m["value"] > 0 or m["unit"] == "%"
            assert isinstance(m["unit"], str)
        checks = err.strip().splitlines()
        assert len(checks) == len(line["checks"])
        assert all(c.startswith("check ") and "(limit " in c for c in checks)
