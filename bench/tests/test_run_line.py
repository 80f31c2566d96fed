"""The run's last line, and what happens with no chip."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench.tests.conftest import ROOT

KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def _cli(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "static_a0.01", "--seed",
         "3000000017", "--seconds", "1", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_without_a_result():
    proc = _cli(ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "not a TPU" in proc.stderr


def test_benchmark_alone_exits_without_a_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _cli(tmp_path, "--trace", "1")
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


@pytest.mark.parametrize("trace", [False, True])
def test_line_has_the_contract_keys(run_tiny, trace):
    line = run_tiny("static_a0.01", trace=trace)
    keys = list(line)
    assert keys[:5] == KEYS and keys[-1] == "checks"
    assert set(keys) <= set(KEYS) | {"breakdown", "checks"}
    assert ("breakdown" in keys) == trace
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    want = ({"probe_ms", "emit_ms", "pairs_d2h_ms"}
            if trace else {"setup_s", "audit_s"})
    assert want <= set(line["metrics"])
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"}
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"}
    json.dumps(line)
