"""Tests of the benchmark itself, on the CPU: ``python -m pytest bench/tests``."""
import os
import sys
from pathlib import Path

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# regions per cell at test size: small enough for the CPU, large enough
# that every audit has pairs to find
TINY = {"static_a0.01": 20000, "static_a100": 4000}


@pytest.fixture
def tiny():
    """``tiny(name)``: the BENCHMARK.json cell ``name`` at test size."""
    from bench import harness

    def make(name):
        cell = harness.load_cell(name, ROOT)
        n = TINY[name]
        cell.config = dict(cell.config, n_extents=n, n_sub=n // 2)
        return cell

    return make


@pytest.fixture
def run_tiny(tiny):
    """``run_tiny(name, **kw)``: one run of a test-size cell on the CPU,
    past the harness's look for a chip; returns the result line."""
    import time

    from bench import harness

    def run(name, seconds=0.5, trace=False, seed=2**33 + 5, **kw):
        return harness.execute(tiny(name), seed, seconds, trace,
                               t_start=time.perf_counter(),
                               require_tpu=False, **kw)

    return run
