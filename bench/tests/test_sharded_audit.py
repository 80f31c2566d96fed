"""The four-chip audit cell at test size, on four virtual CPU devices,
and the readers of its per-layer metrics."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from bench import harness
from bench.tests.conftest import ROOT
from bench.trace.reduce import TraceSummary

_SCRIPT = textwrap.dedent("""
    import json, os, sys, time
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["JAX_PLATFORMS"] = "cpu"
    root = sys.argv[1]
    sys.path[:0] = [root, os.path.join(root, "src")]
    import numpy as np
    from bench import harness

    cell = harness.load_cell("static4_a1", harness.ROOT)
    # N = 2e4 extents, L = 10 N: l = 10 units, as at full size
    cell.config = dict(cell.config, n_extents=20000, n_sub=10000,
                       length=200000)
    seed = 2**33 + 5
    for trace in (False, True):
        line = harness.execute(cell, seed, 0.3, trace,
                               t_start=time.perf_counter(),
                               require_tpu=False)
        print("LINE", json.dumps(line), flush=True)

    # a delivery that loses one chip's rows is not correct
    driver = cell.driver()
    deliver = driver.ShardedAudit.deliver
    driver.ShardedAudit.deliver = lambda self, p: deliver(self, p)[1:]
    cell.driver = lambda: driver
    line = harness.execute(cell, seed, 0.3, False,
                           t_start=time.perf_counter(), require_tpu=False)
    print("FAULT", json.dumps(line), flush=True)

    # the control, the reference on float32 bounds in the matcher's place,
    # at bounds past 2**24 as at full size (L = 1.7e9, l = 1e4 units here)
    cell = harness.load_cell("static4_a1", harness.ROOT)
    cell.config = dict(cell.config, n_extents=172672, n_sub=86336,
                       length=1726720000)
    line = harness.execute(cell, seed, 0.3, False,
                           t_start=time.perf_counter(), require_tpu=False,
                           system="control")
    print("CONTROL", json.dumps(line), flush=True)
""")


@pytest.fixture(scope="module")
def lines():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _SCRIPT, str(ROOT)],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = {}
    for row in proc.stdout.splitlines():
        tag, _, body = row.partition(" ")
        if tag in ("LINE", "FAULT", "CONTROL"):
            out.setdefault(tag, []).append(json.loads(body))
    return out


def test_cell_runs_correct_on_four_devices(lines):
    plain, traced = lines["LINE"]
    for line in (plain, traced):
        assert line["correct"] is True and line["failed"] == 0
        assert line["attempted"] > 0
        assert {c["value"] for c in line["checks"].values()} == {0}
    assert set(plain["metrics"]) == {"setup_s", "audit_s"}
    # on the CPU the trace has no TPU planes: the device metrics are left
    # out, the program's spans are read
    assert {"probe_ms", "emit_ms", "pairs_d2h_ms"} <= set(traced["metrics"])


def test_lost_rows_are_not_correct(lines):
    line, = lines["FAULT"]
    assert line["correct"] is False
    assert line["checks"]["subs_wrong"]["value"] > 0


def test_control_is_not_correct(lines):
    line, = lines["CONTROL"]
    assert line["correct"] is False and line["attempted"] > 0
    assert line["checks"]["subs_wrong"]["value"] > 0
    assert line["checks"]["k_diff"]["value"] > 0


def test_hla_sets_repeat_with_the_seed():
    code = textwrap.dedent("""
        import os, sys
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        os.environ["JAX_PLATFORMS"] = "cpu"
        sys.path.insert(0, sys.argv[1])
        import jax, numpy as np
        from bench.traffic import hla, paper
        mesh = jax.make_mesh((4,), ("p",))
        def sets(seed):
            return [[np.asarray(a) for a in s] for s in hla.uniform_sets(
                paper.device_key(seed, 0), 2, 4000, 2000, 1.0, 40000, mesh)]
        big = 2**33 + 12345
        a, b, c = sets(big), sets(big), sets(big + 1)
        s = hla.uniform_sets(paper.device_key(big, 0), 1, 4000, 2000, 1.0,
                             40000, mesh)[0]
        assert all(not x.sharding.is_fully_replicated for x in s)
        assert all(np.array_equal(x, y) for p, q in zip(a, b)
                   for x, y in zip(p, q))
        assert not np.array_equal(a[0][0], c[0][0])
        assert not np.array_equal(a[0][0], a[1][0])
        for s_lo, s_hi, u_lo, u_hi in a:
            assert s_lo.dtype == np.int32 and s_lo.shape == (2000,)
            assert np.all(s_hi - s_lo == 10) and np.all(u_hi - u_lo == 10)
            assert s_lo.min() >= 0 and max(s_hi.max(), u_hi.max()) <= 40000
        print("HLA_OK")
    """)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", code, str(ROOT)], env=env,
                          capture_output=True, text=True, timeout=300)
    assert "HLA_OK" in proc.stdout, proc.stderr[-4000:]


def test_segment_must_be_whole_units():
    from bench.traffic import hla

    assert hla.segment_units(1.0, 1_500_000_000, 150_000_000) == 10
    with pytest.raises(ValueError):
        hla.segment_units(1.0, 1000, 3)


def _run(ops, chips=4, audits=2, counters=None):
    trace = TraceSummary(window_s=2.0, busy_s=1.0, chips=chips, programs={},
                         ops=ops, idle_by_span={})
    return harness.Run(1.0, 2.0, [0.5] * audits, [], dict(counters or {}),
                       trace, {"hbm_bytes_per_s": 819e9})


def _read(name, run):
    cell = harness.load_cell("static4_a1", ROOT)
    return harness.metric_reader(cell, name)(run)


def test_exchange_readers():
    ops = {"jit_a/%all-to-all.1 all-to-all": 0.4,
           "jit_a/%psum.2 all-reduce": 0.4,
           "jit_b/%reduce-scatter.4 reduce-scatter": 0.2,
           "jit_b/%fusion.3 fusion": 5.0}
    run = _run(ops, counters={"exchange_bytes": 4 * 200e9 * 0.05,
                              "ici_bytes_per_s": 200e9})
    # 1.0 s of collectives over four chips, two audits
    assert _read("exchange_ms", run) == pytest.approx(125.0)
    # 0.05 s of least time a chip against 0.25 s of collectives a chip
    assert _read("exchange_roofline", run) == pytest.approx(20.0)
    quiet = _run({"jit_b/%fusion.3 fusion": 5.0})
    assert _read("exchange_ms", quiet) is None
    assert _read("exchange_roofline", quiet) is None
    # an asynchronous collective's halves do not hold its transfer
    halves = dict(ops)
    halves.update({"jit_a/%all-reduce-start.5 all-reduce-start": 0.01,
                   "jit_a/%all-reduce-done.5 all-reduce-done": 0.3})
    assert _read("exchange_ms", _run(halves)) is None


def test_shard_match_roofline_spreads_bytes_over_the_chips():
    run = _run({}, counters={"least_bytes": 4 * 819e9 * 0.01})
    assert _read("shard_match_roofline", run) == pytest.approx(1.0)


def test_sorted_threaded_reference_is_the_reference():
    import numpy as np

    from bench import reference
    from bench.drivers import sharded_audit

    rng = np.random.default_rng(3)
    n, m = 700, 500
    lo = rng.integers(0, 5000, n + m).astype(np.int32)
    hi = lo + rng.integers(0, 40, n + m).astype(np.int32)
    w = reference.weights(m, 11)
    args = (lo[:n], hi[:n], lo[n:], hi[n:])
    plain = reference.reference_summary(*args, w)
    got, order = sharded_audit.reference_summary(*args, w, threads=3)
    assert np.array_equal(got.count, plain.count[order])
    assert np.array_equal(got.wsum, plain.wsum[order])
    assert got.total == plain.total
    # the pairs themselves, relabelled into the same order, agree; a
    # moved pair and an index out of range do not
    s_lo, s_hi, u_lo, u_hi = args
    i, j = np.nonzero((u_lo[None, :] <= s_hi[:, None])
                      & (s_lo[:, None] <= u_hi[None, :]))
    rows = np.stack([i, j], axis=1)
    assert reference.subs_wrong(
        sharded_audit.pairs_summary(rows, order, n, m, w), got) == 0
    bad = rows.copy()
    bad[0, 1] = (bad[0, 1] + 1) % m
    assert reference.subs_wrong(
        sharded_audit.pairs_summary(bad, order, n, m, w), got) > 0
    bad[0, 0] = n
    assert sharded_audit.pairs_summary(bad, order, n, m, w).bad == 1


def test_weights_keep_the_reference_exact_past_2e6_updates():
    """With 32-bit weights the reference's weight sums over 4·10⁶ updates
    pass 2**53 and round; the cell's narrower weights keep them exact."""
    import numpy as np

    from bench import reference
    from bench.drivers import sharded_audit

    m = 1 << 22
    u_lo = np.arange(m, dtype=np.int32)
    u_hi = u_lo.copy()
    s_lo = s_hi = np.asarray([m - 3], np.int32)     # matches update m - 3
    for w, exact in ((np.full(m, 2.0**32 - 1), False),
                     (sharded_audit.weights(m, 5), True)):
        want = reference.reference_summary(s_lo, s_hi, u_lo, u_hi, w)
        got = reference.pairs_summary([0], [m - 3], 1, m, w)
        assert (reference.subs_wrong(got, want) == 0) == exact
    assert sharded_audit.weights(10**8, 5).max() * 10**8 < 2.0**53
