"""Paper Figs. 7a / 8a / 8b: wall-clock of BF vs ITM-analogue (rank) vs SBM
as functions of algorithm, N, and the overlapping degree α — plus the
*enumeration* mode (count vs pair reporting, sweep emission vs blocked
all-pairs) and the *d-dimensional* mode (dim-0-then-filter baseline vs
selective-dimension sweep vs bit-matrix AND, DESIGN.md §8).

Methodology follows the paper §5: N extents (half subscriptions), identical
length l = αL/N uniformly placed on L = 1e6; measurements average multiple
runs after a warmup (jit) run.  Scaled to CPU-feasible N (the paper's
asymptotics are the claim under test: SBM polylog growth in N,
α-independence, ≫BF; for enumeration, output-sensitivity: sweep emission
cost ~ K, blocked all-pairs cost ~ n·m; for d-dim, candidate-buffer
sensitivity: selective/bit-matrix ~ K on the tall-thin adversary where the
dim-0 baseline is ~ n·m).

Run standalone with ``python -m benchmarks.matching [--only enumeration]
[--only ddim --ndim 2 --workload tall_thin] [--json PATH]`` or through
``python -m benchmarks.run --only matching``.
"""
from __future__ import annotations

import time
from typing import Callable, List

import jax

from repro.core import (bf_count, bitmatrix_count, bitmatrix_enumerate,
                        enumerate_matches, enumerate_matches_ddim,
                        make_clustered_workload, make_uniform_workload,
                        rank_count, sbm_count, sbm_enumerate,
                        select_dimension)
from repro.core.runtime import round_up_pow2
from repro.core.sweep import sequential_sbm_count_numpy
from repro.data.synthetic import ddm_workload

REPS = 5


def _time(fn: Callable, *args, reps: int = REPS) -> float:
    out = fn(*args)
    jax.block_until_ready(out)       # warmup / compile
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
        jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def _time_min(fn: Callable, *args, reps: int = 15) -> float:
    """Per-call *minimum* after a warmup — the contention-robust estimator
    for the millisecond-scale rows the CI bench gate compares against the
    committed baseline (a mean at that scale is one noisy neighbor away
    from a spurious 2x failure)."""
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def wct_vs_algorithm(rows: List[str]) -> None:
    """Fig. 7a analogue (N scaled to CPU): BF vs rank(ITM) vs SBM, α=100."""
    n = 100_000
    subs, upds = make_uniform_workload(jax.random.PRNGKey(0), n // 2, n // 2,
                                       alpha=100.0)
    k_ref = int(rank_count(subs, upds))
    for name, fn in [
        ("matching_bf_n1e5_a100", lambda: bf_count(subs, upds, block=2048)),
        ("matching_rank_n1e5_a100", lambda: rank_count(subs, upds)),
        ("matching_sbm_n1e5_a100", lambda: sbm_count(subs, upds,
                                                     num_segments=16)),
    ]:
        assert int(fn()) == k_ref
        dt = _time(fn)
        rows.append(f"{name},{dt*1e6:.1f},K={k_ref}")
    # sequential SBM (Algorithm 4, host) — the serial baseline
    t0 = time.perf_counter()
    k = sequential_sbm_count_numpy(subs, upds)
    dt = time.perf_counter() - t0
    assert k == k_ref
    rows.append(f"matching_sbm_sequential_n1e5_a100,{dt*1e6:.1f},K={k}")


def wct_vs_n(rows: List[str]) -> None:
    """Fig. 8a analogue: SBM & rank vs N (polylog growth claim)."""
    for n in (10_000, 100_000, 1_000_000):
        subs, upds = make_uniform_workload(jax.random.PRNGKey(1), n // 2,
                                           n // 2, alpha=100.0)
        dt_sbm = _time(lambda: sbm_count(subs, upds, num_segments=16))
        dt_rank = _time(lambda: rank_count(subs, upds))
        rows.append(f"matching_sbm_n{n},{dt_sbm*1e6:.1f},")
        rows.append(f"matching_rank_n{n},{dt_rank*1e6:.1f},")


def wct_vs_alpha(rows: List[str]) -> None:
    """Fig. 8b analogue: SBM WCT vs α (α-independence claim; rank too)."""
    n = 1_000_000
    for alpha in (0.01, 1.0, 100.0):
        subs, upds = make_uniform_workload(jax.random.PRNGKey(2), n // 2,
                                           n // 2, alpha=alpha)
        dt_sbm = _time(lambda: sbm_count(subs, upds, num_segments=16))
        dt_rank = _time(lambda: rank_count(subs, upds))
        a = str(alpha).replace(".", "p")
        rows.append(f"matching_sbm_a{a},{dt_sbm*1e6:.1f},")
        rows.append(f"matching_rank_a{a},{dt_rank*1e6:.1f},")


def scan_impl_sweep(rows: List[str]) -> None:
    """Beyond-paper: two-level (Fig. 5) vs Blelloch vs monolithic scan."""
    n = 1_000_000
    subs, upds = make_uniform_workload(jax.random.PRNGKey(3), n // 2, n // 2,
                                       alpha=100.0)
    for impl in ("two_level", "blelloch", "xla"):
        dt = _time(lambda impl=impl: sbm_count(subs, upds, num_segments=16,
                                               scan_impl=impl))
        rows.append(f"matching_sbm_scan_{impl}_n1e6,{dt*1e6:.1f},")


def enumeration(rows: List[str]) -> None:
    """Count vs *enumerate* throughput: sweep emission vs blocked all-pairs.

    The sweep path is output-sensitive (O((n+m)log(n+m) + K)); blocked
    all-pairs enumeration is O(n·m) regardless of K.  The blocked reference
    is only run at n = m = 1e5 (its 1e10-cell mask is already ~10^3× the
    sweep's work); at n = m = 1e6 it would be 1e12 cells, so only the sweep
    rows are reported there.
    """
    workloads = [
        # (tag, maker, N, alpha, include_blocked)
        ("uniform_n1e5_a100", make_uniform_workload, 100_000, 100.0, True),
        ("clustered_n1e5_a10", make_clustered_workload, 100_000, 10.0, False),
        ("uniform_n1e6_a1", make_uniform_workload, 1_000_000, 1.0, False),
    ]
    for tag, maker, n, alpha, include_blocked in workloads:
        subs, upds = maker(jax.random.PRNGKey(4), n // 2, n // 2, alpha=alpha)
        k = int(sbm_count(subs, upds, num_segments=16))
        cap = round_up_pow2(k)
        dt_count = _time(lambda: sbm_count(subs, upds, num_segments=16))
        pairs, cnt = sbm_enumerate(subs, upds, max_pairs=cap)
        assert int(cnt) == k, (tag, int(cnt), k)
        dt_sweep = _time(lambda: sbm_enumerate(subs, upds, max_pairs=cap))
        rows.append(f"enum_count_{tag},{dt_count*1e6:.1f},K={k}")
        rows.append(f"enum_sweep_{tag},{dt_sweep*1e6:.1f},K={k}")
        if include_blocked:
            # The O(n·m) oracle takes minutes per call: the correctness
            # check doubles as the compile/warmup run, then time one rep.
            _, cnt_b = jax.block_until_ready(
                enumerate_matches(subs, upds, max_pairs=cap, block=2048))
            assert int(cnt_b) == k, (tag, int(cnt_b), k)
            t0 = time.perf_counter()
            jax.block_until_ready(enumerate_matches(subs, upds,
                                                    max_pairs=cap, block=2048))
            dt_blocked = time.perf_counter() - t0
            rows.append(f"enum_blocked_{tag},{dt_blocked*1e6:.1f},K={k}")
            rows.append(f"enum_speedup_{tag},"
                        f"{dt_blocked/dt_sweep:.1f},sweep_vs_blocked_x")


def ddim(rows: List[str], *, ndim: int = 2,
         workload: str = "tall_thin") -> None:
    """d-dim engines head-to-head (DESIGN.md §8): the dim-0-then-filter
    baseline vs the selective-dimension sweep vs the bit-matrix AND.

    On the tall-thin adversary the baseline's candidate buffer is the full
    dim-0 match count (n·m — every pair overlaps in the wide dimension)
    while selective/bit-matrix buffers scale with the final K, so the
    head-to-head runs at a scale where the baseline's O(n·m) buffer still
    fits; a second, larger cell reports the K-proportional engines alone
    (the baseline would need gigabytes there).
    """
    tag = f"d{ndim}_{workload}"
    n = 8_192
    subs, upds = ddm_workload(workload, jax.random.PRNGKey(5), n // 2,
                              n // 2, alpha=10.0, d=ndim)
    gen, counts = select_dimension(subs, upds)
    k = int(bitmatrix_count(subs, upds))
    cap0 = round_up_pow2(max(counts[0], 1))
    cap_gen = round_up_pow2(max(counts[gen], 1))
    cap_k = round_up_pow2(max(k, 1))

    pairs_base, cnt_base = enumerate_matches_ddim(
        subs, upds, max_pairs=cap0, method="sweep", generator_dim=0)
    pairs_sel, cnt_sel = enumerate_matches_ddim(
        subs, upds, max_pairs=cap_gen, method="sweep")
    pairs_bm, cnt_bm = bitmatrix_enumerate(subs, upds, max_pairs=cap_k)
    assert int(cnt_base) == int(cnt_sel) == int(cnt_bm) == k, (
        int(cnt_base), int(cnt_sel), int(cnt_bm), k)

    dt_base = _time(lambda: enumerate_matches_ddim(
        subs, upds, max_pairs=cap0, method="sweep", generator_dim=0))
    dt_sel = _time(lambda: enumerate_matches_ddim(
        subs, upds, max_pairs=cap_gen, method="sweep"))
    dt_bm = _time(lambda: bitmatrix_enumerate(subs, upds, max_pairs=cap_k))
    rows.append(f"ddim_baseline_dim0_{tag}_n{n},{dt_base*1e6:.1f},"
                f"K={k};cap={cap0}")
    rows.append(f"ddim_selective_{tag}_n{n},{dt_sel*1e6:.1f},"
                f"K={k};cap={cap_gen};gen={gen}")
    rows.append(f"ddim_bitmatrix_{tag}_n{n},{dt_bm*1e6:.1f},K={k};cap={cap_k}")
    rows.append(f"ddim_speedup_{tag}_n{n},"
                f"{dt_base/min(dt_sel, dt_bm):.1f},best_vs_dim0_x")

    # the larger cell: K-proportional engines only (count form for the bit
    # matrix — its packed words stay 32x smaller than any boolean mask)
    n = 65_536
    subs, upds = ddm_workload(workload, jax.random.PRNGKey(6), n // 2,
                              n // 2, alpha=10.0, d=ndim)
    gen, counts = select_dimension(subs, upds)
    cap_gen = round_up_pow2(max(counts[gen], 1))
    k = int(bitmatrix_count(subs, upds))
    dt_sel = _time(lambda: enumerate_matches_ddim(
        subs, upds, max_pairs=cap_gen, method="sweep"))
    dt_bmc = _time(lambda: bitmatrix_count(subs, upds))
    rows.append(f"ddim_selective_{tag}_n{n},{dt_sel*1e6:.1f},"
                f"K={k};cap={cap_gen};gen={gen};dim0_cap={counts[0]}")
    rows.append(f"ddim_bitmatrix_count_{tag}_n{n},{dt_bmc*1e6:.1f},K={k}")


def smoke(rows: List[str]) -> None:
    """CI smoke: tiny N through every engine + enumeration, agreement
    asserted — guards the benchmark entry points against silent rot.  It
    is the CPU CI gate, so the Pallas kernel runs in the interpreter."""
    n = 2_000
    subs, upds = make_uniform_workload(jax.random.PRNGKey(0), n // 2, n // 2,
                                       alpha=10.0)
    k = int(sbm_count(subs, upds, num_segments=8))
    assert int(rank_count(subs, upds)) == k
    assert int(bf_count(subs, upds, block=256)) == k
    assert sequential_sbm_count_numpy(subs, upds) == k
    cap = round_up_pow2(k)
    pairs, cnt = sbm_enumerate(subs, upds, max_pairs=cap)
    assert int(cnt) == k
    _, cnt_b = enumerate_matches(subs, upds, max_pairs=cap, block=256)
    assert int(cnt_b) == k
    rows.append(f"matching_smoke_n{n},0,K={k}")
    # warm timings (the agreement pass above compiled everything) — these
    # rows arm the CI bench-regression gate, so they must measure engine
    # runtime, not first-call tracing, with the min-of-N estimator
    # (_time_min) that shrugs off runner contention spikes
    dt_count = _time_min(lambda: sbm_count(subs, upds, num_segments=8))
    dt_enum = _time_min(lambda: sbm_enumerate(subs, upds, max_pairs=cap))
    rows.append(f"matching_smoke_count_n{n},{dt_count*1e6:.1f},")
    rows.append(f"matching_smoke_enum_n{n},{dt_enum*1e6:.1f},")

    # d-dim smoke: every d-dim engine agrees on the tall-thin adversary,
    # with the selective/bit-matrix buffers sized by the final K (the
    # dim-0 candidate count would be n*m/4)
    from repro.core import brute_force_pairs_numpy
    from repro.kernels import sbm_bitmatrix_kernel
    import numpy as np
    n2 = 400
    subs2, upds2 = ddm_workload("tall_thin", jax.random.PRNGKey(1), n2 // 2,
                                n2 // 2, alpha=10.0, d=2)
    want = brute_force_pairs_numpy(subs2, upds2)
    gen, counts = select_dimension(subs2, upds2)
    assert gen != 0 and counts[0] == (n2 // 2) ** 2, (gen, counts)
    cap2 = round_up_pow2(max(counts[gen], 1))
    cap_k = round_up_pow2(max(len(want), 1))
    for method, mp in (("sweep", cap2), ("bitmatrix", cap_k)):
        p, c = enumerate_matches_ddim(subs2, upds2, max_pairs=mp,
                                      method=method)
        got = {(int(i), int(j)) for i, j in np.asarray(p) if i >= 0}
        assert got == want and int(c) == len(want), method
    p, c = sbm_bitmatrix_kernel(subs2, upds2, max_pairs=cap_k,
                                interpret=True)
    got = {(int(i), int(j)) for i, j in np.asarray(p) if i >= 0}
    assert got == want and int(c) == len(want), "bitmatrix kernel"
    rows.append(f"ddim_smoke_talln{n2},0,K={len(want)}")
    dt_sel = _time_min(lambda: enumerate_matches_ddim(subs2, upds2,
                                                      max_pairs=cap2))
    dt_bm = _time_min(lambda: enumerate_matches_ddim(subs2, upds2,
                                                     max_pairs=cap_k,
                                                     method="bitmatrix"))
    rows.append(f"ddim_smoke_selective_n{n2},{dt_sel*1e6:.1f},")
    rows.append(f"ddim_smoke_bitmatrix_n{n2},{dt_bm*1e6:.1f},")

    # runtime executor stats (DESIGN.md §10): the planned paths are
    # probe-seeded, so retries must be 0 on the second identical run, and
    # with the count in the same pow2 ladder bucket the second run must
    # compile nothing new.  Both invariants are asserted here AND emitted
    # as derived counters so benchmarks/check_regression.py re-gates them
    # from the BENCH JSON artifact.
    from repro.core import enumerate_matches_ddim_planned, sbm_enumerate_planned

    def _runtime_row(name, stats):
        ph = stats.phase_seconds
        rows.append(
            f"{name},{sum(ph.values())*1e6:.1f},"
            f"retries={stats.retries};recompiles={stats.recompiles};"
            f"probe_us={ph.get('probe', 0.0)*1e6:.1f};"
            f"emit_us={ph.get('emit', 0.0)*1e6:.1f}")

    _, c1, _ = sbm_enumerate_planned(subs, upds)   # warmup
    _, c2, st = sbm_enumerate_planned(subs, upds)
    assert int(c1) == int(c2) == k
    assert st.retries == 0, f"retry on identical rerun: {st.as_dict()}"
    assert st.recompiles == 0, f"recompile after warmup: {st.as_dict()}"
    _runtime_row(f"runtime_smoke_sweep_n{n}", st)

    _, cd1, _ = enumerate_matches_ddim_planned(subs2, upds2)       # warmup
    _, cd2, std = enumerate_matches_ddim_planned(subs2, upds2)
    assert int(cd1) == int(cd2) == len(want)
    assert std.retries == 0, f"retry on identical rerun: {std.as_dict()}"
    assert std.recompiles == 0, f"recompile after warmup: {std.as_dict()}"
    _runtime_row(f"runtime_smoke_ddim_n{n2}", std)


def run(rows: List[str]) -> None:
    wct_vs_algorithm(rows)
    wct_vs_n(rows)
    wct_vs_alpha(rows)
    scan_impl_sweep(rows)
    enumeration(rows)
    ddim(rows, ndim=2, workload="tall_thin")


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="all",
                    choices=["all", "enumeration", "algorithm", "n", "alpha",
                             "scan", "ddim"])
    ap.add_argument("--ndim", type=int, default=2,
                    help="dimensionality of the --only ddim cell")
    ap.add_argument("--workload", default="tall_thin",
                    choices=["uniform", "clustered", "tall_thin"],
                    help="region placement of the --only ddim cell")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-N CI guard (engine agreement asserted)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows as JSON (the CI bench gate input)")
    args = ap.parse_args()
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    fns = {"all": run, "enumeration": enumeration,
           "algorithm": wct_vs_algorithm, "n": wct_vs_n,
           "alpha": wct_vs_alpha, "scan": scan_impl_sweep,
           "ddim": lambda rows: ddim(rows, ndim=args.ndim,
                                     workload=args.workload)}
    rows: List[str] = []
    print("name,us_per_call,derived")
    (smoke if args.smoke else fns[args.only])(rows)
    for r in rows:
        print(r, flush=True)
    if args.json:
        from benchmarks._bench_json import write_json
        write_json(args.json, rows, meta={"module": "matching",
                                          "smoke": args.smoke})
