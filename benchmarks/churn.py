"""Churn workload — the dynamic DDM setting (Pan et al.; arXiv:1911.03456).

A federation registers N regions once and then *moves* a fraction of them
every step.  The stateless sweep pays O((n+m)·log(n+m) + K) per step no
matter how small the change; the incremental engine
(:mod:`repro.core.incremental`) pays O(b·log b + n + m + K_changed) for b
moved regions.  This benchmark measures both:

* ``churn_rebuild_single_move`` — one region moves, the match state is
  rebuilt from scratch (cache dropped → stateless sweep enumeration);
  this is also the rebuild reference for the fraction sweep — its cost is
  independent of how many regions moved;
* ``churn_delta_single_move`` — the same move served by ``flush()`` delta
  rematching against the persistent index;
* ``churn_delta_<dist>_f*`` — whole-step delta cost at move fractions f
  per step, on the paper-§5 uniform and clustered workloads (compare
  each against the rebuild reference to locate the crossover);
* ``churn_small_batch_*`` — the same single-move flush under the blocked
  endpoint index vs the legacy flat splice (``index_impl="flat"``); the
  speedup row carries an absolute ``min_required`` floor at acceptance
  scale (DESIGN.md §13);
* ``churn_latency_p*`` — p50/p95/p99 flush latency through the broker
  frontend's rolling window (``--latency`` also writes BENCH_pr10.json).

Region sets follow the paper §5 (identical lengths l = αL/N, uniform or
16-cluster placement on L = 1e6).  Run standalone with
``PYTHONPATH=src python -m benchmarks.churn [--smoke]`` or through
``python -m benchmarks.run --only churn``.  ``--smoke`` is the CI guard:
tiny N, one rep, asserts delta == rebuild exactly.
"""
from __future__ import annotations

import time
from typing import List

import jax
import numpy as np

from repro.core import DDMService, make_clustered_workload, make_uniform_workload
from repro.testing.oracles import service_pairs

N_FULL = 100_000          # n = m = 1e5 (the acceptance-criterion scale)
N_SMOKE = 400
ALPHA = 1.0               # K ≈ N·α/2 keeps the python pair set tractable


def _build_service(maker, n_each: int, alpha: float, seed: int) -> DDMService:
    subs, upds = maker(jax.random.PRNGKey(seed), n_each, n_each, alpha=alpha)
    svc = DDMService(dims=1, capacity=2 * n_each)
    s_lo = np.asarray(subs.lo)
    s_hi = np.asarray(subs.hi)
    u_lo = np.asarray(upds.lo)
    u_hi = np.asarray(upds.hi)
    for i in range(n_each):
        svc.register("sub", float(s_lo[i]), float(s_hi[i]))
        svc.register("upd", float(u_lo[i]), float(u_hi[i]))
    return svc


def _build_service_bulk(maker, n_each: int, alpha: float, seed: int,
                        index_impl: str = "blocked") -> DDMService:
    """Register via the bulk API from a deliberately tiny initial capacity:
    elastic table growth (no capacity RuntimeError at any scale) is part
    of what the bulk axis measures."""
    subs, upds = maker(jax.random.PRNGKey(seed), n_each, n_each, alpha=alpha)
    svc = DDMService(dims=1, capacity=16, index_impl=index_impl)
    svc.register("sub", np.asarray(subs.lo), np.asarray(subs.hi))
    svc.register("upd", np.asarray(upds.lo), np.asarray(upds.hi))
    assert int(svc._subs.live.sum()) == n_each
    assert int(svc._upds.live.sum()) == n_each
    return svc


def _random_move(svc: DDMService, rng, length=1.0e6, seg=10.0):
    """Move one random live update region to a fresh uniform spot."""
    ids = svc._upds.live_ids()
    rid = int(ids[rng.randint(ids.size)])
    lo = float(rng.uniform(0, length - seg))
    svc.move("upd", rid, [lo], [lo + seg])
    return rid


def single_move(rows: List[str], n_each: int, reps: int) -> None:
    """One-region move: delta rematch vs full rebuild (same service state).

    Reports the per-rep *minimum* — these rows feed the CI bench gate,
    and at millisecond scale a mean is one contention spike away from a
    spurious 2x regression.
    """
    svc = _build_service(make_uniform_workload, n_each, ALPHA, seed=0)
    svc.all_pairs()                       # warm cache + jit
    rng = np.random.RandomState(1)

    t_delta = float("inf")
    for _ in range(reps):
        _random_move(svc, rng)
        t0 = time.perf_counter()
        svc.flush()                       # delta rematch, cache updated
        t_delta = min(t_delta, time.perf_counter() - t0)

    t_rebuild = float("inf")
    for _ in range(reps):
        _random_move(svc, rng)
        svc.invalidate_cache()            # force the stateless rebuild path
        t0 = time.perf_counter()
        svc.all_pairs()
        t_rebuild = min(t_rebuild, time.perf_counter() - t0)

    k = svc.match_count()
    tag = f"n{n_each:_}".replace("_", "")
    rows.append(f"churn_delta_single_move_{tag},{t_delta*1e6:.1f},K={k}")
    rows.append(f"churn_rebuild_single_move_{tag},{t_rebuild*1e6:.1f},K={k}")
    rows.append(f"churn_single_move_speedup_{tag},"
                f"{t_rebuild/t_delta:.1f},delta_vs_rebuild_x")


def move_fraction_sweep(rows: List[str], n_each: int, reps: int) -> None:
    """Whole-step cost vs move fraction, uniform + clustered region sets.

    Per-rep *minimum*, like :func:`single_move` — any row a ``--json``
    dump can feed the CI gate must be contention-robust.
    """
    for tag, maker in (("uniform", make_uniform_workload),
                       ("clustered", make_clustered_workload)):
        svc = _build_service(maker, n_each, ALPHA, seed=2)
        svc.all_pairs()
        rng = np.random.RandomState(3)
        for frac in (0.0001, 0.001, 0.01):
            b = max(1, int(frac * 2 * n_each))
            t = float("inf")
            for _ in range(reps):
                for _ in range(b):
                    _random_move(svc, rng)
                t0 = time.perf_counter()
                svc.flush()
                t = min(t, time.perf_counter() - t0)
            f = str(frac).replace(".", "p")
            rows.append(f"churn_delta_{tag}_f{f},{t*1e6:.1f},b={b}")


def small_batch(rows: List[str], n_each: int, reps: int) -> float:
    """The PR-10 acceptance axis: single-region move flush, blocked index
    vs the legacy flat splice (``index_impl="flat"``), twin services on
    identical seeds/moves.

    Emits ``churn_small_batch_{flat,blocked}_*`` timings (per-rep
    minimum, CI-gate convention) and a ``churn_small_batch_speedup_*``
    ratio row.  At the acceptance scale (n = m = 1e5) the speedup row
    carries ``min_required=5.0`` — an *absolute* floor the bench gate
    enforces in every run, so the flat-splice regression can't silently
    return.  Below that scale the fixed per-block Python overhead eats
    the win (the analytic model's crossover — see
    :func:`repro.perf.analytic.churn_flush_crossover`), so smoke-scale
    rows stay informational.
    """
    times = {}
    blocks = {}
    deltas = {}
    for impl in ("flat", "blocked"):
        svc = _build_service_bulk(make_uniform_workload, n_each, ALPHA,
                                  seed=11, index_impl=impl)
        svc.all_pairs()                   # warm cache + jit
        rng = np.random.RandomState(42)
        t = float("inf")
        log = []
        for _ in range(reps):
            _random_move(svc, rng)
            t0 = time.perf_counter()
            delta = svc.flush()
            t = min(t, time.perf_counter() - t0)
            log.append((frozenset(delta.added), frozenset(delta.removed)))
        times[impl] = t
        deltas[impl] = log
        surgery = svc._index.last_batch_stats
        blocks[impl] = int(surgery.blocks_touched) if surgery else 0
    assert deltas["flat"] == deltas["blocked"], \
        "small-batch deltas diverged between index impls"
    tag = f"n{n_each}"
    rows.append(f"churn_small_batch_flat_{tag},{times['flat']*1e6:.1f},b=1")
    rows.append(f"churn_small_batch_blocked_{tag},"
                f"{times['blocked']*1e6:.1f},"
                f"b=1;blocks_touched={blocks['blocked']}")
    floor = ";min_required=5.0" if n_each >= N_FULL else ""
    speedup = times["flat"] / times["blocked"]
    rows.append(f"churn_small_batch_speedup_{tag},{speedup:.1f},"
                f"flat_vs_blocked_x{floor}")
    return speedup


def latency(rows: List[str], n_each: int, flushes: int) -> None:
    """Flush-latency distribution through the broker frontend.

    Single-region moves through a :class:`repro.frontend.broker.Broker`
    session; p50/p95/p99 come from the session's rolling flush-latency
    window (the same ``flush_p*_us`` surfaces operators read), not from
    a mean — tail latency is what the blocked index's bounded surgery
    is supposed to protect.
    """
    from repro.frontend.broker import Broker
    subs, upds = make_uniform_workload(jax.random.PRNGKey(11), n_each,
                                       n_each, alpha=ALPHA)
    with Broker() as broker:
        sess = broker.create_session("churn-bench", dims=1, capacity=16)
        sess.register("sub", np.asarray(subs.lo), np.asarray(subs.hi))
        sess.register("upd", np.asarray(upds.lo), np.asarray(upds.hi))
        sess.flush()
        svc = sess.service
        svc.all_pairs()                   # warm cache + jit
        rng = np.random.RandomState(42)
        for _ in range(flushes):
            _random_move(svc, rng)
            sess.flush()
        st = sess.stats()
        tag = f"n{n_each}"
        for q in ("p50", "p95", "p99"):
            rows.append(f"churn_latency_{q}_{tag},"
                        f"{st[f'flush_{q}_us']:.1f},flushes={flushes}")


def _model_crossover_audit(n_each: int, measured_speedup: float) -> None:
    """The analytic cost model must agree with the measured regime.

    Structure checks (any scale): blocked splice beats flat at b = 1,
    the two coincide once the delta spans every block (the bulk
    fallback), and the crossover sits strictly between.  At acceptance
    scale the measured small-batch speedup must land on the model's
    winning side of the crossover.
    """
    from repro.perf.analytic import churn_flush_crossover, churn_splice_cost
    n_endpoints = 4 * n_each              # 2 sides x 2 endpoints each
    flat_1 = churn_splice_cost(n_endpoints, 1, impl="flat")
    blocked_1 = churn_splice_cost(n_endpoints, 1)
    assert blocked_1 < flat_1, (blocked_1, flat_1)
    assert churn_splice_cost(n_endpoints, n_endpoints) == \
        churn_splice_cost(n_endpoints, n_endpoints, impl="flat"), \
        "bulk fallback must coincide with the flat cost"
    cross = churn_flush_crossover(n_endpoints)
    assert 1.0 <= cross < n_endpoints, cross
    if n_each >= N_FULL:
        assert measured_speedup > 1.0, (
            f"model puts b=1 below the crossover ({cross:.0f}) but the "
            f"measured speedup is {measured_speedup:.2f}x")


def bulk_sweep(rows: List[str], n_each: int, bulk_sizes, reps: int) -> None:
    """The bulk-churn axis: b-region move batches through the bulk API.

    For each b, one flush is timed with the stacked vectorized rematch
    (``delta_impl="vector"``: dense mask / fused jit / sort-based by b·m)
    and one with the pre-vectorization per-region loop — the speedup row
    is the tentpole acceptance number.  Per-rep minimum, like
    :func:`single_move`: these rows feed the CI bench gate.
    """
    seg = ALPHA * 1.0e6 / (2 * n_each)
    svc = _build_service_bulk(make_uniform_workload, n_each, ALPHA, seed=7)
    svc.all_pairs()                       # warm cache + jit
    for b in bulk_sizes:
        times = {}
        # sub-100ms flushes at small b drown in scheduler noise on a
        # busy host; min-of-many keeps the speedup row stable where
        # reps are nearly free
        b_reps = max(reps, 25) if b <= 128 else reps
        for impl in ("vector", "loop"):
            svc._index.delta_impl = impl
            rng = np.random.RandomState(1000 + b)
            t = float("inf")
            for _ in range(b_reps):
                rids = rng.choice(svc._upds.live_ids(), size=b, replace=False)
                lo = rng.uniform(0, 1.0e6 - seg, b).astype(np.float32)
                svc.move("upd", rids, lo, lo + np.float32(seg))
                t0 = time.perf_counter()
                svc.flush()
                t = min(t, time.perf_counter() - t0)
            times[impl] = t
            rows.append(f"churn_bulk_{impl}_b{b}_n{n_each},{t*1e6:.1f},b={b}")
        rows.append(f"churn_bulk_speedup_b{b}_n{n_each},"
                    f"{times['loop']/times['vector']:.1f},vector_vs_loop_x")
    svc._index.delta_impl = "vector"


def bulk_smoke(rows: List[str]) -> None:
    """CI bulk guard: vector and loop deltas must be IDENTICAL on the same
    batch (twin services, same seed), and equal to the stateless-sweep
    set difference; the pairs= rows gate engine behavior in CI."""
    twins = {impl: _build_service_bulk(make_uniform_workload, N_SMOKE, 10.0,
                                       seed=7)
             for impl in ("vector", "loop")}
    for impl, svc in twins.items():
        svc._index.delta_impl = impl
        svc.all_pairs()
    seg = 10.0 * 1.0e6 / (2 * N_SMOKE)
    for b in (1, 16, 128):
        rng = np.random.RandomState(1000 + b)
        rids = rng.choice(twins["vector"]._upds.live_ids(), size=b,
                          replace=False)
        lo = rng.uniform(0, 1.0e6 - seg, b).astype(np.float32)
        deltas = {}
        for impl, svc in twins.items():
            before = svc.all_pairs()
            svc.move("upd", rids, lo, lo + np.float32(seg))
            deltas[impl] = svc.flush()
            after = svc.all_pairs()
            assert deltas[impl].added == after - before, (impl, b)
            assert deltas[impl].removed == before - after, (impl, b)
            svc.invalidate_cache()
            assert svc.all_pairs() == after, \
                f"{impl} b={b}: delta cache drifted from sweep rebuild"
            assert after == service_pairs(svc), \
                f"{impl} b={b}: delta cache drifted from host oracle"
        assert deltas["vector"] == deltas["loop"], \
            f"b={b}: vectorized delta != per-region loop delta"
        d = deltas["vector"]
        rows.append(f"churn_bulk_smoke_b{b},0,"
                    f"pairs={len(d.added) + len(d.removed)}")
    # regime audit: every bulk rematch above went through the planner's
    # regime selection and recorded itself; the executor paths must have
    # stayed retry-free (the derived counter re-gates this in CI)
    st = twins["vector"].stats()
    assert st["retries"] == 0, st
    rows.append(f"churn_bulk_smoke_runtime,0,retries={st['retries']};"
                f"regimes={'+'.join(sorted(st['by_regime']))}")
    bulk_sweep(rows, N_SMOKE, bulk_sizes=(1, 16, 128), reps=3)


def smoke(rows: List[str]) -> None:
    """CI smoke: tiny N, every entry point, delta == rebuild asserted."""
    svc = _build_service(make_uniform_workload, N_SMOKE, 10.0, seed=0)
    svc.all_pairs()                      # warm the cache + jit
    rng = np.random.RandomState(4)
    for step in range(3):
        for _ in range(5):
            _random_move(svc, rng, seg=1000.0)
        svc.flush()
    got = svc.all_pairs()
    svc.invalidate_cache()
    assert svc.all_pairs() == got, "delta path drifted from rebuild"
    assert got == service_pairs(svc), "delta path drifted from host oracle"
    rows.append(f"churn_smoke_n{N_SMOKE},0,pairs={len(got)}")

    # runtime stats (DESIGN.md §10): rebuild sweeps are probe-seeded, so
    # they are structurally retry-free, and two identical back-to-back
    # rebuilds share one ladder bucket, so the second compiles nothing.
    # Asserted here and re-gated in CI from the derived counters.
    svc.invalidate_cache()
    svc.all_pairs()                   # rebuild 1 (may compile its bucket)
    svc.invalidate_cache()
    svc.all_pairs()                   # rebuild 2: identical workload
    last = svc.stats()["last"]
    assert last["engine"] == "service_rebuild", last
    assert last["retries"] == 0, f"retry on identical rebuild: {last}"
    assert last["recompiles"] == 0, f"recompile after warmup: {last}"
    ph = last["phase_seconds"]
    rows.append(
        f"churn_smoke_runtime_n{N_SMOKE},{sum(ph.values())*1e6:.1f},"
        f"retries={last['retries']};recompiles={last['recompiles']};"
        f"probe_us={ph.get('probe', 0.0)*1e6:.1f};"
        f"emit_us={ph.get('emit', 0.0)*1e6:.1f}")
    single_move(rows, N_SMOKE, reps=5)
    move_fraction_sweep(rows, N_SMOKE, reps=3)

    # d=2 churn on the tall-thin adversary: the per-dimension incremental
    # index (selective-generator all_pairs + other-dim delta filters,
    # DESIGN.md §8) must track the rebuild path exactly under moves
    from repro.data.synthetic import ddm_workload
    n2 = 50
    subs2, upds2 = ddm_workload("tall_thin", jax.random.PRNGKey(2), n2, n2,
                                alpha=10.0, d=2)
    svc2 = DDMService(dims=2, capacity=4 * n2)
    s_lo = np.asarray(subs2.lo)
    s_hi = np.asarray(subs2.hi)
    u_lo = np.asarray(upds2.lo)
    u_hi = np.asarray(upds2.hi)
    uids = []
    for i in range(n2):
        svc2.register("sub", s_lo[:, i], s_hi[:, i])
        uids.append(svc2.register("upd", u_lo[:, i], u_hi[:, i]))
    svc2.all_pairs()
    rng2 = np.random.RandomState(5)
    for _ in range(3):
        for _ in range(4):
            rid = uids[rng2.randint(n2)]
            lo = rng2.uniform(0, 9e5, 2).astype(np.float32)
            svc2.move("upd", rid, lo, lo + np.float32(1e4))
        svc2.flush()
    got2 = svc2.all_pairs()
    svc2.invalidate_cache()
    assert svc2.all_pairs() == got2, "d=2 delta path drifted from rebuild"
    assert got2 == service_pairs(svc2), \
        "d=2 delta path drifted from host oracle"
    rows.append(f"churn_smoke_d2_talln{n2},0,pairs={len(got2)}")

    # the flat-vs-blocked twin axis + analytic-model structure audit
    speedup = small_batch(rows, N_SMOKE, reps=5)
    _model_crossover_audit(N_SMOKE, speedup)
    latency(rows, N_SMOKE, flushes=20)


def run(rows: List[str], bulk: bool = False,
        with_latency: bool = False) -> None:
    single_move(rows, N_FULL, reps=3)
    speedup = small_batch(rows, N_FULL, reps=3)
    _model_crossover_audit(N_FULL, speedup)
    move_fraction_sweep(rows, N_FULL, reps=2)
    if with_latency:
        latency(rows, N_FULL, flushes=160)
    if bulk:
        bulk_sweep(rows, N_FULL, bulk_sizes=(1, 100, 10_000), reps=2)


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-N CI guard (asserts delta == rebuild)")
    ap.add_argument("--bulk", action="store_true",
                    help="add the bulk-batch axis: b-region move batches, "
                         "vectorized stacked rematch vs per-region loop")
    ap.add_argument("--latency", action="store_true",
                    help="add broker flush-latency percentiles (p50/p95/"
                         "p99) and write the run's summary to the "
                         "repo-root BENCH_pr10.json")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows as JSON (the CI bench gate input)")
    args = ap.parse_args()
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    rows: List[str] = []
    print("name,us_per_call,derived")
    if args.smoke:
        smoke(rows)
        if args.bulk:
            bulk_smoke(rows)
    else:
        run(rows, bulk=args.bulk, with_latency=args.latency)
    for r in rows:
        print(r, flush=True)
    meta = {"module": "churn", "smoke": args.smoke}
    if args.json:
        from benchmarks._bench_json import write_json
        write_json(args.json, rows, meta=meta)
    if args.latency:
        import pathlib

        from benchmarks._bench_json import write_json
        out = pathlib.Path(__file__).resolve().parent.parent \
            / "BENCH_pr10.json"
        write_json(str(out), rows, meta=meta)
