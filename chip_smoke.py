#!/usr/bin/env python3
"""Drive the DDM system's main path once on a TPU and check every result.

    python3 chip_smoke.py [--seed N]                # one chip, every phase
    python3 chip_smoke.py [--seed N] --four-chips   # only the mesh paths

One process, no children.  Each phase prints one line: its name, its sizes,
its wall time in this run, and what it was checked against.  Phases:

  device   a TPU is attached (the script never falls back to the CPU)
  static   paper §5 1-d sets, N = 10⁶ extents (n = m = 5·10⁵), L = 10⁶,
           uniform and clustered, α ∈ {0.01, 1, 100}: sbm_count,
           sbm_count_exact and rank_count against the host Algorithm 4;
           sbm_enumerate on the uniform α = 100 set, checked pair by pair
  churn    DDMService(dims=1), n = m = 10⁵ at α = 1, bulk-registered;
           match_count/pairs, then flushes of b moved updates, each delta
           against the oracle's before/after pair sets
  ddim     DDMService(dims=2) on the tall-thin set, n = m = 2·10⁴
  broker   Broker(journal=True), two sessions, a few hundred queued ops
  kernels  the Pallas kernels compiled for the chip (interpret=False)
           against the XLA engines

``--four-chips`` runs only the paths over a mesh of four chips: the
planned sweep (``sbm_enumerate_planned(..., mesh=mesh)``) on float32 and
int32 sets at N = 10⁶, and bitmatrix_sharded, each against one chip.

Any mismatch or exception exits non-zero.  The last line of stdout, on
success only, is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
Data comes from ``--seed``.  The compile cache is the one
:mod:`repro.compile_cache` chooses.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
if not (ROOT / "src" / "repro").is_dir():
    sys.exit(f"chip_smoke: no repro package under {ROOT / 'src'}")
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

PAPER_N = 1_000_000          # extents, half subscriptions (paper §5)
CHURN_N = 100_000            # regions per side of the served churn phase
CHURN_BATCHES = (1, 100, 1_000, 10_000)
DDIM_N = 20_000              # regions per side, d = 2 tall-thin
KERNEL_BITMATRIX_N = 8192    # regions per side, d = 2 bit-matrix kernels


class Mismatch(Exception):
    """A result disagreed with its reference."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise Mismatch(what)


# backend compiles in this process (persistent-cache hits are not compiles)
_COMPILES = {"count": 0, "seconds": 0.0}


def _on_duration(event: str, duration: float, **kwargs) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILES["count"] += 1
        _COMPILES["seconds"] += duration


# wall seconds spent computing host references (oracles, checks)
_HOST = {"seconds": 0.0}


@contextlib.contextmanager
def host():
    t = time.perf_counter()
    try:
        yield
    finally:
        _HOST["seconds"] += time.perf_counter() - t


def start() -> tuple:
    return time.perf_counter(), dict(_COMPILES), _HOST["seconds"]


def report(name: str, t0: tuple, sizes: str, reference: str) -> None:
    wall = time.perf_counter() - t0[0]
    count = _COMPILES["count"] - t0[1]["count"]
    secs = _COMPILES["seconds"] - t0[1]["seconds"]
    ref = _HOST["seconds"] - t0[2]
    print(f"{name:<8} {sizes} | {wall:.2f} s, of which {count} compiles "
          f"{secs:.2f} s, host references {ref:.2f} s | vs {reference}",
          flush=True)


def pair_keys(pairs, m: int) -> np.ndarray:
    """Sorted int64 keys i·m + j of a padded (max_pairs, 2) buffer."""
    arr = np.asarray(pairs)
    arr = arr[arr[:, 0] >= 0].astype(np.int64)
    return np.sort(arr[:, 0] * m + arr[:, 1])


def rid_pairs(pairs, sub_rids, upd_rids) -> set:
    return {(int(sub_rids[i]), int(upd_rids[j])) for i, j in pairs}


def paper_set(seed: int, workload: str, alpha: float):
    from repro.configs.ddm_paper import ALPHAS, CONFIG, WORKLOADS
    from repro.data.synthetic import ddm_workload

    key = jax.random.fold_in(jax.random.PRNGKey(seed),
                             WORKLOADS.index(workload) * len(ALPHAS)
                             + ALPHAS.index(alpha))
    n = PAPER_N // 2
    return ddm_workload(workload, key, n, n, alpha=alpha,
                        length=CONFIG.length)


def tall_thin_set(seed: int, n: int, alpha: float):
    from repro.core import make_tall_thin_workload

    return make_tall_thin_workload(jax.random.fold_in(
        jax.random.PRNGKey(seed), n), n, n, alpha=alpha, d=2)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def device_phase(min_count: int) -> dict:
    t0 = start()
    devices = jax.devices()
    dev = devices[0]
    check(dev.platform == "tpu",
          f"no TPU: JAX's first device is on {dev.platform!r}")
    check(len(devices) >= min_count,
          f"{min_count} chips needed, JAX sees {len(devices)}")
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}
    report("device", t0, f"{info['kind']} x{info['count']}",
           "jax.devices()[0].platform == 'tpu'")
    return info


def static_phase(seed: int) -> None:
    from repro.configs.ddm_paper import ALPHAS
    from repro.core import rank_count, sbm_count, sbm_count_exact, \
        sbm_enumerate
    from repro.core.sweep import sequential_sbm_count_numpy

    t0 = start()
    ks = []
    for workload in ("uniform", "clustered"):
        for alpha in ALPHAS:
            subs, upds = paper_set(seed, workload, alpha)
            with host():
                want = sequential_sbm_count_numpy(subs, upds)
            got = {"sbm_count": int(sbm_count(subs, upds)),
                   "sbm_count_exact": sbm_count_exact(subs, upds),
                   "rank_count": int(rank_count(subs, upds))}
            for engine, k in got.items():
                check(k == want, f"{workload} α={alpha}: {engine} K={k}, "
                                 f"Algorithm 4 K={want}")
            ks.append(f"{workload[0]}{alpha:g}:{want}")
    # enumerate the densest uniform set and check every pair on the host
    subs, upds = paper_set(seed, "uniform", 100.0)
    k = sbm_count_exact(subs, upds)
    pairs, count = sbm_enumerate(subs, upds, max_pairs=k)
    check(int(count) == k, f"sbm_enumerate count {int(count)} != K {k}")
    arr = np.asarray(pairs).astype(np.int64)
    with host():
        i, j = arr[:, 0], arr[:, 1]
        check(bool(np.all(i >= 0)),
              "sbm_enumerate left padding inside K slots")
        m = upds.size
        check(np.unique(i * m + j).size == k, "sbm_enumerate repeated a pair")
        s_lo, s_hi = np.asarray(subs.lo), np.asarray(subs.hi)
        u_lo, u_hi = np.asarray(upds.lo), np.asarray(upds.hi)
        check(bool(np.all((s_lo[i] <= u_hi[j]) & (u_lo[j] <= s_hi[i]))),
              "sbm_enumerate emitted a non-overlapping pair")
    report("static", t0,
           f"N={PAPER_N} L=1e6 K[{' '.join(ks)}] enumerate K={k}",
           "host Algorithm 4 (sequential_sbm_count_numpy); pairs unique "
           "and overlapping")


def churn_phase(seed: int) -> None:
    from repro.api import DDMService
    from repro.core import Extents, make_uniform_workload
    from repro.testing.oracles import reference_pairs

    t0 = start()
    n = CHURN_N
    subs, upds = make_uniform_workload(jax.random.PRNGKey(seed + 1), n, n,
                                       alpha=1.0)
    s_lo, s_hi = np.asarray(subs.lo), np.asarray(subs.hi)
    u_lo, u_hi = np.array(upds.lo), np.array(upds.hi)
    seg = float(u_hi[0] - u_lo[0])
    svc = DDMService(dims=1)
    sid = svc.register("sub", s_lo, s_hi)
    uid = svc.register("upd", u_lo, u_hi)
    with host():
        before = rid_pairs(reference_pairs(subs, upds), sid, uid)
    check(svc.match_count() == len(before),
          f"match_count {svc.match_count()} != oracle K {len(before)}")
    check(svc.pairs() == before, "pairs() differs from the oracle")

    rng = np.random.default_rng(seed)
    regimes = []
    for b in CHURN_BATCHES:
        idx = rng.choice(n, size=b, replace=False)
        lo = rng.uniform(0.0, 1.0e6 - seg, size=b).astype(np.float32)
        hi = lo + np.float32(seg)
        seen = dict(svc.recorder.by_regime)
        svc.move("upd", uid[idx], lo, hi)
        delta = svc.flush()
        regimes += [r for r in ("dense", "jax", "sort")
                    if svc.recorder.by_regime.get(r, 0) > seen.get(r, 0)]
        u_lo[idx], u_hi[idx] = lo, hi
        with host():
            after = rid_pairs(reference_pairs(
                subs, Extents(np.asarray(u_lo), np.asarray(u_hi))), sid, uid)
        check(delta.added == after - before,
              f"b={b}: delta.added differs from the oracle")
        check(delta.removed == before - after,
              f"b={b}: delta.removed differs from the oracle")
        before = after
    check(svc.match_count() == len(before), "match_count after churn")
    check(svc.pairs() == before, "pairs() after churn")
    check(set(regimes) == {"dense", "jax", "sort"},
          f"churn flushes took regimes {regimes}")
    report("churn", t0,
           f"n=m={n} α=1 K={len(before)} flush b={list(CHURN_BATCHES)} "
           f"regimes={regimes}",
           "testing.oracles.reference_pairs before/after every flush")


def ddim_phase(seed: int) -> None:
    from repro.api import DDMService
    from repro.testing.oracles import reference_pairs

    t0 = start()
    subs, upds = tall_thin_set(seed, DDIM_N, 1.0)
    svc = DDMService(dims=2)
    sid = svc.register("sub", np.asarray(subs.lo).T, np.asarray(subs.hi).T)
    uid = svc.register("upd", np.asarray(upds.lo).T, np.asarray(upds.hi).T)
    # the oracle's sequential sweep runs on the thin dimension 1: dim 0
    # matches all n·m pairs
    with host():
        want = rid_pairs(reference_pairs(subs, upds, sweep_dim=1), sid, uid)
    check(svc.match_count() == len(want),
          f"match_count {svc.match_count()} != oracle K {len(want)}")
    check(svc.pairs() == want, "pairs() differs from the oracle")
    regime = svc.stats()["last"]["regime"]
    check(regime == "sweep_dim1", f"generator {regime}, expected dim 1")
    report("ddim", t0, f"d=2 tall-thin n=m={DDIM_N} K={len(want)} "
           f"generator={regime}",
           "testing.oracles.reference_pairs (sweep on dim 1 + brute force)")


def broker_phase(seed: int) -> None:
    from repro.api import Broker, replay_journal
    from repro.testing.oracles import service_pairs

    t0 = start()
    rng = np.random.default_rng(seed + 2)
    broker = Broker(journal=True)
    tickets = []
    sessions = [broker.create_session(name) for name in ("alpha", "beta")]

    def bounds(k):
        lo = rng.uniform(0.0, 1000.0, size=k).astype(np.float32)
        return lo, lo + rng.uniform(1.0, 40.0, size=k).astype(np.float32)

    registered = {}
    for sess in sessions:
        for side in ("sub", "upd"):
            mine = [sess.register(side, lo, hi) for lo, hi in zip(*bounds(60))]
            mine.append(sess.register(side, *bounds(40)))
            registered[sess.name, side] = mine
            tickets += mine
    broker.flush_all()
    for sess in sessions:
        for side in ("sub", "upd"):
            rids = np.concatenate([np.atleast_1d(t.result(timeout=30.0))
                                   for t in registered[sess.name, side]])
            for rid, lo, hi in zip(rng.choice(rids, 40, replace=False),
                                   *bounds(40)):
                tickets.append(sess.move(side, int(rid), lo, hi))
            gone = rng.choice(rids, 15, replace=False)
            tickets.append(sess.unregister(side, gone[:5]))
            for rid in gone[5:]:
                tickets.append(sess.unregister(side, int(rid)))
            tickets.append(sess.register(side, *bounds(10)))
    broker.flush_all()
    for t in tickets:
        t.result(timeout=30.0)   # raises if failed or never resolved
    ks = []
    for sess in sessions:
        with host():
            want = service_pairs(sess.service)
        count = sess.match_count()
        check(count.exact and count.count == len(want),
              f"session {sess.name}: match_count {count} != oracle "
              f"{len(want)}")
        replayed = replay_journal(sess.journal, dims=1)
        check(replayed.match_count() == len(want),
              f"session {sess.name}: replayed journal disagrees")
        check(sess.pairs() == want, f"session {sess.name}: pairs()")
        ks.append(len(want))
    broker.close()
    report("broker", t0, f"2 sessions {len(tickets)} ops K={ks}",
           "replay_journal and testing.oracles.service_pairs")


def kernels_phase(seed: int) -> None:
    from repro.core import bitmatrix_count, bitmatrix_enumerate, \
        bitmatrix_words, sbm_count_exact, sbm_enumerate
    from repro.kernels import bitmatrix_pallas, sbm_bitmatrix_kernel, \
        sbm_count_kernel, sbm_enumerate_kernel

    t0 = start()
    subs, upds = paper_set(seed, "uniform", 1.0)
    k = sbm_count_exact(subs, upds)
    got = int(sbm_count_kernel(subs, upds, interpret=False))
    check(got == k, f"sbm_count_kernel K={got}, sbm_count K={k}")
    pairs, count = sbm_enumerate_kernel(subs, upds, max_pairs=k,
                                        interpret=False)
    want, _ = sbm_enumerate(subs, upds, max_pairs=k)
    check(int(count) == k, f"sbm_enumerate_kernel count {int(count)}")
    check(np.array_equal(pair_keys(pairs, upds.size),
                         pair_keys(want, upds.size)),
          "sbm_enumerate_kernel pairs differ from sbm_enumerate")

    n2 = KERNEL_BITMATRIX_N
    subs2, upds2 = tall_thin_set(seed, n2, 100.0)
    words, counts, k2 = bitmatrix_pallas(subs2, upds2, interpret=False)
    check(np.array_equal(np.asarray(words),
                         np.asarray(bitmatrix_words(subs2, upds2))),
          "bitmatrix_pallas words differ from bitmatrix_words")
    k2_xla = int(bitmatrix_count(subs2, upds2))
    check(int(k2) == k2_xla and int(np.asarray(counts).sum()) == k2_xla,
          f"bitmatrix_pallas K={int(k2)}, bitmatrix_count K={k2_xla}")
    p2, c2 = sbm_bitmatrix_kernel(subs2, upds2, max_pairs=k2_xla,
                                  interpret=False)
    w2, _ = bitmatrix_enumerate(subs2, upds2, max_pairs=k2_xla)
    check(int(c2) == k2_xla and np.array_equal(np.asarray(p2),
                                               np.asarray(w2)),
          "sbm_bitmatrix_kernel pairs differ from bitmatrix_enumerate")
    report("kernels", t0,
           f"sweep N={PAPER_N} α=1 K={k}; bitmatrix d=2 n=m={n2} K={k2_xla}",
           "XLA sbm_count/sbm_enumerate and bitmatrix_words/_enumerate")


def hla_int_set(seed: int):
    """The paper's uniform α = 1 placement on HLA's integer dimension:
    N = 10⁶ int32 extents of length 10 on [0, 10N]."""
    from repro.core import Extents

    rng = np.random.default_rng(seed)
    length, seg = 10 * PAPER_N, 10
    lo = jax.numpy.asarray(rng.integers(0, length - seg + 1, PAPER_N),
                           jax.numpy.int32)
    n = PAPER_N // 2
    return (Extents(lo[:n], lo[:n] + seg), Extents(lo[n:], lo[n:] + seg))


def four_chip_phase(seed: int) -> None:
    from repro.core import bitmatrix_count, bitmatrix_sharded, \
        bitmatrix_words, sbm_enumerate_planned

    t0 = start()
    mesh = jax.make_mesh((4,), ("p",), devices=jax.devices()[:4])

    def spread(x, what):
        n = len(x.sharding.device_set)
        check(n == 4, f"{what} lives on {n} device(s), not 4")
        check(not x.sharding.is_fully_replicated,
              f"{what} is copied whole to every device, not sharded")

    ks = {}
    for dtype, (subs, upds) in (("float32", paper_set(seed, "uniform", 1.0)),
                                ("int32", hla_int_set(seed))):
        pairs1, count1, _ = sbm_enumerate_planned(subs, upds)
        pairs4, count4, stats = sbm_enumerate_planned(subs, upds, mesh=mesh)
        spread(pairs4, f"{dtype} planned pairs on the mesh")
        check(stats.chips == 4 and stats.exchange_bytes > 0,
              f"{dtype} planned call on the mesh: chips {stats.chips}, "
              f"exchange bytes {stats.exchange_bytes}")
        check(int(count4) == int(count1),
              f"{dtype} planned count on the mesh {int(count4)}, one chip "
              f"{int(count1)}")
        check(np.array_equal(pair_keys(pairs4, upds.size),
                             pair_keys(pairs1, upds.size)),
              f"{dtype} planned pairs on the mesh differ from one chip")
        ks[dtype] = int(count1)

    subs2, upds2 = tall_thin_set(seed, DDIM_N, 1.0)
    words4, kw4 = bitmatrix_sharded(subs2, upds2, mesh, "p")
    spread(words4, "bitmatrix_sharded words")
    words1 = bitmatrix_words(subs2, upds2)
    check(np.array_equal(np.asarray(words4)[:DDIM_N], np.asarray(words1))
          and not np.asarray(words4)[DDIM_N:].any(),
          "bitmatrix_sharded words differ from one chip")
    kw1 = int(bitmatrix_count(subs2, upds2))
    check(int(kw4) == kw1, f"bitmatrix_sharded K={int(kw4)}, one chip {kw1}")
    report("4-chip", t0,
           f"mesh (4,) planned sweep N={PAPER_N} α=1 float32 K="
           f"{ks['float32']}, int32 K={ks['int32']}; bitmatrix d=2 "
           f"n=m={DDIM_N} K={kw1}",
           "one-chip sbm_enumerate_planned and bitmatrix_words")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the mesh paths over four chips")
    args = ap.parse_args()

    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    device = device_phase(4 if args.four_chips else 1)
    if args.four_chips:
        four_chip_phase(args.seed)
    else:
        static_phase(args.seed)
        churn_phase(args.seed)
        ddim_phase(args.seed)
        broker_phase(args.seed)
        kernels_phase(args.seed)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
