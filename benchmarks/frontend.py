"""Frontend workload — the concurrent broker under offered load (PR 8).

The broker (:mod:`repro.frontend`) coalesces mutations from many client
threads into per-session batches drained by the vectorized delta path.
This benchmark answers the capacity questions the frontend exists for:

* ``frontend_tput_x{1,2,4}`` — applied throughput (ops/s) and p99 flush
  latency with producers offering 1×/2×/4× the drain rate under the
  ``reject`` policy: past saturation, throughput must hold (not
  collapse), the queue must stay bounded, and the reject fraction must
  absorb the excess.
* smoke mode (``--smoke``, the CI guard) replaces real-time pacing with
  deterministic burst phases so every hard assert is timing-independent:
  at 4× offered load the queue never exceeds its bound, **zero accepted
  mutations are lost** (journal replay into a fresh service must
  reproduce the live pair set, cross-checked against the
  ``sweep_rebuild_pairs``/``service_pairs`` oracles), degraded
  ``match_count`` reads are served ``exact=False``, and the warmed
  steady-state flush reports ``retries=0;recompiles=0`` under the PR 7
  counter gate.

Run standalone with ``PYTHONPATH=src python -m benchmarks.frontend
[--smoke] [--json PATH]`` or through ``python -m benchmarks.run``.
"""
from __future__ import annotations

import threading
import time
from typing import List

import numpy as np

from repro.api import (
    AdmissionPolicy,
    Broker,
    DegradePolicy,
    OverloadError,
    replay_journal,
)
from repro.testing.oracles import service_pairs, sweep_rebuild_pairs

QUEUE = 256               # admission bound of the benchmark session
N_SEED = 512              # warm regions per side before load is offered
LENGTH = 1.0e6
SEG = 2_000.0


def _seed_session(sess, rng, n_each: int) -> None:
    lo_s = rng.uniform(0, LENGTH - SEG, n_each).astype(np.float32)
    lo_u = rng.uniform(0, LENGTH - SEG, n_each).astype(np.float32)
    sess.register("sub", lo_s, lo_s + np.float32(SEG))
    sess.register("upd", lo_u, lo_u + np.float32(SEG))
    sess.flush()


def _offer(sess, rng, n_ops: int) -> tuple:
    """Submit n_ops random register/move ops; (accepted tickets, rejected)."""
    accepted, rejected = [], 0
    for i in range(n_ops):
        lo = float(rng.uniform(0, LENGTH - SEG))
        side = "sub" if i % 2 else "upd"
        try:
            if i % 3 == 0:
                rid = int(rng.randint(N_SEED))
                accepted.append(sess.move(side, rid, lo, lo + SEG))
            else:
                accepted.append(sess.register(side, lo, lo + SEG))
        except OverloadError:
            rejected += 1
    return accepted, rejected


def _live_dicts(svc):
    """rid → (lo, hi) dicts of the live tables (the oracle input)."""
    out = []
    for table in (svc._subs, svc._upds):
        ids = table.live_ids()
        out.append({int(r): (table.lo[:, r].copy(), table.hi[:, r].copy())
                    for r in ids})
    return out


def _verify_zero_loss(sess) -> int:
    """Replay the journal single-threaded; live == replay == oracles.

    Returns the live pair count (a deterministic derived row under fixed
    seeds).  Raises if any accepted-then-applied mutation failed to reach
    the index — the smoke-mode acceptance criterion.
    """
    replayed = replay_journal(sess.journal, dims=sess.dims,
                              capacity=sess.service._subs.lo.shape[1])
    live = service_pairs(sess.service)
    again = service_pairs(replayed)
    assert live == again, (
        f"accepted-mutation loss: live {len(live)} pairs != "
        f"replay {len(again)} pairs")
    if sess.dims == 1:
        live_s, live_u = _live_dicts(sess.service)
        assert live == sweep_rebuild_pairs(live_s, live_u), \
            "live state drifted from the stateless sweep rebuild oracle"
    return len(live)


# ---------------------------------------------------------------------------
# smoke mode: deterministic burst phases (the CI guard)
# ---------------------------------------------------------------------------

def overload_smoke(rows: List[str]) -> None:
    """4× offered load, ``reject`` policy, zero-loss + degradation asserts."""
    broker = Broker(
        admission=AdmissionPolicy(max_queue=QUEUE, backpressure="reject"),
        degrade=DegradePolicy(max_queue_depth=QUEUE // 2),
        journal=True)
    sess = broker.create_session("hot", dims=1, capacity=4 * N_SEED)
    rng = np.random.RandomState(0)
    _seed_session(sess, rng, N_SEED)
    sess.pairs()                           # warm the cache + jit

    tickets, rejected = [], 0
    for _ in range(3):                     # three bursts, drain between
        acc, rej = _offer(sess, rng, 4 * QUEUE)   # 4× the queue bound
        tickets.extend(acc)
        rejected += rej
        assert sess.queue_depth <= QUEUE, \
            f"queue depth {sess.queue_depth} exceeded bound {QUEUE}"
        read = sess.match_count()          # queue is full ⇒ degraded
        assert read.exact is False and read.pending > 0, read
        sess.flush()
    healthy = sess.match_count()           # drained ⇒ exact again
    assert healthy.exact is True, healthy

    for t in tickets:                      # every accepted op resolved OK
        t.result(timeout=0)
    n_pairs = _verify_zero_loss(sess)

    st = sess.stats()
    assert st["rejected"] == rejected and rejected > 0
    assert st["accepted"] == len(tickets) + 2      # + the 2 seed blocks
    assert st["applied"] == st["accepted"], \
        "accepted ops left unapplied after final drain"
    assert st["degraded_reads"] == 3 and st["exact_reads"] >= 1
    rows.append(f"frontend_smoke_overload,0,pairs={n_pairs}")
    rows.append(
        f"frontend_smoke_admission,0,"
        f"accepted={st['accepted']};rejected={st['rejected']};lost=0;"
        f"degraded_reads={st['degraded_reads']}")


def steady_state_smoke(rows: List[str]) -> None:
    """Warmed steady-state flush: the PR 7 zero-counter gate.

    Identical-shape move bursts land in one pow2 ladder bucket, so after
    the warmup flush the steady-state flush must report zero retries and
    zero recompiles — emitted as a ``retries=;recompiles=`` derived row,
    which ``check_regression`` fails on any nonzero value.
    """
    broker = Broker()
    sess = broker.create_session("steady", dims=1, capacity=4 * N_SEED)
    rng = np.random.RandomState(1)
    _seed_session(sess, rng, N_SEED)
    sess.pairs()

    def burst_and_flush() -> float:
        for _ in range(32):                # fixed burst shape
            rid = int(rng.randint(N_SEED))
            lo = float(rng.uniform(0, LENGTH - SEG))
            sess.move("upd", rid, lo, lo + SEG)
        t0 = time.perf_counter()
        sess.flush()
        return time.perf_counter() - t0

    burst_and_flush()                      # warmup: may compile its bucket
    rec = sess.service.recorder
    before = (rec.retries, rec.recompiles)
    t_flush = burst_and_flush()            # steady state: same bucket
    retries = rec.retries - before[0]
    recompiles = rec.recompiles - before[1]
    rows.append(
        f"frontend_smoke_runtime,{t_flush*1e6:.1f},"
        f"retries={retries};recompiles={recompiles}")
    n_pairs = len(sess.pairs())
    rows.append(f"frontend_smoke_steady,0,pairs={n_pairs}")


def threaded_smoke(rows: List[str]) -> None:
    """Barrier-released producer threads against one session (``block``
    policy + background flusher): zero loss under real concurrency."""
    n_threads, per_thread = 4, 200
    with Broker(admission=AdmissionPolicy(max_queue=64,
                                          backpressure="block",
                                          block_timeout=30.0),
                journal=True, flush_interval=0.005) as broker:
        sess = broker.create_session("mt", dims=1, capacity=4 * N_SEED)
        seed_rng = np.random.RandomState(2)
        _seed_session(sess, seed_rng, N_SEED)
        barrier = threading.Barrier(n_threads)
        tickets: List[list] = [[] for _ in range(n_threads)]

        def producer(k: int) -> None:
            rng = np.random.RandomState(100 + k)
            barrier.wait()
            acc, rej = _offer(sess, rng, per_thread)
            assert rej == 0                # block policy never rejects
            tickets[k].extend(acc)

        threads = [threading.Thread(target=producer, args=(k,))
                   for k in range(n_threads)]
        t0 = time.perf_counter()
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        for ts in tickets:
            for t in ts:
                t.result(timeout=30.0)     # resolved by the flusher
        dt = time.perf_counter() - t0
        broker.flush_all()
        _verify_zero_loss(sess)
        st = sess.stats()
        assert st["applied"] == st["accepted"]
    ops = n_threads * per_thread
    rows.append(f"frontend_smoke_threads,{dt/ops*1e6:.1f},"
                f"threads={n_threads};ops={ops};lost=0")


def smoke(rows: List[str]) -> None:
    overload_smoke(rows)
    steady_state_smoke(rows)
    threaded_smoke(rows)


# ---------------------------------------------------------------------------
# full mode: paced offered-load sweep (1x / 2x / 4x the drain rate)
# ---------------------------------------------------------------------------

def offered_load_sweep(rows: List[str], duration: float = 2.0) -> None:
    """1x/2x/4x offered load = that many saturating producer threads
    against one session (``reject`` policy, background flusher), plus one
    reader thread probing ``match_count`` — degraded past the threshold.
    Reported: applied throughput (as us/op), reject fraction, p99 flush
    latency, degraded-read count."""
    for mult in (1, 2, 4):
        broker = Broker(
            admission=AdmissionPolicy(max_queue=QUEUE, backpressure="reject"),
            degrade=DegradePolicy(max_queue_depth=QUEUE // 4),
            flush_interval=0.002)
        sess = broker.create_session("load", dims=1, capacity=16 * N_SEED)
        _seed_session(sess, np.random.RandomState(0), N_SEED)
        sess.pairs()                        # warm cache + jit
        stop = threading.Event()
        counts = [[0, 0] for _ in range(mult)]   # accepted, rejected

        def producer(k: int) -> None:
            rng = np.random.RandomState(10 + k)
            acc = rej = i = 0
            while not stop.is_set():
                i += 1
                lo = float(rng.uniform(0, LENGTH - SEG))
                try:
                    if i % 3 == 0:
                        sess.move("upd", int(rng.randint(N_SEED)),
                                  lo, lo + SEG)
                    else:
                        sess.register("upd", lo, lo + SEG)
                    acc += 1
                except OverloadError:
                    rej += 1
            counts[k][0], counts[k][1] = acc, rej

        def reader() -> None:
            while not stop.is_set():
                sess.match_count()
                time.sleep(0.01)

        threads = [threading.Thread(target=producer, args=(k,))
                   for k in range(mult)] + [threading.Thread(target=reader)]
        for th in threads:
            th.start()
        time.sleep(duration)
        stop.set()
        for th in threads:
            th.join()
        broker.close()
        st = sess.stats()
        accepted = sum(c[0] for c in counts)
        rejected = sum(c[1] for c in counts)
        offered = accepted + rejected
        applied_tput = accepted / duration
        rows.append(
            f"frontend_tput_x{mult},{1e6/max(applied_tput, 1e-9):.1f},"
            f"offered={offered};reject_frac={rejected/max(offered, 1):.2f};"
            f"p99_flush_us={st['flush_p99_us']:.0f};"
            f"degraded_reads={st['degraded_reads']}")


def run(rows: List[str]) -> None:
    offered_load_sweep(rows)


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="deterministic CI guard: 4x overload bursts, "
                         "zero-loss replay, degraded reads, counter gate")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows as JSON (the CI bench gate input)")
    args = ap.parse_args()
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    rows: List[str] = []
    print("name,us_per_call,derived")
    if args.smoke:
        smoke(rows)
    else:
        run(rows)
    for r in rows:
        print(r, flush=True)
    if args.json:
        from benchmarks._bench_json import write_json
        write_json(args.json, rows, meta={"module": "frontend",
                                          "smoke": args.smoke})
