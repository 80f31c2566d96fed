"""The concurrent broker frontend (PR 8 tentpole; DESIGN.md §11).

Unit tests make each admission behavior observable — block, reject and
shed_oldest each produce a distinct, asserted outcome — plus deadline
expiry at flush boundaries and the degraded-read ladder.  The threaded
stress test is the tentpole acceptance check: barrier-released writer
threads race reader threads against one session, then the composed delta
stream must equal a single-threaded replay of the journal, cross-checked
against the conformance harness's ``sweep_rebuild_pairs`` oracle.
"""
import threading
import time

import jax
import numpy as np
import pytest

from repro.api import (
    AdmissionPolicy,
    Broker,
    CountResult,
    DDMError,
    DDMService,
    DeadlineExceeded,
    DegradePolicy,
    OverloadError,
    ValidationError,
    replay_journal,
)
from repro.testing.oracles import service_pairs, sweep_rebuild_pairs

jax.config.update("jax_platform_name", "cpu")


def _live_dicts(svc):
    out = []
    for table in (svc._subs, svc._upds):
        out.append({int(r): (table.lo[:, r].copy(), table.hi[:, r].copy())
                    for r in table.live_ids()})
    return out


# ---------------------------------------------------------------------------
# tickets + flush boundary basics
# ---------------------------------------------------------------------------

def test_ticket_resolves_at_flush_with_assigned_rids():
    broker = Broker()
    sess = broker.create_session("s", dims=1)
    t_scalar = sess.register("sub", 0.0, 10.0)
    t_block = sess.register("upd", np.array([5.0, 20.0]),
                            np.array([6.0, 21.0]))
    assert not t_scalar.done()
    with pytest.raises(TimeoutError):
        t_scalar.result(timeout=0)          # nothing flushed yet
    sess.flush()
    rid = t_scalar.result(timeout=0)
    rids = t_block.result(timeout=0)
    assert isinstance(rid, int) and len(rids) == 2
    assert sess.pairs() == {(rid, int(rids[0]))}


def test_bad_op_fails_its_ticket_not_the_batch():
    broker = Broker()
    sess = broker.create_session("s", dims=1)
    good = sess.register("sub", 0.0, 1.0)
    bad = sess.register("sub", np.array([[5.0]]), np.array([[2.0]]))  # lo>hi
    also_good = sess.register("upd", 0.5, 0.6)
    sess.flush()
    with pytest.raises(ValidationError):
        bad.result(timeout=0)
    assert sess.pairs() == {(good.result(0), also_good.result(0))}
    assert sess.stats()["failed"] == 1


def test_service_flush_failure_fails_the_batch_tickets():
    class LostDevice(DDMService):
        def flush(self):
            raise RuntimeError("device lost")

    broker = Broker(service_factory=LostDevice)
    sess = broker.create_session("s", dims=1)
    tickets = [sess.register("sub", 0.0, 1.0), sess.register("upd", 0.5, 2.0)]
    with pytest.raises(RuntimeError, match="device lost"):
        sess.flush()
    for t in tickets:
        assert t.done()
        with pytest.raises(RuntimeError, match="device lost"):
            t.result(timeout=0)
    assert sess.stats()["failed"] == 2 and sess.queue_depth == 0


def test_failed_service_flush_breaks_the_session():
    class LostDevice(DDMService):
        def flush(self):
            raise RuntimeError("device lost")

    kinds = [LostDevice, DDMService]      # one per create_session call
    broker = Broker(service_factory=lambda **kw: kinds.pop(0)(**kw))
    lost = broker.create_session("a-lost", dims=1)
    ok = broker.create_session("b-ok", dims=1)
    lost.register("sub", 0.0, 1.0)
    t_ok = ok.register("sub", 0.0, 1.0)
    # the healthy session is flushed although the first one raises
    with pytest.raises(RuntimeError, match="device lost"):
        broker.flush_all()
    assert isinstance(t_ok.result(timeout=0), int)
    # the lost session's tables hold a region its index never took: it
    # refuses every later op, flush and read with the stored cause
    for call in (lambda: lost.register("upd", 0.5, 2.0), lost.flush,
                 lost.match_count, lost.pairs):
        with pytest.raises(DDMError, match="broken") as err:
            call()
        assert isinstance(err.value.__cause__, RuntimeError)
    assert lost.queue_depth == 0
    assert ok.match_count().count == 0


def test_move_and_unregister_through_queue():
    broker = Broker(journal=True)
    sess = broker.create_session("s", dims=2)
    s = sess.register("sub", [0.0, 0.0], [10.0, 10.0])
    u = sess.register("upd", [5.0, 5.0], [6.0, 6.0])
    sess.flush()
    s_rid, u_rid = s.result(0), u.result(0)
    assert sess.pairs() == {(s_rid, u_rid)}
    sess.move("upd", u_rid, [50.0, 50.0], [60.0, 60.0])
    assert sess.pairs() == set()            # pairs() drains the queue
    sess.unregister("sub", s_rid)
    sess.flush()
    replayed = replay_journal(sess.journal, dims=2,
                              capacity=sess.service._subs.lo.shape[1])
    assert service_pairs(replayed) == service_pairs(sess.service)


# ---------------------------------------------------------------------------
# admission control: each policy observable
# ---------------------------------------------------------------------------

def test_reject_policy_raises_and_counts():
    broker = Broker(admission=AdmissionPolicy(max_queue=2,
                                              backpressure="reject"))
    sess = broker.create_session("s", dims=1)
    sess.register("sub", 0.0, 1.0)
    sess.register("sub", 1.0, 2.0)
    with pytest.raises(OverloadError, match="'reject' policy"):
        sess.register("sub", 2.0, 3.0)
    assert sess.stats()["rejected"] == 1
    assert sess.queue_depth == 2            # bound held
    sess.flush()
    sess.register("sub", 2.0, 3.0)          # space again after drain


def test_shed_oldest_policy_fails_oldest_ticket():
    broker = Broker(admission=AdmissionPolicy(max_queue=2,
                                              backpressure="shed_oldest"))
    sess = broker.create_session("s", dims=1)
    first = sess.register("sub", 0.0, 1.0)
    second = sess.register("sub", 1.0, 2.0)
    third = sess.register("sub", 2.0, 3.0)  # sheds `first`
    assert first.done()
    with pytest.raises(OverloadError, match="shed"):
        first.result(timeout=0)
    sess.flush()
    assert second.result(0) is not None and third.result(0) is not None
    st = sess.stats()
    assert st["shed"] == 1 and st["applied"] == 2


def test_block_policy_waits_for_drain_and_times_out():
    broker = Broker(admission=AdmissionPolicy(max_queue=1,
                                              backpressure="block",
                                              block_timeout=0.05))
    sess = broker.create_session("s", dims=1)
    sess.register("sub", 0.0, 1.0)
    t0 = time.perf_counter()
    with pytest.raises(OverloadError, match="blocking"):
        sess.register("sub", 1.0, 2.0)      # nobody drains: times out
    assert time.perf_counter() - t0 >= 0.04
    # with a concurrent drain the same submit goes through
    timer = threading.Timer(0.01, sess.flush)
    timer.start()
    ticket = sess.register("sub", 1.0, 2.0)
    timer.join()
    sess.flush()
    assert ticket.result(0) is not None


def test_admission_policy_validation():
    with pytest.raises(ValidationError, match="backpressure"):
        AdmissionPolicy(backpressure="drop_newest")
    with pytest.raises(ValidationError, match="max_queue"):
        AdmissionPolicy(max_queue=0)
    with pytest.raises(ValidationError, match="estimator"):
        DegradePolicy(estimator="psychic")


# ---------------------------------------------------------------------------
# deadlines at flush boundaries
# ---------------------------------------------------------------------------

def test_expired_op_dropped_whole_at_flush():
    broker = Broker()
    sess = broker.create_session("s", dims=1)
    fresh = sess.register("sub", 0.0, 10.0)
    stale = sess.register("upd", 5.0, 6.0, timeout=0.0)
    time.sleep(0.01)                        # deadline passes in the queue
    sess.flush()
    with pytest.raises(DeadlineExceeded, match="deadline passed"):
        stale.result(timeout=0)
    assert fresh.result(0) is not None
    assert sess.pairs() == set()            # the expired upd never landed
    assert sess.stats()["expired"] == 1


def test_unexpired_deadline_applies_normally():
    broker = Broker()
    sess = broker.create_session("s", dims=1)
    t = sess.register("sub", 0.0, 1.0, timeout=60.0)
    sess.flush()
    assert t.result(0) is not None


# ---------------------------------------------------------------------------
# graceful degradation
# ---------------------------------------------------------------------------

def _warm(sess, n=8):
    lo = np.linspace(0.0, 900.0, n).astype(np.float32)
    sess.register("sub", lo, lo + np.float32(200.0))
    sess.register("upd", lo + np.float32(50.0), lo + np.float32(60.0))
    sess.flush()


def test_degraded_read_by_queue_depth():
    broker = Broker(degrade=DegradePolicy(max_queue_depth=3))
    sess = broker.create_session("s", dims=1)
    _warm(sess)
    exact = sess.match_count()
    assert exact.exact is True and exact.source == "index"
    for i in range(3):
        sess.register("upd", 1e5 + i, 1e5 + i + 1)
    degraded = sess.match_count()
    assert isinstance(degraded, CountResult)
    assert degraded.exact is False and degraded.pending == 3
    assert degraded.source == "probe_count"
    assert degraded.count == exact.count    # estimate over applied state
    assert int(degraded) == degraded.count
    sess.flush()
    assert sess.match_count().exact is True
    st = sess.stats()
    assert st["degraded_reads"] == 1 and st["exact_reads"] >= 2


def test_degraded_read_by_p99_latency():
    broker = Broker(degrade=DegradePolicy(max_p99_seconds=0.0))
    sess = broker.create_session("s", dims=1)
    _warm(sess)                             # any flush ⇒ p99 >= 0.0
    assert sess.is_degraded()
    sess.register("upd", 0.0, 1.0)
    assert sess.match_count().exact is False


def test_degraded_read_grid_estimator_and_ddim():
    broker = Broker(degrade=DegradePolicy(max_queue_depth=1,
                                          estimator="grid"))
    sess = broker.create_session("s", dims=1)
    _warm(sess)
    sess.register("upd", 0.0, 1.0)
    got = sess.match_count()
    assert got.exact is False and got.source == "grid_count"
    sess2 = broker.create_session("s2", dims=2,
                                  degrade=DegradePolicy(max_queue_depth=1))
    sess2.register("sub", [0.0, 0.0], [10.0, 10.0])
    sess2.register("upd", [5.0, 5.0], [6.0, 6.0])
    sess2.flush()
    sess2.register("upd", [50.0, 50.0], [51.0, 51.0])
    got2 = sess2.match_count()              # d>1 falls back to the probe
    assert got2.exact is False and got2.source == "probe_count"
    assert got2.count >= 1                  # min_d per-dim K: upper bound


# ---------------------------------------------------------------------------
# broker-level plumbing
# ---------------------------------------------------------------------------

def test_sessions_are_isolated_and_stats_aggregate():
    broker = Broker()
    a = broker.create_session("a", dims=1)
    b = broker.create_session("b", dims=1)
    ta = a.register("sub", 0.0, 10.0)
    tb = b.register("upd", 5.0, 6.0)
    broker.flush_all()
    assert a.pairs() == set() and b.pairs() == set()   # no cross-tenant pairs
    assert ta.result(0) == 0 and tb.result(0) == 0     # independent rid spaces
    st = broker.stats()
    assert st["totals"]["sessions"] == 2
    assert st["totals"]["applied"] == 2
    assert set(st["sessions"]) == {"a", "b"}
    with pytest.raises(ValidationError, match="already exists"):
        broker.create_session("a")
    with pytest.raises(KeyError):
        broker.session("missing")


def test_background_flusher_resolves_tickets():
    with Broker(flush_interval=0.005) as broker:
        sess = broker.create_session("s", dims=1)
        t = sess.register("sub", 0.0, 1.0)
        assert t.result(timeout=2.0) is not None       # no explicit flush
    assert sess.queue_depth == 0            # close() drains


def test_drop_session_fails_pending_tickets():
    broker = Broker()
    sess = broker.create_session("s", dims=1)
    t = sess.register("sub", 0.0, 1.0)
    broker.drop_session("s")
    with pytest.raises(OverloadError, match="dropped"):
        t.result(timeout=0)
    assert "s" not in broker.sessions()


def test_frontend_records_into_shared_recorder():
    broker = Broker(degrade=DegradePolicy(max_queue_depth=1))
    sess = broker.create_session("s", dims=1)
    _warm(sess)
    sess.register("upd", 0.0, 1.0)
    sess.match_count()                      # degraded
    snap = broker.stats()["recorder"]
    assert snap["by_engine"]["frontend_flush"] >= 1
    assert snap["by_engine"]["frontend_degraded_read"] == 1


# ---------------------------------------------------------------------------
# the tentpole stress test: threaded writers/readers vs replay + oracle
# ---------------------------------------------------------------------------

def _run_threaded_stress(backpressure, *, debug_locks=False):
    """Barrier-released writers and readers against one session; the
    composed delta stream (live state) must equal a single-threaded
    journal replay and the stateless ``sweep_rebuild_pairs`` oracle.
    Returns the closed broker and its session for extra assertions."""
    n_writers, n_readers, per_writer = 4, 2, 120
    broker = Broker(
        admission=AdmissionPolicy(max_queue=48, backpressure=backpressure,
                                  block_timeout=30.0),
        degrade=DegradePolicy(max_queue_depth=24),
        journal=True, flush_interval=0.002, debug_locks=debug_locks)
    sess = broker.create_session("stress", dims=1, capacity=64)
    _warm(sess, n=16)
    barrier = threading.Barrier(n_writers + n_readers)
    errors = []
    reads = []

    def writer(k):
        rng = np.random.RandomState(500 + k)
        try:
            barrier.wait()
            tickets = []
            for i in range(per_writer):
                lo = float(rng.uniform(0, 9e5))
                side = "sub" if (i + k) % 2 else "upd"
                if i % 4 == 0:
                    tickets.append(sess.move(side, int(rng.randint(16)),
                                             lo, lo + 500.0))
                else:
                    tickets.append(sess.register(side, lo, lo + 500.0))
            for t in tickets:
                try:
                    t.result(timeout=30.0)
                except OverloadError:
                    pass                    # shed under shed_oldest: legal
        except Exception as exc:  # pragma: no cover - diagnostic
            errors.append(exc)

    def reader():
        try:
            barrier.wait()
            for _ in range(40):
                reads.append(sess.match_count())
        except Exception as exc:  # pragma: no cover - diagnostic
            errors.append(exc)

    threads = ([threading.Thread(target=writer, args=(k,))
                for k in range(n_writers)]
               + [threading.Thread(target=reader)
                  for _ in range(n_readers)])
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    broker.close()
    assert not errors, errors

    # zero accepted-mutation loss: replay the journal single-threaded
    replayed = replay_journal(sess.journal, dims=1,
                              capacity=sess.service._subs.lo.shape[1])
    live = service_pairs(sess.service)
    assert service_pairs(replayed) == live
    # and the composed state equals the stateless sweep rebuild oracle
    live_s, live_u = _live_dicts(sess.service)
    assert sweep_rebuild_pairs(live_s, live_u) == live
    # every admitted op is accounted for: applied + shed + expired + failed
    st = sess.stats()
    assert st["accepted"] == (st["applied"] + st["shed"] + st["expired"]
                              + st["failed"])
    if backpressure == "block":
        assert st["shed"] == 0
    # readers always got a typed answer, exact or flagged-degraded
    assert reads and all(isinstance(r, CountResult) for r in reads)
    return broker, sess


@pytest.mark.parametrize("backpressure", ["block", "shed_oldest"])
def test_threaded_stress_matches_single_threaded_replay(backpressure):
    _run_threaded_stress(backpressure)


def test_threaded_stress_under_debug_locks():
    """The same stress run under TSan-lite audited locks: zero lock
    discipline violations, and the contention counters surface through
    ``Broker.stats()["locks"]`` (DESIGN.md §12)."""
    broker, _sess = _run_threaded_stress("block", debug_locks=True)
    locks = broker.stats()["locks"]
    assert locks["violations"] == []
    # broker lock registered first = ranks before the session lock
    assert locks["order"][0] == "broker"
    assert "session:stress" in locks["order"]
    # the audited locks actually saw the traffic (writers + flusher +
    # readers all acquire the session lock)
    assert locks["acquisitions"]["session:stress"] > 100
    assert set(locks["contended"]) == set(locks["acquisitions"])
