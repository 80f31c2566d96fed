"""Unified execution-plan runtime: capacity planner + instrumented executor.

The paper's SBM pipeline (and every variant in its journal follow-up,
arXiv:1911.03456) shares one structural fact: pairs are emitted into a
fixed-size buffer whose required capacity is only known after the counting
sweep.  The repo-wide contract that falls out of it — *pairs beyond*
``max_pairs`` *are dropped but still counted; callers check*
``count <= max_pairs`` *and retry bigger* — used to be re-implemented
ad-hoc per layer (a retry loop in the test harness, hand-sized buffers in
the service, three divergent power-of-two padding ladders).  This module
is the single home for all of it (DESIGN.md §10):

* **Planner** — :func:`round_up_pow2` is THE pow2 ladder (``max(8, ·)``
  floor so tiny drifting counts share one bucket and the jit cache stays
  warm); :class:`CapacityPolicy` decides the initial ``max_pairs`` (from a
  counting-sweep / selectivity-probe estimate, or a start capacity),
  pow2 growth on overflow, and an optional **hard cap** that raises
  :class:`CapacityError` instead of growing.  :func:`pad_axis` /
  :func:`pad_columns` are the one encoding of inert-extent padding.
* **Executor** — :func:`execute_enumeration` is the one true
  count-then-retry loop (promoted out of the test harness; the
  conformance registry now runs the production path).  Every call records
  a :class:`MatchStats`: per-phase wall times, blocking device→host
  readbacks, retry count, jit recompiles (via the compile-cache probe
  :func:`jit_compiles`), final capacity, and padded-vs-actual waste.
* **Observability** — :class:`MatchStats` is the one recorder.  Its
  :meth:`MatchStats.phase` times a phase into ``phase_seconds`` and opens
  the host span ``ddm.<phase>`` on the profiler's clock;
  :meth:`MatchStats.readback` wraps a blocking device→host read in the
  child span ``ddm.<phase>.readback`` and counts it.  Spans carry the
  call's ``engine`` and per-process ``call`` number as metadata.
  :class:`StatsRecorder` aggregates stats across calls;
  :meth:`repro.core.service.DDMService.stats` surfaces one.
* **Bulk-regime policy** — :class:`BulkRegimePolicy` owns the
  dense/jax/sort thresholds of the incremental engine's stacked rematch
  (:func:`repro.core.incremental._bulk_overlap_pairs`), so the three
  regimes can be forced and audited via stats.

Phase-time vocabulary: the device pipeline is sort → count → offsets →
emit (DESIGN.md §3), but sort+count fuse into the counting-sweep probe
and offsets+emit fuse into each enumeration attempt under jit, so the
wall-clock split observable from the host is ``probe`` (sort + count),
``emit`` (offset table + pair emission, summed over retry attempts) and
``collect`` (host-side pair-set materialization, when requested).
Inside the two programs, ``jax.named_scope`` names the device stages
``ddm.sort``, ``ddm.count`` (probe), ``ddm.ranks``, ``ddm.search`` and
``ddm.gather`` (emission); the names reach the profiler as each
instruction's ``op_name``.

This module stays import-light (stdlib + numpy at module scope; jax is
imported lazily) so host-only paths like the incremental index keep their
no-jax-at-import property: where jax was never imported, a phase only
times.
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
import sys
import time
from collections import deque
from typing import (Callable, ContextManager, Deque, Dict, Iterator, List,
                    Optional, Set, Tuple)

import numpy as np

from repro.core.errors import CapacityError, ValidationError

Pair = Tuple[int, int]
PairSet = Set[Pair]


# ---------------------------------------------------------------------------
# The padding ladder — THE one pow2-bucketing rule in the repo
# ---------------------------------------------------------------------------

def round_up_pow2(k: int) -> int:
    """Power-of-two ``max_pairs`` buckets with a ``max(8, ·)`` floor.

    Bounded jit recompiles as K drifts between calls (service queries,
    benchmark sweeps, fuzzer ladders): two counts in the same bucket
    compile once.  This is the only ladder implementation in ``src/`` —
    every layer imports it from here.
    """
    return max(8, 1 << (k - 1).bit_length())


def inert_bounds(dtype):
    """``(top, bottom)`` of a bounds dtype: ``(+inf, -inf)`` for floats,
    the integer range's ends for integers.  An extent ``[top, bottom]``
    overlaps nothing but an extent that spans the whole range."""
    import jax.numpy as jnp

    if jnp.issubdtype(dtype, jnp.inexact):
        return jnp.inf, -jnp.inf
    info = jnp.iinfo(dtype)
    return info.max, info.min


def pad_axis(lo, hi, multiple: int):
    """Pad ``(d, n)`` extent columns to a multiple with inert
    ``[top, bottom]`` sentinels (:func:`inert_bounds`: every closed-interval
    test against a sentinel is False) — THE one encoding of the
    inert-extent convention, shared by the sharded and Pallas bit-matrix
    paths."""
    import jax.numpy as jnp

    pad = (-lo.shape[1]) % multiple
    if pad == 0:
        return lo, hi
    d = lo.shape[0]
    top, bottom = inert_bounds(lo.dtype)
    return (
        jnp.concatenate([lo, jnp.full((d, pad), top, lo.dtype)], axis=1),
        jnp.concatenate([hi, jnp.full((d, pad), bottom, hi.dtype)], axis=1),
    )


def pad_columns(a: np.ndarray, n: int, fill: float) -> np.ndarray:
    """Host-side column padding of a ``(d, b)`` block to ``n`` columns.

    The numpy face of the same inert-sentinel convention as
    :func:`pad_axis` (callers pass ``+inf``/``-inf`` for lo/hi): the
    incremental engine's fused-mask regime pads to :func:`round_up_pow2`
    buckets with it so jit recompiles stay bounded."""
    if a.shape[1] == n:
        return a
    out = np.full((a.shape[0], n), fill, a.dtype)
    out[:, :a.shape[1]] = a
    return out


# ---------------------------------------------------------------------------
# Capacity planning
# ---------------------------------------------------------------------------

# CapacityError is defined in repro.core.errors (the unified DDMError
# hierarchy, DESIGN.md §11) and re-exported here — the historical import
# path `from repro.core.runtime import CapacityError` stays valid.


@dataclasses.dataclass(frozen=True)
class CapacityPolicy:
    """How the planner sizes and grows ``max_pairs`` buffers.

    ``start_cap`` is the first attempt's capacity when no estimate is
    available (the classic cold-start of the test-harness loop).  With an
    estimate (counting sweep / selectivity probe), the first capacity is
    its :func:`round_up_pow2` bucket instead.  On overflow the executor
    grows to the pow2 bucket of the exact returned count; ``hard_cap``
    (when set) turns growth past it into a :class:`CapacityError`;
    ``max_attempts`` bounds the loop against engines that misreport
    counts.
    """

    start_cap: int = 64
    hard_cap: Optional[int] = None
    max_attempts: int = 10


DEFAULT_POLICY = CapacityPolicy()


def initial_capacity(estimate: Optional[int],
                     policy: CapacityPolicy = DEFAULT_POLICY) -> int:
    """First-attempt ``max_pairs``: the estimate's ladder bucket, or the
    policy's start capacity; clamped to ``hard_cap`` when set (the
    executor then raises only if the *actual* count needs more)."""
    cap = (policy.start_cap if estimate is None
           else round_up_pow2(max(int(estimate), 1)))
    if policy.hard_cap is not None:
        cap = min(cap, policy.hard_cap)
    return cap


def next_capacity(count: int, cap: int,
                  policy: CapacityPolicy = DEFAULT_POLICY) -> int:
    """Grown capacity after an overflow (``count > cap``): the ladder
    bucket of the exact count.  Raises :class:`CapacityError` when the
    policy's hard cap forbids the growth."""
    nxt = round_up_pow2(max(int(count), cap + 1))
    if policy.hard_cap is not None and nxt > policy.hard_cap:
        raise CapacityError(
            f"enumeration needs max_pairs={nxt} (count {count}) but the "
            f"policy hard cap is {policy.hard_cap}")
    return nxt


# ---------------------------------------------------------------------------
# jit compile-cache probe
# ---------------------------------------------------------------------------

# One backend compile == one '/jax/core/compile/backend_compile_duration'
# monitoring event; counting them is how the executor attributes
# recompiles to a call without reaching into jit internals.
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_probe = {"count": 0, "armed": False}


def _arm_compile_probe() -> None:
    if _compile_probe["armed"]:
        return
    from jax import monitoring

    def _on_duration(event: str, duration: float, **kwargs) -> None:
        if event == _COMPILE_EVENT:
            _compile_probe["count"] += 1

    monitoring.register_event_duration_secs_listener(_on_duration)
    _compile_probe["armed"] = True


def jit_compiles() -> int:
    """Monotonic count of XLA backend compiles since the probe was armed.

    Deltas across a region of code count the jit recompiles it caused —
    zero after warmup is the ladder's whole point.
    :func:`execute_enumeration` records each call's delta as
    ``MatchStats.recompiles``.
    """
    _arm_compile_probe()
    return _compile_probe["count"]


# ---------------------------------------------------------------------------
# Per-call stats + the aggregating recorder
# ---------------------------------------------------------------------------

_CALLS = itertools.count(1)       # per-process call numbers of MatchStats
_annotation = {"cls": None}


def _trace_annotation():
    """``jax.profiler.TraceAnnotation`` once jax is imported, else None —
    a host-only process never imports jax for a span."""
    if _annotation["cls"] is None and "jax" in sys.modules:
        from jax.profiler import TraceAnnotation

        _annotation["cls"] = TraceAnnotation
    return _annotation["cls"]


@dataclasses.dataclass
class MatchStats:
    """Observability record of one planned matching call.

    ``engine`` names the entry point (``"sweep"``, ``"service_rebuild"``,
    ``"incremental_bulk"``, …); ``regime`` the internal strategy when one
    was selected (the bulk rematch's ``dense``/``jax``/``sort``, the
    sweep emission's ``expand``/``search`` slot map, the ddim generator
    choice, …).  ``attempts`` lists every capacity tried —
    ``len(attempts) - 1 == retries``.  ``phase_seconds`` keys follow the
    module-level vocabulary (``probe``/``emit``/``collect``; host-side
    engines use their own phase names, e.g. ``rematch``, plus the
    incremental index's ``splice``/``rank_patch`` surgery phases).
    ``blocks_touched`` counts the blocked endpoint index's per-batch
    block mutations (0 for non-blocked engines; DESIGN.md §13).
    ``readbacks`` counts the blocking device→host reads the call made
    before it returned (a planned 1-d sweep: the probe's four count
    partials, then the emission's count).  ``chips`` is the number of
    chips the call's programs spanned, and ``exchange_bytes`` the bytes
    their collectives moved between chips, computed from their shapes
    (0 on one chip).  ``call`` is a per-process number that the call's
    profiler spans carry with its ``engine``.
    """

    engine: str = ""
    regime: str = ""
    count: int = 0
    capacity: int = 0
    retries: int = 0
    recompiles: int = 0
    blocks_touched: int = 0
    readbacks: int = 0
    chips: int = 1
    exchange_bytes: int = 0
    attempts: List[int] = dataclasses.field(default_factory=list)
    phase_seconds: Dict[str, float] = dataclasses.field(default_factory=dict)
    call: int = dataclasses.field(default_factory=lambda: next(_CALLS),
                                  compare=False, repr=False)

    @property
    def waste(self) -> int:
        """Padded-vs-actual buffer waste of the final attempt."""
        return max(self.capacity - self.count, 0)

    @property
    def peak_buffer_elements(self) -> int:
        """Largest pair buffer materialized across attempts (elements,
        i.e. ``max_pairs * 2`` int32 slots of the widest attempt)."""
        return 2 * max(self.attempts, default=self.capacity)

    @property
    def splice_us(self) -> float:
        """Stream-surgery wall time in µs (the blocked/flat splice phase)."""
        return self.phase_seconds.get("splice", 0.0) * 1e6

    @property
    def rank_patch_us(self) -> float:
        """Rank-table rebuild/patch wall time in µs."""
        return self.phase_seconds.get("rank_patch", 0.0) * 1e6

    def add_phase(self, name: str, seconds: float) -> None:
        self.phase_seconds[name] = self.phase_seconds.get(name, 0.0) + seconds

    @contextlib.contextmanager
    def phase(self, name: str) -> Iterator[None]:
        """Time the enclosed code into ``phase_seconds[name]`` (wall
        seconds, summed over repeats) inside the host span ``ddm.<name>``.
        A body that raises records no time."""
        with self._span(name):
            t0 = time.perf_counter()
            yield
            self.add_phase(name, time.perf_counter() - t0)

    @contextlib.contextmanager
    def readback(self, phase: str, n: int = 1) -> Iterator[None]:
        """Count ``n`` blocking device→host reads made by the enclosed code
        and put them in the span ``ddm.<phase>.readback``."""
        self.readbacks += n
        with self._span(f"{phase}.readback"):
            yield

    def _span(self, name: str) -> ContextManager:
        annotation = _trace_annotation()
        if annotation is None:
            return contextlib.nullcontext()
        return annotation(f"ddm.{name}", engine=self.engine, call=self.call)

    def as_dict(self) -> Dict[str, object]:
        return {
            "engine": self.engine,
            "regime": self.regime,
            "count": self.count,
            "capacity": self.capacity,
            "retries": self.retries,
            "recompiles": self.recompiles,
            "blocks_touched": self.blocks_touched,
            "readbacks": self.readbacks,
            "chips": self.chips,
            "exchange_bytes": self.exchange_bytes,
            "attempts": list(self.attempts),
            "waste": self.waste,
            "peak_buffer_elements": self.peak_buffer_elements,
            "splice_us": self.splice_us,
            "rank_patch_us": self.rank_patch_us,
            "phase_seconds": dict(self.phase_seconds),
        }


class StatsRecorder:
    """Rolling aggregate of :class:`MatchStats` across calls.

    Keeps the last ``history`` records plus monotonic totals (calls,
    retries, recompiles, per-engine and per-regime call counts) —
    the backing store of :meth:`repro.core.service.DDMService.stats`.
    """

    def __init__(self, history: int = 64):
        self._history: Deque[MatchStats] = deque(maxlen=history)
        self.calls = 0
        self.retries = 0
        self.recompiles = 0
        self.by_engine: Dict[str, int] = {}
        self.by_regime: Dict[str, int] = {}

    def record(self, stats: MatchStats) -> MatchStats:
        self._history.append(stats)
        self.calls += 1
        self.retries += stats.retries
        self.recompiles += stats.recompiles
        if stats.engine:
            self.by_engine[stats.engine] = \
                self.by_engine.get(stats.engine, 0) + 1
        if stats.regime:
            self.by_regime[stats.regime] = \
                self.by_regime.get(stats.regime, 0) + 1
        return stats

    @property
    def last(self) -> Optional[MatchStats]:
        return self._history[-1] if self._history else None

    def history(self) -> List[MatchStats]:
        return list(self._history)

    def snapshot(self) -> Dict[str, object]:
        """JSON-able aggregate view (totals + the last record)."""
        return {
            "calls": self.calls,
            "retries": self.retries,
            "recompiles": self.recompiles,
            "by_engine": dict(self.by_engine),
            "by_regime": dict(self.by_regime),
            "last": self.last.as_dict() if self.last else None,
        }


# ---------------------------------------------------------------------------
# The executor — the one count-then-retry loop in the repo
# ---------------------------------------------------------------------------

def execute_enumeration(
    fn: Callable,
    subs,
    upds,
    *,
    estimate: Optional[int] = None,
    capacity: Optional[int] = None,
    policy: CapacityPolicy = DEFAULT_POLICY,
    engine: str = "",
    regime: str = "",
    stats: Optional[MatchStats] = None,
    recorder: Optional[StatsRecorder] = None,
):
    """Run ``fn(subs, upds, max_pairs=c) -> (buffer, count)`` under the
    repo-wide overflow contract, instrumented.

    The first attempt's capacity is ``capacity`` verbatim when given
    (callers that must pin an exact buffer, e.g. the exact-fit tests),
    else the planner's :func:`initial_capacity` from ``estimate``/policy.
    ``count > max_pairs`` means the buffer was short: the count is exact
    (for the selective d-dim sweep it is the generator candidate count,
    whose retry yields the exact K), so one growth step to its ladder
    bucket converges — a second retry only happens when the first
    retry's *post-filter* count revealed a larger requirement.

    Returns ``(buffer, count, stats)``; the buffer/count are the last
    attempt's device results (buffer padded with ``(-1, -1)``).  Raises
    :class:`CapacityError` on a hard-cap violation or when
    ``policy.max_attempts`` is exhausted.  Each attempt runs in the
    ``emit`` phase, its count read in ``emit.readback``.  A caller that
    ran the estimate's counting sweep in a ``probe`` phase hands that
    record in as ``stats`` (named there; ``engine``/``regime`` name a
    fresh record otherwise); ``recorder`` (when given) receives the stats.
    """
    if stats is None:
        stats = MatchStats(engine=engine, regime=regime)
    cap = (int(capacity) if capacity is not None
           else initial_capacity(estimate, policy))
    _arm_compile_probe()
    compiles_before = jit_compiles()
    for attempt in range(max(policy.max_attempts, 1)):
        stats.attempts.append(cap)
        with stats.phase("emit"):
            buf, count = fn(subs, upds, max_pairs=cap)
            with stats.readback("emit"):
                c = int(count)               # device sync: closes the phase
        if c <= cap:
            stats.count = c
            stats.capacity = cap
            stats.retries = attempt
            stats.recompiles = jit_compiles() - compiles_before
            if recorder is not None:
                recorder.record(stats)
            return buf, count, stats
        cap = next_capacity(c, cap, policy)
    raise CapacityError(
        f"enumeration never satisfied count <= max_pairs within "
        f"{policy.max_attempts} attempts (engine {stats.engine!r}, "
        f"attempts {stats.attempts})")


def pair_set(pairs) -> PairSet:
    """A padded ``(max_pairs, 2)`` buffer → ``{(i, j)}`` (drops the
    ``(-1, -1)`` padding)."""
    arr = np.asarray(pairs)
    if arr.size == 0:
        return set()
    arr = arr[arr[:, 0] >= 0]
    return {(int(i), int(j)) for i, j in arr}


def pairs_via_retry(fn, subs, upds, *, start_cap: int = 64,
                    policy: Optional[CapacityPolicy] = None,
                    engine: str = "",
                    recorder: Optional[StatsRecorder] = None) -> PairSet:
    """Exact pair set of an enumeration under the overflow contract.

    The set-returning face of :func:`execute_enumeration` (the historical
    test-harness entry point, now the production executor): runs the
    retry loop from ``start_cap``, materializes the final buffer on the
    host, and cross-checks that the buffer holds exactly ``count`` pairs
    (a miscounting engine fails loudly here, not in a downstream diff).
    """
    policy = policy or DEFAULT_POLICY
    buf, count, stats = execute_enumeration(
        fn, subs, upds, capacity=start_cap, policy=policy, engine=engine)
    with stats.phase("collect"):
        with stats.readback("collect"):
            host = np.asarray(buf)
        got = pair_set(host)
    if recorder is not None:
        recorder.record(stats)
    c = int(count)
    if len(got) != c:
        raise AssertionError(
            f"buffer holds {len(got)} pairs but count says {c}")
    return got


# ---------------------------------------------------------------------------
# Bulk-rematch regime policy (the incremental engine's dense/jax/sort)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BulkRegimePolicy:
    """Thresholds of the stacked bulk rematch's three regimes.

    ``b·m <= dense_max_elems``: one dense numpy mask (lowest constant, no
    sort setup).  ``b·m <= jax_max_elems``: the jitted fused mask (one
    multithreaded pass, pow2-padded shapes).  Above: the output-sensitive
    sort-based candidates path.  Defaults are the crossovers measured at
    m=1e5 on this container (EXPERIMENTS.md §Churn): dense wins to
    b·m ≈ 2e6, jax to ≈ 2e7, sort beyond.  ``force`` pins a regime
    outright — the audit/benchmark knob (each regime reports its name in
    :class:`MatchStats`, so a forced run is verifiable from stats).
    """

    dense_max_elems: int = 1 << 21
    jax_max_elems: int = 1 << 24
    force: Optional[str] = None

    def __post_init__(self):
        if self.force is not None and self.force not in BULK_REGIMES:
            raise ValidationError(
                f"force must be one of {BULK_REGIMES}, got {self.force!r}")


BULK_REGIMES = ("dense", "jax", "sort")
DEFAULT_BULK_POLICY = BulkRegimePolicy()


def select_bulk_regime(b: int, m: int,
                       policy: BulkRegimePolicy = DEFAULT_BULK_POLICY) -> str:
    """Regime of a b-query × m-counterpart stacked rematch under a policy."""
    if policy.force is not None:
        return policy.force
    elems = b * m
    if elems <= policy.dense_max_elems:
        return "dense"
    if elems <= policy.jax_max_elems:
        return "jax"
    return "sort"
