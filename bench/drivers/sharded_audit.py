"""Sharded static audit: one stateless match of a whole HLA region set
across the four chips of a host.

One audit is ``repro.core.sbm_enumerate_planned(..., mesh=mesh)`` over the
set (the sort across the mesh and the probe count, then the planned
emission, the pair buffer sharded over the chips) and the K valid pairs
delivered to the host: each chip's shard of the buffer pulled, in
parallel, and its (-1, -1) rows dropped.  Audits cycle through ``sets``
sets drawn from the seed at set-up, made on the device already sharded.

``correct`` is decided as in ``static_audit.py``: for each set audited in
the window, one of its audits drawn from the seed (a reservoir of one per
set), exact K and every overlapping pair exactly once, by
``bench/reference.py`` with weights of fewer bits (:func:`weights`).  The
reference runs after the window on sorted copies of the bounds, on slices
of the subscriptions in parallel threads (:func:`reference_summary`).

The ``control`` system puts the reference on float32-rounded bounds, the
precision below the configuration's int32, in the matcher's place; it
answers with a :class:`reference.Summary` (``bench/control.py``).
"""
from __future__ import annotations

import inspect
import json
import math
import os
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

from bench import reference
from bench.harness import NoDevice, SpecError
from bench.traffic import hla, paper

SPANS = ("audit.match", "audit.d2h")
CHIPS = 4
INTERCONNECT = Path(__file__).resolve().parents[1] / "interconnect.json"


def build(ctx):
    return ShardedAudit(ctx)


def _valid_rows(shard) -> np.ndarray:
    out = np.asarray(shard.data)
    return out[out[:, 0] >= 0]


def _as_is(out):
    return out


class _Stats:
    """The part of ``MatchStats`` an audit reads, for the control."""

    def __init__(self, count: int):
        self.count = count
        self.phase_seconds = {}
        self.exchange_bytes = 0


def to_f32(x) -> np.ndarray:
    """``x`` rounded to float32: the control's precision (24 bits of the
    31 that the bounds use)."""
    return np.asarray(x).astype(np.float32)


def _interconnect(kind: str):
    """The chip's interconnect bandwidth, or None off a TPU."""
    table = json.loads(INTERCONNECT.read_text())["devices"]
    return table[kind]["ici_bytes_per_s"] if kind in table else None


def weights(m: int, seed: int) -> np.ndarray:
    """``reference.weights`` cut to the bits that keep the reference's
    weight sums exact over m updates.  Its prefix sums come out float64
    (numpy joins its uint64 cumsum to a Python 0 by promoting both), which
    is exact only below 2**53: 32-bit weights stop being exact past about
    2·10⁶ updates, and this cell has 10⁸."""
    bits = min(32, 53 - math.ceil(math.log2(max(m, 2))))
    return np.floor(reference.weights(m, seed) / 2.0 ** (32 - bits))


def reference_summary(s_lo, s_hi, u_lo, u_hi, w, threads: int):
    """``reference.reference_summary`` of every subscription, with the
    subscriptions in the order of their upper bounds and the updates in
    that of their lower bounds (which makes its binary searches walk
    memory in order), over slices of the subscriptions in parallel
    threads.  Returns the summary, in that subscription order, and the
    order.  A subscription's answer depends on it and the set of updates
    alone, whatever their order or the slice it is in."""
    with ThreadPoolExecutor(2) as pool:
        by_hi, by_lo = pool.map(np.argsort, (s_hi, u_lo))
    s_lo, s_hi = s_lo[by_hi], s_hi[by_hi]
    u_lo, u_hi, w = u_lo[by_lo], u_hi[by_lo], w[by_lo]
    cuts = np.linspace(0, s_lo.shape[0], threads + 1).astype(np.int64)
    with ThreadPoolExecutor(threads) as pool:
        parts = list(pool.map(
            lambda k: reference.reference_summary(
                s_lo[cuts[k]:cuts[k + 1]], s_hi[cuts[k]:cuts[k + 1]],
                u_lo, u_hi, w), range(threads)))
    return reference.Summary(np.concatenate([p.count for p in parts]),
                             np.concatenate([p.wsum for p in parts]),
                             sum(p.total for p in parts)), by_hi


def pairs_summary(rows, order, n: int, m: int, w):
    """``reference.pairs_summary`` of delivered pairs with the
    subscriptions relabelled into ``order``; a subscription index out of
    range stays out of range."""
    i = rows[:, 0].astype(np.int64)
    rank = np.empty(n, np.int64)
    rank[order] = np.arange(n)
    ok = (i >= 0) & (i < n)
    i = np.where(ok, rank[np.where(ok, i, 0)], -1)
    return reference.pairs_summary(i, rows[:, 1], n, m, w)


class ShardedAudit:
    SPANS = SPANS

    def __init__(self, ctx):
        import jax
        from jax.profiler import TraceAnnotation

        from repro.core import Extents, sbm_enumerate_planned
        from repro.core.runtime import StatsRecorder, round_up_pow2

        if "mesh" not in inspect.signature(sbm_enumerate_planned).parameters:
            raise SpecError("sbm_enumerate_planned takes no mesh: the "
                            "system under test cannot run this cell")
        if ctx.system not in ("program", "control"):
            raise SpecError(f"this cell has no {ctx.system!r} system")
        cfg, mix = ctx.config, ctx.traffic
        devices = jax.devices()
        if len(devices) < CHIPS:
            raise NoDevice(f"the cell needs {CHIPS} devices, JAX sees "
                           f"{len(devices)}")
        self.mesh = jax.make_mesh((CHIPS,), ("p",), devices=devices[:CHIPS])
        self.ctx = ctx
        self.annotate = TraceAnnotation
        self.match = sbm_enumerate_planned
        self.n = int(cfg["n_sub"])
        self.m = int(cfg["n_extents"]) - self.n
        self.n_sets = int(mix["sets"])
        self.recorder = StatsRecorder(history=1)
        self.rng = paper.host_rng(ctx.seed, 1)
        self.w = weights(self.m, ctx.seed)
        self.pool = ThreadPoolExecutor(CHIPS)
        self.spans = []
        self.counters = {"least_bytes": 0.0, "exchange_bytes": 0.0}
        ici = _interconnect(devices[0].device_kind)
        if ici is not None:
            self.counters["ici_bytes_per_s"] = float(ici)
        elif devices[0].platform == "tpu":
            raise SpecError(f"no interconnect bandwidth for "
                            f"{devices[0].device_kind!r} in {INTERCONNECT}")
        self.failed = 0

        t = time.perf_counter()
        self.bounds = hla.uniform_sets(
            paper.device_key(ctx.seed, 0), self.n_sets,
            int(cfg["n_extents"]), self.n, float(mix["alpha"]),
            int(cfg["length"]), self.mesh)
        jax.block_until_ready(self.bounds)
        self.host = None
        if ctx.system == "control":
            self.host = [tuple(np.asarray(a) for a in b) for b in self.bounds]
            self.sets = [((to_f32(s_lo), to_f32(s_hi)),
                          (to_f32(u_lo), to_f32(u_hi)))
                         for s_lo, s_hi, u_lo, u_hi in self.host]
            self.match, self.deliver = self._f32_match, _as_is
        else:
            self.sets = [(Extents(s_lo, s_hi), Extents(u_lo, u_hi))
                         for s_lo, s_hi, u_lo, u_hi in self.bounds]
        t = ctx.phase("datagen", t)
        if ctx.system == "control":
            self.kept, self.seen, self.i = {}, [0] * self.n_sets, 0
            ctx.phase("warmup", t)
            return

        # warm-up: one audit of the first set, then one of each other
        # pair-buffer bucket the sets need
        from repro.core import sbm_count_sharded

        self.kept, self.seen = {}, [0] * self.n_sets
        self.i = 0
        self.step()
        buckets = {round_up_pow2(max(self.recorder.last.count, 1)): 0}
        for k in range(1, self.n_sets):
            count = int(sbm_count_sharded(*self.sets[k], self.mesh, "p"))
            buckets.setdefault(round_up_pow2(max(count, 1)), k)
        for k in list(buckets.values())[1:]:
            self.i = k
            self.step()
        self.kept, self.seen = {}, [0] * self.n_sets
        self.spans = []
        self.counters.update(least_bytes=0.0, exchange_bytes=0.0)
        self.i = 0
        ctx.phase("warmup", t)

    def deliver(self, pairs):
        """Each chip's shard of the pair buffer pulled, padding dropped."""
        return list(self.pool.map(_valid_rows, pairs.addressable_shards))

    def _f32_match(self, subs, upds, recorder=None, mesh=None):
        """The reference on float32 bounds, in the matcher's place: the
        control.  It answers with a :class:`reference.Summary`, its
        subscriptions in their own order."""
        threads = max(1, min(8, os.cpu_count() or 1))
        got, order = reference_summary(*subs, *upds, self.w, threads)
        count, wsum = np.empty_like(got.count), np.empty_like(got.wsum)
        count[order], wsum[order] = got.count, got.wsum
        out = reference.Summary(count, wsum, got.total)
        return out, out.total, _Stats(out.total)

    def step(self) -> float:
        k = self.i % self.n_sets
        self.i += 1
        subs, upds = self.sets[k]
        t0 = time.perf_counter()
        with self.annotate("audit.match"):
            pairs, _, stats = self.match(subs, upds, recorder=self.recorder,
                                         mesh=self.mesh)
        t1 = time.perf_counter()
        with self.annotate("audit.d2h"):
            out = self.deliver(pairs)
        t2 = time.perf_counter()
        count = stats.count
        self.seen[k] += 1
        if self.rng.random() * self.seen[k] < 1.0:
            self.kept[k] = (out, count)
        self.counters["least_bytes"] += 8.0 * (self.n + self.m) + 8.0 * count
        self.counters["exchange_bytes"] += float(stats.exchange_bytes)
        if self.ctx.trace:
            self.spans.append({"probe": stats.phase_seconds.get("probe", 0.0),
                               "emit": stats.phase_seconds.get("emit", 0.0),
                               "d2h": t2 - t1})
        return t2 - t0

    def finish(self) -> None:
        """Free the program's device state, keeping host copies of the
        sets the reference will judge."""
        self.host = {k: self.host[k] if self.host is not None else
                     tuple(np.asarray(a) for a in self.bounds[k])
                     for k in self.kept}
        self.sets = self.bounds = None
        self.pool.shutdown()

    def check(self) -> dict:
        n, m, w = self.n, self.m, self.w
        threads = max(1, min(8, os.cpu_count() or 1))
        wrong = k_diff = 0
        for k, (out, count) in sorted(self.kept.items()):
            s_lo, s_hi, u_lo, u_hi = self.host[k]
            want, order = reference_summary(s_lo, s_hi, u_lo, u_hi, w,
                                            threads)
            if isinstance(out, reference.Summary):
                got = reference.Summary(out.count[order], out.wsum[order],
                                        out.total, out.bad)
            else:
                got = pairs_summary(np.concatenate(out), order, n, m, w)
            wrong += reference.subs_wrong(got, want)
            k_diff += abs(got.total - want.total) + abs(int(count)
                                                        - want.total)
        self.ctx.log(f"compared: one audit of each of {len(self.kept)} "
                     f"sets audited in the window")
        return {"subs_wrong": (wrong, 0), "k_diff": (k_diff, 0)}
