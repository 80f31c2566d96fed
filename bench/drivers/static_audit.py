"""Static audit: one stateless match of a whole paper §5 set.

One audit is ``repro.core.sbm_enumerate_planned`` over the set (the probe
count, then the planned emission) and the K valid pairs delivered to the
host as an int array: the padded buffer pulled and its (-1, -1) rows
dropped, as ``DDMService`` does.  Audits cycle through ``sets`` sets drawn
from the seed at set-up, so no result carries over between audits.

The reference judges, for each set audited in the window, one of its
audits drawn from the seed (reservoir sampling): exact K, and every
overlapping pair exactly once.
"""
from __future__ import annotations

import time

import numpy as np

from bench import reference
from bench.traffic import paper

SPANS = ("audit.match", "audit.d2h")


def build(ctx):
    return StaticAudit(ctx)


def _valid_rows(pairs) -> np.ndarray:
    """The padded pair buffer pulled to the host, its (-1, -1) rows dropped."""
    out = np.asarray(pairs)
    return out[out[:, 0] >= 0]


def _as_is(out):
    return out


class _Stats:
    """The part of ``MatchStats`` an audit reads, for the control."""

    def __init__(self, count: int):
        self.count = count
        self.phase_seconds = {}


class StaticAudit:
    SPANS = SPANS

    def __init__(self, ctx):
        import jax
        from jax.profiler import TraceAnnotation

        from repro.core import Extents, sbm_enumerate_planned
        from repro.core.runtime import StatsRecorder

        cfg, mix = ctx.config, ctx.traffic
        self.ctx = ctx
        self.annotate = TraceAnnotation
        self.n = int(cfg["n_sub"])
        self.m = int(cfg["n_extents"]) - self.n
        self.n_sets = int(mix["sets"])
        self.recorder = StatsRecorder(history=1)
        self.rng = paper.host_rng(ctx.seed, 1)
        self.w = reference.weights(self.m, ctx.seed)
        self.spans, self.counters = [], {"least_bytes": 0.0}
        self.failed = 0

        t = time.perf_counter()
        lo, hi = paper.uniform_sets(paper.device_key(ctx.seed, 0),
                                    self.n_sets, int(cfg["n_extents"]),
                                    float(mix["alpha"]),
                                    float(cfg["length"]))
        jax.block_until_ready((lo, hi))
        self.bounds = (lo, hi)
        n = self.n

        @jax.jit
        def split(lo, hi):
            return [(Extents(lo[k, :n], hi[k, :n]),
                     Extents(lo[k, n:], hi[k, n:]))
                    for k in range(self.n_sets)]

        self.host = None
        if ctx.system == "control":
            self.host = (np.asarray(lo), np.asarray(hi))
            self.sets = [((reference.to_bf16(self.host[0][k, :n]),
                           reference.to_bf16(self.host[1][k, :n])),
                          (reference.to_bf16(self.host[0][k, n:]),
                           reference.to_bf16(self.host[1][k, n:])))
                         for k in range(self.n_sets)]
            self.match, self.deliver = self._bf16_match, _as_is
        else:
            self.sets = jax.block_until_ready(split(lo, hi))
            self.match, self.deliver = sbm_enumerate_planned, _valid_rows
        t = ctx.phase("datagen", t)

        # warm-up: one audit of the first set in each pair-buffer bucket
        from repro.core.runtime import round_up_pow2
        from repro.core.sweep import sbm_count_exact

        buckets = {}
        if ctx.system != "control":
            for k, (subs, upds) in enumerate(self.sets):
                buckets.setdefault(round_up_pow2(max(
                    sbm_count_exact(subs, upds), 1)), k)
        self.kept, self.seen = {}, [0] * self.n_sets
        self.i = 0
        for k in buckets.values():
            self.i = k
            self.step()
        self.kept, self.seen = {}, [0] * self.n_sets
        self.spans, self.counters["least_bytes"] = [], 0.0
        self.i = 0
        ctx.phase("warmup", t)

    def step(self) -> float:
        k = self.i % self.n_sets
        self.i += 1
        subs, upds = self.sets[k]
        t0 = time.perf_counter()
        with self.annotate("audit.match"):
            pairs, _, stats = self.match(subs, upds, recorder=self.recorder)
        t1 = time.perf_counter()
        with self.annotate("audit.d2h"):
            out = self.deliver(pairs)
        t2 = time.perf_counter()
        count = stats.count
        # reservoir of one per set: each audit of set k is kept with
        # probability 1/(audits of k so far)
        self.seen[k] += 1
        if self.rng.random() * self.seen[k] < 1.0:
            self.kept[k] = (out, count)
        self.counters["least_bytes"] += 8.0 * (self.n + self.m) + 8.0 * count
        if self.ctx.trace:
            self.spans.append({"probe": stats.phase_seconds.get("probe", 0.0),
                               "emit": stats.phase_seconds.get("emit", 0.0),
                               "d2h": t2 - t1})
        return t2 - t0

    def _bf16_match(self, subs, upds, recorder=None):
        """The reference on bfloat16 bounds, in the matcher's place: the
        control.  It answers with a :class:`reference.Summary`."""
        out = reference.reference_summary(*subs, *upds, self.w)
        return out, out.total, _Stats(out.total)

    def finish(self) -> None:
        """Free the program's device state, keeping host copies of the sets."""
        if self.host is None:
            self.host = (np.asarray(self.bounds[0]),
                         np.asarray(self.bounds[1]))
        self.sets = self.bounds = None

    def check(self) -> dict:
        n, m, w = self.n, self.m, self.w
        wrong = k_diff = 0
        for k, (out, count) in sorted(self.kept.items()):
            lo, hi = self.host[0][k], self.host[1][k]
            want = reference.reference_summary(lo[:n], hi[:n], lo[n:], hi[n:],
                                               w)
            got = out if isinstance(out, reference.Summary) else \
                reference.pairs_summary(out[:, 0], out[:, 1], n, m, w)
            wrong += reference.subs_wrong(got, want)
            k_diff += abs(got.total - want.total) + abs(int(count)
                                                        - want.total)
        self.ctx.log(f"compared: one audit of each of {len(self.kept)} "
                     f"sets audited in the window")
        return {"subs_wrong": (wrong, 0), "k_diff": (k_diff, 0)}
