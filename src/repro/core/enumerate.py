"""Pair enumeration — the O(K) emission phase with TPU-legal shapes.

TPUs cannot append to a dynamically sized list (the paper's ``L ← L ∪ {..}``
under an atomic).  The standard adaptation is count → prefix offsets →
scatter: a first pass sizes the output, a second writes each pair to its
precomputed slot.  Output buffers are padded to a static ``max_pairs``.

Two engines behind the same (pairs, count) contract:

* :func:`sbm_enumerate` — the sort-based sweep, output-sensitive
  O((n+m)·log(n+m) + max_pairs).  Per-extent emission counts come from the
  same indicator cumsums as :func:`repro.core.sweep.sbm_count`; their
  exclusive scan is the offset table.  Each output slot finds its emitter
  and its rank in it, then one gather per slot reads the counterpart
  (DESIGN.md §3).  Large buffers expand the offset table over the slots by
  a scatter and a prefix scan; small ones binary-search it per slot, which
  is cheaper there (:func:`_slot_map`).  :func:`sbm_enumerate_sharded`
  runs the scheme, with the search, across a device mesh axis;
  :func:`repro.kernels.sbm_enumerate_kernel` is the Pallas on-chip form.
* :func:`enumerate_matches` — blocked all-pairs O(n·m) + stream compaction.
  Kept as the cross-check oracle and for tiny inputs where the sort
  dominates.

Overflow contract (all engines): pairs beyond ``max_pairs`` are dropped but
still counted — callers check ``count <= max_pairs`` and retry bigger.
"""
from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import prefix as prefix_lib
from repro.core import runtime as runtime_lib
from repro.core.intervals import Extents, intersect_1d
from repro.core.runtime import round_up_pow2  # noqa: F401 — canonical ladder
from repro.core.sweep import (_indicator_deltas, _pad_stream,
                              emission_rank_tables, encode_endpoints,
                              rank_tables_from_cumsums, resolve_cumsum)


def _count_dtype():
    """Pair counts accumulate in int64 under x64 (K can exceed 2^31 even
    when every per-emitter count fits int32); int32 otherwise — the same
    convention as :func:`repro.core.sweep.sbm_count`."""
    return jnp.int64 if jax.config.read("jax_enable_x64") else jnp.int32


def _offset_cumsum(counts: jax.Array) -> jax.Array:
    """Offset-table cumsum with the repo-wide K ≥ 2³¹ contract.

    Under x64 the scan runs in exact int64.  Without x64 it *saturates* at
    2³¹−1 (:func:`repro.core.prefix.cumsum_saturating_i32`) instead of
    wrapping: the table stays monotonic, so slot→emitter binary search stays
    correct for every slot < ``max_pairs`` (necessarily < 2³¹), and the
    returned count pins at the 2³¹−1 sentinel rather than going negative.
    Callers needing the true K beyond the sentinel use
    :func:`repro.core.sweep.sbm_count_exact`.
    """
    if jax.config.read("jax_enable_x64"):
        return jnp.cumsum(counts, dtype=jnp.int64)
    return prefix_lib.cumsum_saturating_i32(counts)


def _empty_result(max_pairs: int):
    return (jnp.full((max_pairs, 2), -1, jnp.int32),
            jnp.zeros((), _count_dtype()))


# ---------------------------------------------------------------------------
# Sweep-based enumeration (the paper's emission phase, output-sensitive)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("max_pairs", "num_segments",
                                             "scan_impl"))
def _sbm_enumerate_jit(subs: Extents, upds: Extents, *, max_pairs: int,
                       num_segments: int, scan_impl: str):
    n = subs.lo.shape[0]
    m = upds.lo.shape[0]
    # named device stages (metadata only): the profiler reads them back
    # from each instruction's op_name
    with jax.named_scope("ddm.sort"):
        ep = _pad_stream(encode_endpoints(subs, upds), num_segments)
    with jax.named_scope("ddm.ranks"):
        cumsum_fn = resolve_cumsum(scan_impl, num_segments)
        a_start, a_cnt, b_start, b_cnt, subs_by_lo, upds_by_lo = \
            emission_rank_tables(ep, n, m, cumsum_fn)

        # Offset table: exclusive scan of per-emitter counts (emitters are
        # the n subs then the m upds; the scan is over n+m entries, not the
        # stream).  Without x64 it saturates at 2^31-1 instead of wrapping
        # (_offset_cumsum).
        counts = jnp.concatenate([a_cnt, b_cnt])
        off = _offset_cumsum(counts)
        k_total = off[-1]

    pairs = _emit_pairs(off, counts, k_total, a_start, b_start, subs_by_lo,
                        upds_by_lo, max_pairs=max_pairs,
                        num_segments=num_segments,
                        form=_slot_map(max_pairs, n + m))
    return pairs, k_total


def _emit_pairs(off, counts, k_total, a_start, b_start, subs_by_lo,
                upds_by_lo, *, max_pairs: int, num_segments: int,
                form: str) -> jax.Array:
    """Slot-parallel emission from the offset and rank tables.

    Slot s belongs to the emitter whose offset range contains it; its rank
    within the emitter selects the counterpart by lower-endpoint rank (a
    contiguous range — see emission_rank_tables).  ``form`` is how slots
    find their emitter (:func:`_slot_map`); both give the same buffer.
    """
    n = a_start.shape[0]
    m = b_start.shape[0]
    with jax.named_scope("ddm.search"):
        slots = jnp.arange(max_pairs, dtype=jnp.int32)
    if form == "expand":
        # One rank→id table: a subscription emitter's counterparts are
        # upds_by_lo[a_start + r], an update emitter's subs_by_lo[b_start + r]
        # = table[m + b_start + r]; with r = s - excl, each emitter's base
        # makes the counterpart table[base[e(s)] + s].
        with jax.named_scope("ddm.search"):
            excl = off - counts
            base = jnp.concatenate([a_start, m + b_start]) - excl
        e, base_s = _expand_slots(excl, base, max_pairs, num_segments)
        with jax.named_scope("ddm.gather"):
            table = jnp.concatenate([upds_by_lo, subs_by_lo])
            c = table[jnp.minimum(base_s + slots, n + m - 1)]
            is_a = e < n
            pi = jnp.where(is_a, e, c)
            pj = jnp.where(is_a, c, e - n)
    else:
        e, r = _search_slots(slots, off, counts)
        with jax.named_scope("ddm.gather"):
            is_a = e < n
            j_of_a = upds_by_lo[jnp.clip(a_start[jnp.minimum(e, n - 1)] + r,
                                         0, m - 1)]
            i_of_b = subs_by_lo[jnp.clip(b_start[jnp.clip(e - n, 0, m - 1)]
                                         + r, 0, n - 1)]
            pi = jnp.where(is_a, e, i_of_b)
            pj = jnp.where(is_a, j_of_a, e - n)
    with jax.named_scope("ddm.gather"):
        valid = slots < jnp.minimum(k_total, max_pairs)
        return jnp.where(valid[:, None], jnp.stack([pi, pj], axis=-1), -1)


def _slot_map(max_pairs: int, n_emitters: int) -> str:
    """How the emission maps its output slots to emitters, from the shapes.

    ``"search"`` binary-searches the offset table for every slot: about
    ⌈log2(n+m)⌉ dependent probes a slot, then a gather per table.
    ``"expand"`` scatters two marks per emitter and scans the slots: about
    2·(n+m) scattered updates, one pass over the slots, one gather.  The
    expansion is taken where the search's probes outnumber its updates.
    """
    probes = max_pairs * math.ceil(math.log2(n_emitters))
    return "expand" if probes > 2 * n_emitters else "search"


def _search_slots(slots: jax.Array, off: jax.Array, counts: jax.Array):
    """Emitter of every slot and the slot's rank within it, by binary
    search of the inclusive offset table ``off``."""
    with jax.named_scope("ddm.search"):
        e = jnp.searchsorted(off, slots, side="right").astype(jnp.int32)
        e = jnp.minimum(e, off.shape[0] - 1)
    with jax.named_scope("ddm.gather"):
        return e, slots - (off[e] - counts[e])


def _expand_slots(excl: jax.Array, base: jax.Array, max_pairs: int,
                  num_segments: int):
    """Emitter of every slot and that emitter's ``base``, by run-length
    expansion: no per-slot search and no per-emitter gather.

    Emitter e marks the first slot of its range, ``excl[e]``, with 1 and with
    the step ``base[e] - base[e-1]``; an inclusive scan over the slots then
    gives slot s the number of emitters starting at or before it, e(s) + 1,
    and the telescoped sum ``base[e(s)]``.  Emitters with nothing to emit
    share the next one's start and cancel out; emitters starting at or past
    ``max_pairs`` own no slot and are dropped.  ``excl`` is non-decreasing
    below ``max_pairs``; ``base`` of every emitter that owns a slot fits
    int32, and the int32 sums wrap harmlessly (the result fits).  The scans
    are two-level scans over ``gcd(max_pairs, num_segments)`` segments,
    which measured faster on a v5e than one ``jnp.cumsum``.
    """
    with jax.named_scope("ddm.search"):
        at = jnp.minimum(excl, max_pairs).astype(jnp.int32)
        base = base.astype(jnp.int32)
        step = base - jnp.concatenate([jnp.zeros((1,), jnp.int32), base[:-1]])

        def scan(marks):
            return prefix_lib.cumsum_two_level(
                jnp.zeros((max_pairs,), jnp.int32).at[at].add(
                    marks, mode="drop"),
                math.gcd(max_pairs, num_segments))

        return scan(jnp.ones_like(at)) - 1, scan(step)


def sbm_enumerate(subs: Extents, upds: Extents, *, max_pairs: int,
                  num_segments: int = 8, scan_impl: str = "two_level"
                  ) -> Tuple[jax.Array, jax.Array]:
    """All matching (i, j) pairs via the sort-based sweep (1-d extents).

    O((n+m)·log(n+m) + max_pairs), one gather per output slot: no n×m
    intermediate is ever formed.  Slots find their emitters by a scatter
    and prefix scan over the buffer, or, where the buffer is small against
    n+m, by binary search (:func:`_slot_map`); both give the same buffer.
    Returns (pairs (max_pairs, 2) int32 padded with (-1, -1), count) with
    the same overflow contract as :func:`enumerate_matches`.
    Deterministic order: subscription emitters by id, then update emitters
    by id, each range ordered by the counterpart's lower-endpoint rank.
    Requires well-formed extents (lo <= hi) — like :func:`sbm_count`.
    """
    if subs.lo.shape[0] == 0 or upds.lo.shape[0] == 0:
        return _empty_result(max_pairs)
    return _sbm_enumerate_jit(subs, upds, max_pairs=max_pairs,
                              num_segments=num_segments, scan_impl=scan_impl)


def sbm_enumerate_planned(subs: Extents, upds: Extents, *,
                          num_segments: int = 8,
                          scan_impl: str = "two_level",
                          policy: runtime_lib.CapacityPolicy =
                          runtime_lib.DEFAULT_POLICY,
                          recorder: runtime_lib.StatsRecorder | None = None):
    """Plan-aware sweep enumeration: probe → plan → emit, instrumented.

    Runs the counting sweep as the planner's selectivity probe, sizes
    ``max_pairs`` to the exact K's ladder bucket, and executes the
    emission under the runtime's retry loop (structurally zero retries:
    the probe count is exact).  ``stats.regime`` names the slot map the
    emission took, ``"expand"`` or ``"search"`` (:func:`_slot_map`).
    Returns ``(pairs, count, stats)`` — the production face of
    :func:`sbm_enumerate` (DESIGN.md §10).
    """
    from repro.core.sweep import probe_count

    stats = runtime_lib.MatchStats(engine="sweep")
    if subs.size == 0 or upds.size == 0:
        stats.add_phase("probe", 0.0)
        if recorder is not None:
            recorder.record(stats)
        return jnp.full((0, 2), -1, jnp.int32), jnp.int32(0), stats

    with stats.phase("probe"):
        k = probe_count(subs, upds, stats, num_segments=num_segments,
                        scan_impl=scan_impl)

    def fn(s, u, *, max_pairs):
        stats.regime = _slot_map(max_pairs, s.lo.shape[0] + u.lo.shape[0])
        return sbm_enumerate(s, u, max_pairs=max_pairs,
                             num_segments=num_segments, scan_impl=scan_impl)

    return runtime_lib.execute_enumeration(
        fn, subs, upds, estimate=k, policy=policy, stats=stats,
        recorder=recorder)


def sbm_enumerate_sharded(subs: Extents, upds: Extents, mesh, axis_name: str,
                          *, max_pairs: int,
                          max_pairs_per_shard: int | None = None
                          ) -> Tuple[jax.Array, jax.Array]:
    """Distributed sweep enumeration over one mesh axis.

    Mirrors :func:`repro.core.sweep.sbm_count_sharded`: the sorted stream is
    split into contiguous shards, global indicator cumsums run as the
    distributed two-level scan, and each shard emits the pairs whose
    emitting upper endpoint it owns.  Global pair offsets are the
    psum'd/all-gathered per-shard emission totals.  The output buffer is
    sharded over ``axis_name`` in equal slot ranges: each shard emits the
    pairs of its global range that fall in every destination's slots and
    one ``all_to_all`` delivers them, so a chip holds O(max_pairs) pair
    slots in flight and keeps max_pairs / P of the result.  The rank→id
    tables are psum-combined (O(n+m) comm — the pair payload itself is the
    dominant output).

    The buffer has ``max_pairs`` rounded up to a positive multiple of the
    shard count rows (an uneven row sharding does not exist); rows past
    ``min(count, max_pairs)`` are −1.  A shard keeps at most
    ``max_pairs_per_shard`` (default ``max_pairs``) of its pairs and drops
    the excess, but the returned count is still exact.  Without x64, a
    global K ≥ 2³¹ pins the count at the 2³¹−1 sentinel and returns an
    all-(-1) buffer (the cross-shard offsets would wrap) — never silently
    wrong pairs.
    """
    from jax.sharding import PartitionSpec as P

    n = subs.lo.shape[0]
    m = upds.lo.shape[0]
    if n == 0 or m == 0:
        return _empty_result(max_pairs)
    cdtype = _count_dtype()
    cap = max_pairs if max_pairs_per_shard is None else max_pairs_per_shard
    num_shards = mesh.shape[axis_name]
    per_shard = -(-max(max_pairs, 1) // num_shards)  # output slots per shard
    ep = _pad_stream(encode_endpoints(subs, upds), num_shards)
    sub_lo, sub_up, upd_lo, upd_up = _indicator_deltas(ep)
    owner = ep.owner
    is_upper = ep.is_upper.astype(jnp.int32)
    is_sub = ep.is_sub.astype(jnp.int32)

    def body(sub_lo, upd_lo, owner, is_upper, is_sub):
        # Stream-position cumsums are bounded by the stream length and
        # always fit int32 (unlike the pair counts below); pin the dtype so
        # the rank-table scatters stay int32 under x64.
        c_sub_lo = prefix_lib.shard_inclusive_cumsum(
            sub_lo, axis_name).astype(jnp.int32)
        c_upd_lo = prefix_lib.shard_inclusive_cumsum(
            upd_lo, axis_name).astype(jnp.int32)

        # Rank tables: the same class-A/B construction as the single-device
        # path; each extent's endpoints live on some shard, so the psum
        # combine assembles the full (n,)/(m,) tables on every shard.
        a_start, a_cnt, b_start, b_cnt, subs_by_lo, upds_by_lo = \
            rank_tables_from_cumsums(
                is_sub == 1, is_upper == 1, owner, c_sub_lo, c_upd_lo, n, m,
                combine=lambda t: lax.psum(t, axis_name))

        # local emission: one count per local upper endpoint (the emitter's
        # class count, gathered from the global tables at its owner)
        real = owner >= 0
        sel_s_up = (is_sub == 1) & (is_upper == 1) & real
        sel_u_up = (is_sub == 0) & (is_upper == 1) & real
        o_c = jnp.clip(owner, 0)
        cnt = jnp.where(sel_s_up, a_cnt[jnp.minimum(o_c, n - 1)], 0)
        cnt = cnt + jnp.where(sel_u_up, b_cnt[jnp.minimum(o_c, m - 1)], 0)
        # per-shard offsets: int64-exact under x64, saturating int32 without
        # (the aggregate psum'd count is exact only below 2^31 in that case)
        lc = _offset_cumsum(cnt)
        local_total = lc[-1]
        base = prefix_lib.shard_exclusive_offsets(local_total, axis_name)
        if cdtype == jnp.int64:
            k_total = lax.psum(local_total, axis_name)
            overflow = jnp.zeros((), jnp.bool_)
        else:
            # psum of int32 local totals can wrap even when every shard is
            # below the sentinel — combine 15-bit lanes (each psum provably
            # fits int32 for any realistic shard count) and saturate, so
            # the aggregate honors the same never-wrap contract as
            # _offset_cumsum.  When the aggregate does overflow, the
            # cross-shard offsets (base) would wrap and route pairs to the
            # wrong output slots, so the overflow
            # flag blanks the pair buffer: callers get the 2^31-1 count
            # sentinel and an all-(-1) buffer, never silently wrong pairs.
            hi = lax.psum(local_total >> 15, axis_name)
            lo15 = lax.psum(local_total & 0x7FFF, axis_name)
            s = (hi << 15) + lo15
            overflow = (hi >= 1 << 16) | (s < 0)
            k_total = jnp.where(overflow, jnp.int32((1 << 31) - 1), s)

        # global slot g = dest·per_shard + t is this shard's local pair
        # g − base; row dest of the send buffer goes to shard dest
        g = (jnp.arange(num_shards, dtype=jnp.int32)[:, None] * per_shard
             + jnp.arange(per_shard, dtype=jnp.int32)[None, :]).reshape(-1)
        local = g - base
        lvalid = ((local >= 0) & (local < jnp.minimum(local_total, cap))
                  & (g < max_pairs) & ~overflow)
        slots = jnp.clip(local, 0).astype(jnp.int32)
        epos = jnp.searchsorted(lc, slots, side="right").astype(jnp.int32)
        epos = jnp.minimum(epos, lc.shape[0] - 1)
        r = slots - (lc[epos] - cnt[epos])
        o = jnp.clip(owner[epos], 0)
        emitter_is_sub = sel_s_up[epos]
        j_of_a = upds_by_lo[jnp.clip(a_start[jnp.minimum(o, n - 1)] + r,
                                     0, m - 1)]
        i_of_b = subs_by_lo[jnp.clip(b_start[jnp.minimum(o, m - 1)] + r,
                                     0, n - 1)]
        pi = jnp.where(emitter_is_sub, o, i_of_b)
        pj = jnp.where(emitter_is_sub, j_of_a, o)
        send = jnp.where(lvalid[:, None], jnp.stack([pi, pj], axis=-1), -1)
        recv = lax.all_to_all(send.reshape(num_shards, per_shard, 2),
                              axis_name, 0, 0)
        # the shards' global ranges are disjoint: at most one source holds
        # a pair for each slot, the rest send −1
        return jnp.max(recv, axis=0), k_total

    fn = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis_name),) * 5,
        out_specs=(P(axis_name), P())))
    return fn(sub_lo, upd_lo, owner, is_upper, is_sub)


# ---------------------------------------------------------------------------
# Blocked all-pairs enumeration — the cross-check oracle
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("max_pairs", "block"))
def enumerate_matches(subs: Extents, upds: Extents, *, max_pairs: int,
                      block: int = 256) -> Tuple[jax.Array, jax.Array]:
    """All matching (i, j) pairs, padded to ``max_pairs`` with (-1, -1).

    Blocked all-pairs test + stream compaction: within each subscription
    block the match mask is compacted with a prefix sum; a scan carries the
    global write pointer across blocks (deterministic order: by (i, j)).
    O(n·m) — the oracle the sweep engines are tested against.
    Returns (pairs (max_pairs, 2) int32, count).  Pairs beyond ``max_pairs``
    are dropped but still counted — callers check ``count <= max_pairs``.
    """
    n = subs.lo.shape[0]
    pad = (-n) % block
    s_lo = jnp.pad(subs.lo, (0, pad), constant_values=jnp.inf).reshape(-1, block)
    s_hi = jnp.pad(subs.hi, (0, pad), constant_values=-jnp.inf).reshape(-1, block)
    n_blocks = s_lo.shape[0]
    base_i = jnp.arange(n_blocks, dtype=jnp.int32) * block

    out = jnp.full((max_pairs, 2), -1, jnp.int32)

    def body(carry, blk):
        write_ptr, out = carry
        b_lo, b_hi, b_base = blk
        mask = intersect_1d(b_lo[:, None], b_hi[:, None],
                            upds.lo[None, :], upds.hi[None, :])
        flat = mask.reshape(-1)
        local_pos = jnp.cumsum(flat.astype(jnp.int32), dtype=jnp.int32) - 1
        dest = jnp.where(flat, write_ptr + local_pos, max_pairs)  # drop slot
        ii = (b_base + jnp.arange(block, dtype=jnp.int32))[:, None]
        jj = jnp.arange(upds.lo.shape[0], dtype=jnp.int32)[None, :]
        pairs = jnp.stack(jnp.broadcast_arrays(ii, jj), axis=-1).reshape(-1, 2)
        out = out.at[jnp.minimum(dest, max_pairs), :].set(
            jnp.where(flat[:, None], pairs, -1), mode="drop")
        return (write_ptr + jnp.sum(flat, dtype=jnp.int32), out), None

    (count, out), _ = lax.scan(body, (jnp.int32(0), out), (s_lo, s_hi, base_i))
    return out, count


def enumerate_matches_sweep_numpy(subs: Extents, upds: Extents) -> np.ndarray:
    """Host-side O(N log N + K) enumeration via the sequential sweep.

    The serial Algorithm-4 baseline for the device engines; matches
    :func:`enumerate_matches` as a set.
    """
    from repro.core.sweep import sequential_sbm_pairs_numpy
    pairs = sorted(sequential_sbm_pairs_numpy(subs, upds))
    if not pairs:
        return np.zeros((0, 2), np.int32)
    return np.asarray(pairs, np.int32)


# The d-dimensional composition (selective-dimension sweep + bit-matrix
# AND) lives in repro.core.ddim; it layers on the 1-d engines above.
