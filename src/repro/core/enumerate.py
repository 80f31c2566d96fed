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
from repro.core.errors import ValidationError
from repro.core.sweep import (_lane_partial_sums, _pad_stream,
                              _saturate_from_lanes, _decode_tags,
                              emission_rank_tables, encode_endpoints,
                              resolve_cumsum)


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
                          recorder: runtime_lib.StatsRecorder | None = None,
                          mesh=None):
    """Plan-aware sweep enumeration: probe → plan → emit, instrumented.

    Runs the counting sweep as the planner's selectivity probe, sizes
    ``max_pairs`` to the exact K's ladder bucket, and executes the
    emission under the runtime's retry loop (structurally zero retries:
    the probe count is exact).  ``stats.regime`` names the slot map the
    emission took, ``"expand"`` or ``"search"`` (:func:`_slot_map`).
    Returns ``(pairs, count, stats)`` — the production face of
    :func:`sbm_enumerate` (DESIGN.md §10).

    With a one-axis ``mesh`` the sets (best sharded over its axis) are
    matched across its chips: the probe sorts the endpoint stream across
    the mesh and counts (:func:`repro.core.sweep._sort_count_sharded`), the
    emission reuses that sorted stream, and the pair buffer comes back
    sharded over the axis, its rows rounded up to a multiple of the chip
    count (DESIGN.md §14).  ``stats.chips`` and ``stats.exchange_bytes``
    record the mesh's share.
    """
    from repro.core.sweep import (_exact_count, probe_count,
                                  _sort_count_exchange_bytes,
                                  _sort_count_sharded)

    stats = runtime_lib.MatchStats(engine="sweep")
    if subs.size == 0 or upds.size == 0:
        stats.add_phase("probe", 0.0)
        if recorder is not None:
            recorder.record(stats)
        return jnp.full((0, 2), -1, jnp.int32), jnp.int32(0), stats

    if mesh is None:
        with stats.phase("probe"):
            k = probe_count(subs, upds, stats, num_segments=num_segments,
                            scan_impl=scan_impl)

        def fn(s, u, *, max_pairs):
            stats.regime = _slot_map(max_pairs,
                                     s.lo.shape[0] + u.lo.shape[0])
            return sbm_enumerate(s, u, max_pairs=max_pairs,
                                 num_segments=num_segments,
                                 scan_impl=scan_impl)
    else:
        if len(mesh.axis_names) != 1:
            raise ValidationError(f"a planned sweep spans one mesh axis, "
                                  f"not {mesh.axis_names}")
        axis, = mesh.axis_names
        n, m, chips = subs.size, upds.size, mesh.size
        stats.chips = chips
        with stats.phase("probe"):
            tags, partials = _sort_count_sharded(subs, upds, mesh=mesh,
                                                 axis_name=axis)
            with stats.readback("probe", len(partials)):
                k = _exact_count(*partials)
        stats.exchange_bytes += _sort_count_exchange_bytes(n, m, chips)

        def fn(s, u, *, max_pairs):
            stats.regime = _slot_map(max_pairs, tags.shape[0] // chips)
            stats.exchange_bytes += _emit_exchange_bytes(
                n, m, chips, max_pairs, stats.regime)
            return _emit_sharded(tags, n=n, m=m, max_pairs=max_pairs,
                                 mesh=mesh, axis_name=axis)

    return runtime_lib.execute_enumeration(
        fn, subs, upds, estimate=k, policy=policy, stats=stats,
        recorder=recorder)


def sbm_enumerate_sharded(subs: Extents, upds: Extents, mesh, axis_name: str,
                          *, max_pairs: int) -> Tuple[jax.Array, jax.Array]:
    """Distributed sweep enumeration over one mesh axis: the sort across
    the mesh (:func:`repro.core.sweep._sort_count_sharded`), then the
    emission from that stream, each chip writing only the slots it owns
    (DESIGN.md §14) — the two programs a planned sweep on a mesh runs.

    Returns ``(pairs, count)``: the buffer has ``max_pairs`` rounded up to
    a positive multiple of the shard count rows, sharded over
    ``axis_name``; rows past ``min(count, max_pairs)`` are −1.  Without
    x64, a global K ≥ 2³¹ pins the count at the 2³¹−1 sentinel and
    returns an all-(-1) buffer (the cross-shard offsets would wrap) —
    never silently wrong pairs.
    """
    from repro.core.sweep import _sort_count_sharded

    n, m = subs.lo.shape[0], upds.lo.shape[0]
    if n == 0 or m == 0:
        return _empty_result(max_pairs)
    tags, _ = _sort_count_sharded(subs, upds, mesh=mesh, axis_name=axis_name)
    return _emit_sharded(tags, n=n, m=m, max_pairs=max_pairs, mesh=mesh,
                         axis_name=axis_name)


@functools.partial(jax.jit, static_argnames=("n", "m", "max_pairs", "mesh",
                                             "axis_name"))
def _emit_sharded(tags: jax.Array, *, n: int, m: int, max_pairs: int, mesh,
                  axis_name: str):
    """The emission from a stream sorted across the mesh: the pair buffer
    (sharded over the axis, ``per_shard`` rows a shard) and the count."""
    from jax.sharding import PartitionSpec as P

    shards = mesh.shape[axis_name]
    per_shard = -(-max(max_pairs, 1) // shards)
    fn = jax.shard_map(
        functools.partial(
            _emit_shard_body, n=n, m=m, max_pairs=max_pairs,
            per_shard=per_shard, shards=shards, axis_name=axis_name,
            form=_slot_map(max_pairs, tags.shape[0] // shards)),
        mesh=mesh, in_specs=P(axis_name), out_specs=(P(axis_name), P()),
        check_vma=False)
    return fn(tags)


def _emit_shard_body(tags, *, n, m, max_pairs, per_shard, shards, axis_name,
                     form):
    """Shard body of the emission; ``tags`` is this shard's contiguous
    range of the sorted stream.

    Every stream position is an emitter: an upper endpoint emits its
    extent's class count, any other record nothing, and emitters in stream
    order own consecutive global slots from their shard's exclusive
    offset.  An emitter reads its count and its counterparts' first rank
    from its own cumsums and its opening rank, one gather from the psum'd
    join table (:func:`_join_tables`).  Shard q owns output slots
    ``[q·per_shard, (q+1)·per_shard)``.
    """
    cdtype = _count_dtype()
    slots_all = shards * per_shard
    with jax.named_scope("ddm.ranks"):
        is_sub, is_upper, owner = _decode_tags(tags, n, m)
        real = owner >= 0
        # stream-position cumsums fit int32; pin it under x64 too
        c_sub_lo = prefix_lib.shard_inclusive_cumsum(
            (is_sub & ~is_upper & real).astype(jnp.int32),
            axis_name).astype(jnp.int32)
        c_upd_lo = prefix_lib.shard_inclusive_cumsum(
            (~is_sub & ~is_upper & real).astype(jnp.int32),
            axis_name).astype(jnp.int32)
        local = _join_tables(is_sub, is_upper, owner, c_sub_lo, c_upd_lo,
                             n, m)
    with jax.named_scope("ddm.exchange"):
        # each table is linear in its scattered entries: psum the shards'
        open_at, table = (lax.psum(t, axis_name) for t in local)
    with jax.named_scope("ddm.ranks"):
        sel_s = is_sub & is_upper & real
        sel_u = ~is_sub & is_upper & real
        # the emitter's code (sub i, or n + update j); other records read
        # entry n and emit nothing
        code = jnp.where(sel_s, owner, n + jnp.where(sel_u, owner, 0))
        opened = open_at[code]
        # counterpart lowers seen here less those seen at the opening;
        # they start at that rank in table = [upds_by_lo, subs_by_lo]
        cnt = jnp.where(sel_s, c_upd_lo - opened,
                        jnp.where(sel_u, c_sub_lo - opened, 0))
        start = jnp.where(sel_s, opened, m + opened)
        lc, local_total = _shard_offsets(cnt)
    with jax.named_scope("ddm.exchange"):
        base = prefix_lib.shard_exclusive_offsets(local_total, axis_name)
        if cdtype == jnp.int64:
            k_total = lax.psum(local_total, axis_name)
            overflow = jnp.zeros((), jnp.bool_)
        else:
            # psum of int32 local totals can wrap even when every shard is
            # below the sentinel — combine 15-bit lanes (each psum provably
            # fits int32 for any realistic shard count) and saturate.  When
            # the aggregate overflows, the cross-shard offsets (base) wrap,
            # so the flag blanks the pair buffer: callers get the 2^31-1
            # count sentinel and an all-(-1) buffer, never wrong pairs.  A
            # shard whose own total saturated has wrapped offsets (lc) even
            # where the others emit nothing: it adds 2^16 to the high lane,
            # which raises the flag.
            saturated = local_total == jnp.int32((1 << 31) - 1)
            hi = lax.psum((local_total >> 15)
                          + jnp.where(saturated, jnp.int32(1 << 16), 0),
                          axis_name)
            lo15 = lax.psum(local_total & 0x7FFF, axis_name)
            s = (hi << 15) + lo15
            overflow = (hi >= 1 << 16) | (s < 0)
            k_total = jnp.where(overflow, jnp.int32((1 << 31) - 1), s)

    if form == "expand":
        with jax.named_scope("ddm.search"):
            excl = base + lc - cnt
            rebased = start - excl.astype(jnp.int32)
        code_s, base_s = _expand_slots_sharded(excl, (code, rebased),
                                               slots_all, axis_name)
        with jax.named_scope("ddm.gather"):
            mine = lax.axis_index(axis_name) * per_shard + jnp.arange(
                per_shard, dtype=jnp.int32)
            c = table[jnp.clip(base_s + mine, 0, n + m - 1)]
            is_a = code_s < n
            pairs = jnp.stack([jnp.where(is_a, code_s, c),
                               jnp.where(is_a, c, code_s - n)], axis=-1)
            valid = (mine < jnp.minimum(k_total, max_pairs)) & ~overflow
            return jnp.where(valid[:, None], pairs, -1), k_total

    # search: the slots are few; every shard finds the emitter of each of
    # its own pairs among all slots, and one all_to_all routes the rows
    with jax.named_scope("ddm.search"):
        g = jnp.arange(slots_all, dtype=jnp.int32)
        rank = g - base
        lvalid = ((rank >= 0) & (rank < local_total) & (g < max_pairs)
                  & ~overflow)
        e, r = _search_slots(jnp.clip(rank, 0).astype(jnp.int32), lc, cnt)
    with jax.named_scope("ddm.gather"):
        c = table[jnp.clip(start[e] + r, 0, n + m - 1)]
        is_a = code[e] < n
        send = jnp.where(lvalid[:, None], jnp.stack(
            [jnp.where(is_a, code[e], c),
             jnp.where(is_a, c, code[e] - n)], axis=-1), -1)
    with jax.named_scope("ddm.exchange"):
        recv = lax.all_to_all(send.reshape(shards, per_shard, 2), axis_name,
                              0, 0)
    with jax.named_scope("ddm.gather"):
        # the shards' global ranges are disjoint: at most one source holds
        # a pair for each slot, the rest send −1
        return jnp.max(recv, axis=0), k_total


def _join_tables(is_sub, is_upper, owner, c_sub_lo, c_upd_lo, n: int,
                 m: int):
    """This shard's entries of the mesh emission's two (n + m,) tables.

    ``open_at``: at sub i, the update-lower cumsum at its lower record; at
    n + j, the sub-lower cumsum at update j's.  ``table``: rank → id,
    ``[upds_by_lo, subs_by_lo]``.  Only lower records write, each entry
    once across the stream, so the shards' tables psum into the whole
    ones.  (The one-chip emission's emitters are extent ids and read
    :func:`repro.core.sweep.rank_tables_from_cumsums`' id-indexed tables
    directly.)
    """
    lower = ~is_upper & (owner >= 0)
    s_lo = is_sub & lower
    u_lo = ~is_sub & lower
    size = n + m    # past the end: dropped
    at = jnp.where(s_lo, owner, jnp.where(u_lo, n + owner, size))
    open_at = jnp.zeros((size,), jnp.int32).at[at].set(
        jnp.where(is_sub, c_upd_lo, c_sub_lo), mode="drop")
    rank = jnp.where(u_lo, c_upd_lo - 1, jnp.where(s_lo, m + c_sub_lo - 1,
                                                    size))
    table = jnp.zeros((size,), jnp.int32).at[rank].set(owner, mode="drop")
    return open_at, table


def _shard_offsets(cnt: jax.Array):
    """The inclusive offsets of a shard's emitters and its total, under
    :func:`_offset_cumsum`'s contract: int64 under x64; without it the
    total saturates at 2³¹−1 (exact lane sums, :func:`_lane_partial_sums`)
    and the offsets are a plain int32 scan, which wraps only where the
    total saturates; the caller then flags overflow and blanks the
    buffer.  (The saturating tree
    scan takes the chip's compiler tens of minutes over 10⁷ emitters.)"""
    if _count_dtype() == jnp.int64:
        lc = jnp.cumsum(cnt, dtype=jnp.int64)
        return lc, lc[-1]
    return (jnp.cumsum(cnt, dtype=jnp.int32),
            _saturate_from_lanes(*_lane_partial_sums(cnt)))


def _expand_slots_sharded(excl, values, slots_all: int, axis_name: str):
    """Each of this shard's slots' emitter ``values``, by the run-length
    expansion of :func:`_expand_slots` across the mesh.

    Every stream position marks its first global slot ``excl`` with the
    step of each value from the position before it in the global order
    (for the shard's first, the previous shard's last); a reduce-scatter
    sums the shards' marks into each owner's slot range, and a scan across
    shards gives every slot the telescoped value of the last position
    starting at or before it — its emitter, since positions that emit
    nothing share the next one's start.  int32 sums wrap harmlessly.
    """
    with jax.named_scope("ddm.search"):
        at = jnp.minimum(excl, slots_all).astype(jnp.int32)
    out = []
    for v in values:
        v = v.astype(jnp.int32)
        with jax.named_scope("ddm.exchange"):
            last = lax.all_gather(v[-1], axis_name)
        with jax.named_scope("ddm.search"):
            i = lax.axis_index(axis_name)
            prev = jnp.where(i > 0, last[jnp.maximum(i - 1, 0)], 0)
            step = v - jnp.concatenate([prev[None], v[:-1]])
            marks = jnp.zeros((slots_all,), jnp.int32).at[at].add(
                step, mode="drop")
        with jax.named_scope("ddm.exchange"):
            marks = lax.psum_scatter(marks, axis_name, scatter_dimension=0,
                                     tiled=True)
        with jax.named_scope("ddm.search"):
            out.append(prefix_lib.shard_inclusive_cumsum(
                marks, axis_name).astype(jnp.int32))
    return out


def _emit_exchange_bytes(n: int, m: int, shards: int, max_pairs: int,
                        form: str) -> int:
    """What :func:`_emit_sharded`'s collectives move: the scans' carries,
    the two join-table psums, the offsets and the count, then the slot
    marks' reduce-scatter (``expand``) or the rows' all_to_all
    (``search``)."""
    from repro.core.sweep import _collective_bytes

    if shards == 1:
        return 0
    c = 8 if _count_dtype() == jnp.int64 else 4
    slots = shards * -(-max(max_pairs, 1) // shards)
    total = (2 * _collective_bytes("all_gather", 4, shards)
             + 2 * _collective_bytes("all_reduce", 4 * (n + m), shards)
             + _collective_bytes("all_gather", c, shards)
             + (1 if c == 8 else 2) * _collective_bytes("all_reduce", c,
                                                      shards))
    if form == "expand":
        return total + 2 * (_collective_bytes("all_gather", 4, shards)
                            + _collective_bytes("reduce_scatter", 4 * slots,
                                               shards)
                            + _collective_bytes("all_gather", 4, shards))
    return total + _collective_bytes("all_to_all", 8 * slots, shards)


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
    top, bottom = runtime_lib.inert_bounds(subs.lo.dtype)
    s_lo = jnp.pad(subs.lo, (0, pad), constant_values=top).reshape(-1, block)
    s_hi = jnp.pad(subs.hi, (0, pad), constant_values=bottom).reshape(-1, block)
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
