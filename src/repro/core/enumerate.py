"""Pair enumeration — the O(K) emission phase with TPU-legal shapes.

TPUs cannot append to a dynamically sized list (the paper's ``L ← L ∪ {..}``
under an atomic).  The standard adaptation is count → prefix offsets →
scatter: a first pass sizes the output, a second writes each pair to its
precomputed slot.  Output buffers are padded to a static ``max_pairs``.

Two engines behind the same (pairs, count) contract:

* the sort-based sweep, output-sensitive O((n+m)·log(n+m) + max_pairs),
  one structure at any chip count.  The probe sorts the endpoint stream
  over a one-axis mesh and counts
  (:func:`repro.core.sweep._sort_count_sharded`); the emission reads that
  stream (:func:`_emit_sharded`).  Every stream record is an emitter: its
  count and counterpart range come from two lower-endpoint cumsums and one
  join table, the exclusive scan of the counts is the offset table, and
  each output slot finds its emitter by a scatter and prefix scan or, where
  the buffer is small, by binary search (:func:`_slot_map`; DESIGN.md §3,
  §14).  :func:`sbm_enumerate` and :func:`sbm_enumerate_planned` without a
  mesh run it on a one-device mesh;
  :func:`repro.kernels.sbm_enumerate_kernel` is the Pallas on-chip form.
* :func:`enumerate_matches` — blocked all-pairs O(n·m) + stream compaction.
  Kept as the cross-check oracle and for tiny inputs where the sort
  dominates.

Overflow contract (all engines): pairs beyond ``max_pairs`` are dropped but
still counted — callers check ``count <= max_pairs`` and retry bigger.  The
sweep's count is exact under x64; without it a K ≥ 2³¹ pins the count at
the 2³¹−1 sentinel, and the buffer still holds min(K, max_pairs) genuine
pairs.
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
from repro.core.sweep import (_collective_bytes, _decode_tags, _exact_count,
                              _lane_partial_sums, _sort_count_exchange_bytes,
                              _sort_count_sharded, combine_lane_partials)

_AXIS = "p"                 # the axis of the one-device mesh


def _count_dtype():
    """Pair counts accumulate in int64 under x64 (K can exceed 2^31 even
    when every per-emitter count fits int32); int32 otherwise — the same
    convention as :func:`repro.core.sweep.sbm_count`."""
    return jnp.int64 if jax.config.read("jax_enable_x64") else jnp.int32


def _empty_result(max_pairs: int):
    return (jnp.full((max_pairs, 2), -1, jnp.int32),
            jnp.zeros((), _count_dtype()))


@functools.cache
def _one_device_mesh():
    """The one-axis mesh over the first device: where a call given no mesh
    runs the sweep's programs.  Its axis is ``Auto``, so the arrays it
    returns carry no sharding in their types and go on into any program."""
    return jax.make_mesh((1,), (_AXIS,), (jax.sharding.AxisType.Auto,),
                         devices=jax.devices()[:1])


# ---------------------------------------------------------------------------
# Sweep-based enumeration (the paper's emission phase, output-sensitive)
# ---------------------------------------------------------------------------

def sbm_enumerate(subs: Extents, upds: Extents, *, max_pairs: int
                  ) -> Tuple[jax.Array, jax.Array]:
    """All matching (i, j) pairs via the sort-based sweep (1-d extents).

    O((n+m)·log(n+m) + max_pairs), one gather per output slot: no n×m
    intermediate is ever formed.  This is :func:`sbm_enumerate_sharded` on
    a one-device mesh.  Returns (pairs (max_pairs, 2) int32 padded with
    (-1, -1), count) under the module's overflow contract.  The rows are in
    stream order, each emitter's pairs at its upper endpoint's place; read
    them as a set.  Requires well-formed extents (lo <= hi) whose bounds
    :func:`repro.core.sweep._sort_key` takes: floats of at most 32 bits or
    integers that int32 holds.
    """
    return sbm_enumerate_sharded(subs, upds, _one_device_mesh(), _AXIS,
                                 max_pairs=max_pairs)


def sbm_enumerate_planned(subs: Extents, upds: Extents, *,
                          policy: runtime_lib.CapacityPolicy =
                          runtime_lib.DEFAULT_POLICY,
                          recorder: runtime_lib.StatsRecorder | None = None,
                          mesh=None):
    """Plan-aware sweep enumeration: probe → plan → emit, instrumented.

    The probe sorts the endpoint stream across the mesh's one axis and
    counts (:func:`repro.core.sweep._sort_count_sharded`); its exact K
    sizes ``max_pairs`` to its ladder bucket, and the emission reads the
    probe's sorted stream under the runtime's retry loop (structurally
    zero retries: the probe count is exact).  ``stats.regime`` names the
    slot map the emission took, ``"expand"`` or ``"search"``
    (:func:`_slot_map`).  Returns ``(pairs, count, stats)`` — the
    production face of :func:`sbm_enumerate` (DESIGN.md §10).

    ``mesh`` is a one-axis mesh over which the sets (best sharded over its
    axis) are matched; without one, the first device's one-device mesh.
    The pair buffer comes back sharded over the axis, its rows rounded up
    to a multiple of the chip count (DESIGN.md §14).  ``stats.chips`` and
    ``stats.exchange_bytes`` record the mesh's share.
    """
    stats = runtime_lib.MatchStats(engine="sweep")
    if subs.size == 0 or upds.size == 0:
        stats.add_phase("probe", 0.0)
        if recorder is not None:
            recorder.record(stats)
        return jnp.full((0, 2), -1, jnp.int32), jnp.int32(0), stats

    mesh = _one_device_mesh() if mesh is None else mesh
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
    """Sweep enumeration over one mesh axis: the sort across the mesh
    (:func:`repro.core.sweep._sort_count_sharded`), then the emission from
    that stream, each chip writing only the slots it owns (DESIGN.md §14)
    — the two programs of a planned sweep.

    Returns ``(pairs, count)``: the buffer has ``max_pairs`` rounded up to
    a positive multiple of the shard count rows, sharded over
    ``axis_name``; rows past ``min(count, max_pairs)`` are −1.  The
    module's overflow contract holds: without x64, a K ≥ 2³¹ pins the
    count at 2³¹−1 and the first ``max_pairs`` rows are genuine pairs.
    """
    n, m = subs.lo.shape[0], upds.lo.shape[0]
    if n == 0 or m == 0:
        return _empty_result(max_pairs)
    tags, _ = _sort_count_sharded(subs, upds, mesh=mesh, axis_name=axis_name)
    return _emit_sharded(tags, n=n, m=m, max_pairs=max_pairs, mesh=mesh,
                         axis_name=axis_name)


def _slot_map(max_pairs: int, n_emitters: int) -> str:
    """How the emission maps its output slots to emitters, from the shapes.

    ``"search"`` binary-searches the offset table for every slot: about
    ⌈log2(n_emitters)⌉ dependent probes a slot, then a gather per table.
    ``"expand"`` scatters two marks per emitter and scans the slots: about
    2·n_emitters scattered updates, one pass over the slots, one gather.
    The expansion is taken where the search's probes outnumber its updates.
    The emitters are a shard's stream records.
    """
    probes = max_pairs * math.ceil(math.log2(n_emitters))
    return "expand" if probes > 2 * n_emitters else "search"


@functools.partial(jax.jit, static_argnames=("n", "m", "max_pairs", "mesh",
                                             "axis_name"))
def _emit_sharded(tags: jax.Array, *, n: int, m: int, max_pairs: int, mesh,
                  axis_name: str):
    """The emission from a stream sorted across the mesh: the pair buffer
    (sharded over the axis, ``per_shard`` rows a shard) and the count.
    ``max_pairs`` must be below 2³⁰, so that offsets pinned at it add
    within int32 (:func:`_pinned_offsets`)."""
    from jax.sharding import PartitionSpec as P

    if max_pairs >= 1 << 30:
        raise ValidationError(f"the sweep emits into fewer than 2^30 rows, "
                              f"not max_pairs={max_pairs}")
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
    range of the sorted stream.  The one code that writes the sweep's
    pair rows.

    Every stream position is an emitter: an upper endpoint emits its
    extent's class count, any other record nothing, and emitters in stream
    order own consecutive global slots from their shard's exclusive
    offset.  An emitter reads its count and its counterparts' first rank
    from its own cumsums and its opening rank, one gather from the psum'd
    join table (:func:`_join_tables`).  Shard q owns output slots
    ``[q·per_shard, (q+1)·per_shard)``.  Offsets are pinned at
    ``max_pairs`` (:func:`_pinned_offsets`), exact wherever a slot reads
    them; the count comes from exact lane sums.
    """
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
        excl, local_total = _pinned_offsets(cnt, max_pairs)
        lanes = _lane_partial_sums(cnt)
    with jax.named_scope("ddm.exchange"):
        totals = lax.all_gather(local_total, axis_name)
        k_total = combine_lane_partials(
            *(lax.psum(v, axis_name) for v in lanes))
    with jax.named_scope("ddm.ranks"):
        base = _pinned_offsets(totals, max_pairs)[0][
            lax.axis_index(axis_name)]

    if form == "expand":
        with jax.named_scope("ddm.search"):
            at = base + excl
            rebased = start - at
        code_s, base_s = _expand_slots_sharded(at, (code, rebased),
                                               slots_all, axis_name)
        with jax.named_scope("ddm.gather"):
            mine = lax.axis_index(axis_name) * per_shard + jnp.arange(
                per_shard, dtype=jnp.int32)
            c = table[jnp.clip(base_s + mine, 0, n + m - 1)]
            is_a = code_s < n
            pairs = jnp.stack([jnp.where(is_a, code_s, c),
                               jnp.where(is_a, c, code_s - n)], axis=-1)
            valid = mine < jnp.minimum(k_total, max_pairs)
            return jnp.where(valid[:, None], pairs, -1), k_total

    # search: the slots are few; every shard finds the emitter of each of
    # its own pairs among all slots, and one all_to_all routes the rows
    with jax.named_scope("ddm.search"):
        g = jnp.arange(slots_all, dtype=jnp.int32)
        rank = g - base
        lvalid = (rank >= 0) & (rank < local_total) & (g < max_pairs)
        e, r = _search_slots(jnp.maximum(rank, 0), excl)
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


def _pinned_offsets(cnt: jax.Array, cap: int):
    """Exclusive offsets of nonnegative int32 counts, and their total, each
    pinned at ``cap`` < 2³⁰: exactly min(offset, cap), in int32.

    With every count clamped to ``cap`` (which moves no offset below it),
    the plain int32 scan is exact up to its first offset at or past
    ``cap``: every offset before that one is below ``cap``, and it adds one
    count of at most ``cap``.  The scan may wrap after it, where the
    offsets are pinned.
    """
    cnt = jnp.minimum(cnt, cap)
    excl = jnp.cumsum(cnt, dtype=jnp.int32) - cnt
    over = excl >= cap
    first = jnp.argmax(over)
    pinned = over[first] & (jnp.arange(cnt.shape[0]) >= first)
    excl = jnp.where(pinned, jnp.int32(cap), excl)
    return excl, jnp.minimum(excl[-1] + cnt[-1], cap)


def _search_slots(slots: jax.Array, excl: jax.Array):
    """Emitter of every slot and the slot's rank within it, by binary
    search of the exclusive offsets ``excl``: the last emitter starting at
    or before the slot (emitters with nothing to emit share the next one's
    start)."""
    with jax.named_scope("ddm.search"):
        e = jnp.searchsorted(excl, slots, side="right").astype(jnp.int32) - 1
    with jax.named_scope("ddm.gather"):
        return e, slots - excl[e]


def _join_tables(is_sub, is_upper, owner, c_sub_lo, c_upd_lo, n: int,
                 m: int):
    """This shard's entries of the emission's two (n + m,) tables.

    ``open_at``: at sub i, the update-lower cumsum at its lower record; at
    n + j, the sub-lower cumsum at update j's.  ``table``: rank → id,
    ``[upds_by_lo, subs_by_lo]``.  Only lower records write, each entry
    once across the stream, so the shards' tables psum into the whole
    ones.
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


def _expand_slots_sharded(excl, values, slots_all: int, axis_name: str):
    """Each of this shard's slots' emitter ``values``, by run-length
    expansion: no per-slot search and no per-emitter gather.

    Every stream position marks its first global slot ``excl`` with the
    step of each value from the position before it in the global order
    (for the shard's first, the previous shard's last); a reduce-scatter
    sums the shards' marks into each owner's slot range, and an inclusive
    scan across shards gives every slot the telescoped value of the last
    position starting at or before it — its emitter, since positions that
    emit nothing share the next one's start.  Positions starting at or past
    ``slots_all`` own no slot and are dropped.  int32 sums wrap harmlessly.
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
    the two join-table psums, the shards' totals and the count's four
    lane partials, then the slot marks' reduce-scatter (``expand``) or the
    rows' all_to_all (``search``)."""
    if shards == 1:
        return 0
    lane = 8 if _count_dtype() == jnp.int64 else 4
    slots = shards * -(-max(max_pairs, 1) // shards)
    total = (3 * _collective_bytes("all_gather", 4, shards)
             + 2 * _collective_bytes("all_reduce", 4 * (n + m), shards)
             + 4 * _collective_bytes("all_reduce", lane, shards))
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
