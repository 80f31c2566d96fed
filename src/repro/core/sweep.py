"""Parallel Sort-Based Matching (the paper's Algorithms 4/5/6) in JAX.

Pipeline (paper §4):

1.  **Endpoint encoding + sort** — every extent contributes two endpoint
    records ``(value, is_upper, is_sub, owner)``.  Ties sort lowers before
    uppers so that *closed*-interval semantics hold (an interval starting
    exactly where another ends still matches).
2.  **Segmented local scans** — the sorted stream is split into P segments;
    each segment computes local prefix information independently.
3.  **Master prefix combine** — the paper's two-level scan (Fig. 5) stitches
    the segments together.
4.  **Emission** — at every *upper* endpoint the number of active
    counterpart extents is emitted.

For counting semantics (what the paper's own evaluation measures), the
delta-set monoid of Algorithm 6 degenerates to ±1 integer deltas and the
whole sweep collapses to four segmented prefix sums — branch-free and
VPU/MXU friendly.  The faithful *set*-form (Algorithm 6 verbatim, with
Sadd/Sdel materialized) is also provided and tested; it is the basis of the
Pallas bitmask kernel.

Exactness: both forms return exactly the brute-force count for arbitrary
inputs (ties, duplicates, zero-length intervals included) — see
``tests/test_core_sweep.py`` (hypothesis sweeps).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import prefix as prefix_lib
from repro.core.intervals import Extents
from repro.core.errors import ValidationError
from repro.core.runtime import inert_bounds


class EndpointStream(NamedTuple):
    """Sorted endpoint records (all shape (2N,))."""

    values: jax.Array      # endpoint coordinate (sorted, ties: lowers first)
    is_upper: jax.Array    # bool
    is_sub: jax.Array      # bool — subscription vs update endpoint
    owner: jax.Array       # int32 — index into the owning extent set


def encode_endpoints(subs: Extents, upds: Extents) -> EndpointStream:
    """Build + sort the endpoint stream (paper Alg. 4 lines 1-4)."""
    n = subs.lo.shape[0]
    m = upds.lo.shape[0]
    values = jnp.concatenate([subs.lo, subs.hi, upds.lo, upds.hi])
    is_upper = jnp.concatenate([
        jnp.zeros((n,), jnp.bool_), jnp.ones((n,), jnp.bool_),
        jnp.zeros((m,), jnp.bool_), jnp.ones((m,), jnp.bool_)])
    is_sub = jnp.concatenate([
        jnp.ones((2 * n,), jnp.bool_), jnp.zeros((2 * m,), jnp.bool_)])
    owner = jnp.concatenate([
        jnp.arange(n, dtype=jnp.int32), jnp.arange(n, dtype=jnp.int32),
        jnp.arange(m, dtype=jnp.int32), jnp.arange(m, dtype=jnp.int32)])
    # lexsort: last key is primary → sort by value, lowers before uppers.
    order = jnp.lexsort((is_upper, values))
    return EndpointStream(values[order], is_upper[order], is_sub[order], owner[order])


def _indicator_deltas(ep: EndpointStream):
    """The four ±1 indicator streams of the counting sweep."""
    sub_lo = (ep.is_sub & ~ep.is_upper).astype(jnp.int32)
    sub_up = (ep.is_sub & ep.is_upper).astype(jnp.int32)
    upd_lo = (~ep.is_sub & ~ep.is_upper).astype(jnp.int32)
    upd_up = (~ep.is_sub & ep.is_upper).astype(jnp.int32)
    return sub_lo, sub_up, upd_lo, upd_up


def _emission_counts(sub_lo, sub_up, upd_lo, upd_up, cumsum_fn):
    """Per-endpoint emission counts given an inclusive-cumsum primitive.

    At a subscription-upper endpoint k, the sequential sweep emits
    ``|UpdSet|`` pairs where UpdSet = updates opened at positions ≤ k and not
    closed at positions < k; symmetrically for update-uppers.  Each
    overlapping pair is emitted exactly once (at the earlier of its two upper
    endpoints) — see tests for the tie-case audit.
    """
    c_sub_lo = cumsum_fn(sub_lo)
    c_sub_up = cumsum_fn(sub_up)
    c_upd_lo = cumsum_fn(upd_lo)
    c_upd_up = cumsum_fn(upd_up)
    active_sub_before = c_sub_lo - (c_sub_up - sub_up)   # excl. self-closing
    active_upd_before = c_upd_lo - (c_upd_up - upd_up)
    emit = sub_up * active_upd_before + upd_up * active_sub_before
    return emit


def _pad_stream(ep: EndpointStream, multiple: int) -> EndpointStream:
    """Pad to a segment multiple with inert sentinel endpoints (lowers at
    the top of the dtype)."""
    total = ep.values.shape[0]
    pad = (-total) % multiple
    if pad == 0:
        return ep
    # A padded record is an update-*lower* endpoint at the top of the
    # dtype, after the sort: it increments active_upd after every real
    # endpoint but is never emitted against (emission only happens at upper
    # endpoints, all of which precede it).
    top, _ = inert_bounds(ep.values.dtype)
    return EndpointStream(
        jnp.concatenate([ep.values, jnp.full((pad,), top, ep.values.dtype)]),
        jnp.concatenate([ep.is_upper, jnp.zeros((pad,), jnp.bool_)]),
        jnp.concatenate([ep.is_sub, jnp.zeros((pad,), jnp.bool_)]),
        jnp.concatenate([ep.owner, jnp.full((pad,), -1, jnp.int32)]),
    )


def resolve_cumsum(scan_impl: str, num_segments: int):
    """Inclusive-cumsum primitive for a named scan backend.

    ``scan_impl``: 'two_level' (paper Fig. 5), 'blelloch' (tree scan), or
    'xla' (monolithic ``jnp.cumsum`` — the serial-scan reference).
    """
    if scan_impl == "two_level":
        return functools.partial(prefix_lib.cumsum_two_level,
                                 num_segments=num_segments)
    if scan_impl == "blelloch":
        return prefix_lib.cumsum_blelloch
    if scan_impl == "xla":
        return functools.partial(jnp.cumsum, axis=-1)
    raise ValidationError(f"unknown scan_impl {scan_impl!r}")


_INT32_MAX = (1 << 31) - 1
_LANE_CHUNK = 1 << 14


def _lane_partial_sums(x: jax.Array):
    """Exact sum of a nonnegative int32 vector as four int32 partials.

    ``jnp.sum`` of int32 accumulates in int32 and silently wraps once the
    total reaches 2³¹ — for the sweep that happens at K ≥ 2³¹ pairs, which a
    few duplicated extents already produce.  Each element is split into
    16-bit hi/lo lanes and every lane is summed in chunks of ``_LANE_CHUNK``
    elements, so every intermediate provably fits int32 (chunk sums
    < 2¹⁴·2¹⁶ = 2³⁰; the second-level lane sums < 2³⁰ for any input below
    2²⁸ elements — far beyond what fits in memory).  Returns
    ``(a, b, c, d)`` with ``sum(x) == (a << 32) + ((b + c) << 16) + d``.
    """

    def lane_sum(lane):
        pad = (-lane.shape[0]) % _LANE_CHUNK
        lane = jnp.concatenate([lane, jnp.zeros((pad,), jnp.int32)])
        chunk = jnp.sum(lane.reshape(-1, _LANE_CHUNK), axis=1)   # < 2^30 each
        return jnp.sum(chunk >> 16), jnp.sum(chunk & 0xFFFF)

    a, b = lane_sum(x >> 16)       # sum(x >> 16)  == (a << 16) + b
    c, d = lane_sum(x & 0xFFFF)    # sum(x & 0xFFFF) == (c << 16) + d
    return a, b, c, d


def _saturate_from_lanes(a, b, c, d):
    """min(total, 2³¹−1) as int32 from :func:`_lane_partial_sums` partials."""
    t = b + c                       # each < 2^30 → fits int32
    low = (t << 16) + d             # wraps negative iff it exceeds int32
    sat = (a > 0) | (t >= 1 << 15) | (low < 0)
    return jnp.where(sat, jnp.int32(_INT32_MAX), low)


def combine_lane_partials(a, b, c, d):
    """Total from :func:`_lane_partial_sums` partials — THE one
    implementation of the repo-wide overflow contract (exact int64 under
    x64, saturating at the 2³¹−1 sentinel without).  Every engine that
    reduces lane partials (counting sweep, sharded sweep, bit-matrix
    popcounts) must route through here so the contract can never diverge.
    """
    if jax.config.read("jax_enable_x64"):
        a, b, c, d = (v.astype(jnp.int64) for v in (a, b, c, d))
        return (a << 32) + ((b + c) << 16) + d
    return _saturate_from_lanes(a, b, c, d)


@functools.partial(jax.jit, static_argnames=("num_segments", "scan_impl"))
def _sbm_count_partials(subs: Extents, upds: Extents, *, num_segments: int,
                        scan_impl: str):
    # named device stages (metadata only): the profiler reads them back
    # from each instruction's op_name
    with jax.named_scope("ddm.sort"):
        ep = _pad_stream(encode_endpoints(subs, upds), num_segments)
    with jax.named_scope("ddm.count"):
        sub_lo, sub_up, upd_lo, upd_up = _indicator_deltas(ep)
        cumsum_fn = resolve_cumsum(scan_impl, num_segments)
        emit = _emission_counts(sub_lo, sub_up, upd_lo, upd_up, cumsum_fn)
        return _lane_partial_sums(emit)


@functools.partial(jax.jit, static_argnames=("num_segments", "scan_impl"))
def sbm_count(subs: Extents, upds: Extents, *, num_segments: int = 8,
              scan_impl: str = "two_level") -> jax.Array:
    """Parallel SBM (counting form).  Returns K = |{(i,j): S_i ∩ U_j ≠ ∅}|.

    ``scan_impl``: 'two_level' (paper Fig. 5), 'blelloch' (tree scan), or
    'xla' (monolithic ``jnp.cumsum`` — the serial-scan reference).

    Overflow contract: the accumulation is exact internally (16-bit lane
    split, see :func:`_lane_partial_sums`).  With x64 enabled the result is
    an exact int64; without x64 the int32 result **saturates** at 2³¹−1
    instead of silently wrapping — callers seeing 2³¹−1 should use
    :func:`sbm_count_exact` for the true K.
    """
    a, b, c, d = _sbm_count_partials(subs, upds, num_segments=num_segments,
                                     scan_impl=scan_impl)
    return combine_lane_partials(a, b, c, d)


def probe_count(subs: Extents, upds: Extents, stats, *,
                num_segments: int = 8, scan_impl: str = "two_level") -> int:
    """Plan-aware counting sweep: the exact K for the runtime planner.

    The cheap selectivity probe of DESIGN.md §10 — one fused sort+count
    pass whose exact K seeds :func:`repro.core.runtime.initial_capacity`
    (so the follow-on enumeration needs zero retries).  The caller times
    it in ``stats.phase("probe")``; the four blocking partial reads run in
    the ``probe.readback`` span and count in ``stats.readbacks``.
    """
    if subs.lo.shape[-1] == 0 or upds.lo.shape[-1] == 0:
        return 0
    partials = _sbm_count_partials(subs, upds, num_segments=num_segments,
                                   scan_impl=scan_impl)
    with stats.readback("probe", len(partials)):
        return _exact_count(*partials)


def _exact_count(a, b, c, d) -> int:
    """K from the four lane partials, combined in Python integers."""
    return (int(a) << 32) + ((int(b) + int(c)) << 16) + int(d)


def sbm_count_exact(subs: Extents, upds: Extents, *, num_segments: int = 8,
                    scan_impl: str = "two_level") -> int:
    """K as an exact Python int, valid beyond 2³¹ even without x64.

    Runs the same jitted lane-partial kernel as :func:`sbm_count` and
    combines the four int32 partials host-side with arbitrary-precision
    arithmetic.
    """
    if subs.lo.shape[-1] == 0 or upds.lo.shape[-1] == 0:
        return 0
    return _exact_count(*_sbm_count_partials(
        subs, upds, num_segments=num_segments, scan_impl=scan_impl))


@functools.partial(jax.jit, static_argnames=("num_segments",))
def sbm_active_profile(subs: Extents, upds: Extents, *, num_segments: int = 8):
    """Per-endpoint (active_sub, active_upd) counts *after* each endpoint.

    The paper's Fig. 4 quantity (|SubSet| as the sweep advances).  Useful for
    load-balance analysis and tested against a sequential reference.
    """
    ep = _pad_stream(encode_endpoints(subs, upds), num_segments)
    sub_lo, sub_up, upd_lo, upd_up = _indicator_deltas(ep)
    cumsum_fn = functools.partial(prefix_lib.cumsum_two_level,
                                  num_segments=num_segments)
    active_sub = cumsum_fn(sub_lo) - cumsum_fn(sub_up)
    active_upd = cumsum_fn(upd_lo) - cumsum_fn(upd_up)
    return ep, active_sub, active_upd


# --------------------------------------------------------------------------
# Faithful set-form (Algorithm 5 + 6): delta sets + monoid prefix
# --------------------------------------------------------------------------

def segment_delta_sets(ep: EndpointStream, num_segments: int, n: int, m: int):
    """Algorithm 6 lines 1-17, vectorized.

    Returns (Sadd, Sdel, Uadd, Udel), each (P, n|m) boolean.  Invariants
    (paper §4): Sadd[p] = subs whose *lower* is in T_p and upper is not;
    Sdel[p] = subs whose *upper* is in T_p and lower is not.
    """
    total = ep.values.shape[0]
    if total % num_segments:
        raise ValidationError("stream must be padded to a segment multiple")
    seg = total // num_segments
    seg_of = jnp.arange(total, dtype=jnp.int32) // seg
    segs = jnp.arange(num_segments, dtype=jnp.int32)

    def per_type(is_sub_type: bool, count: int):
        sel_lo = (ep.is_sub == is_sub_type) & ~ep.is_upper & (ep.owner >= 0)
        sel_up = (ep.is_sub == is_sub_type) & ep.is_upper & (ep.owner >= 0)
        # segment holding each extent's lower/upper endpoint
        lo_seg = jnp.full((count,), -1, jnp.int32).at[
            jnp.where(sel_lo, ep.owner, count)].set(
            jnp.where(sel_lo, seg_of, -1), mode="drop")
        up_seg = jnp.full((count,), -1, jnp.int32).at[
            jnp.where(sel_up, ep.owner, count)].set(
            jnp.where(sel_up, seg_of, -1), mode="drop")
        add = (lo_seg[None, :] == segs[:, None]) & (up_seg[None, :] != segs[:, None])
        rem = (up_seg[None, :] == segs[:, None]) & (lo_seg[None, :] != segs[:, None])
        return add, rem

    sadd, sdel = per_type(True, n)
    uadd, udel = per_type(False, m)
    return sadd, sdel, uadd, udel


def active_sets_at_segment_starts(subs: Extents, upds: Extents,
                                  num_segments: int):
    """SubSet[p]/UpdSet[p] of Algorithm 6 lines 18-21 (boolean masks)."""
    n, m = subs.lo.shape[0], upds.lo.shape[0]
    ep = _pad_stream(encode_endpoints(subs, upds), num_segments)
    sadd, sdel, uadd, udel = segment_delta_sets(ep, num_segments, n, m)
    sub_active = prefix_lib.delta_scan_exclusive(sadd, sdel)
    upd_active = prefix_lib.delta_scan_exclusive(uadd, udel)
    return ep, sub_active, upd_active


# --------------------------------------------------------------------------
# Distributed sweep: the paper's algorithm across a device mesh axis
# --------------------------------------------------------------------------
#
# Under a mesh no chip holds the whole endpoint stream (DESIGN.md §14).
# Each chip encodes the endpoints of its slice of the bounds as two int32
# words, a key and a tag, and sorts them; the chips agree on exact
# splitters; one all_to_all hands each chip its contiguous share of the
# global order, which it sorts again.  The tag orders ties as
# encode_endpoints does, so the sharded stream is the one-chip stream.

_UPPER = 1 << 30            # tag bit: an upper endpoint
_UPD = 1 << 29              # tag bit: an update endpoint
_OWNER = _UPD - 1           # tag bits: the owning extent's index


def _sort_key(x: jax.Array) -> jax.Array:
    """Order-preserving int32 image of the bounds; a float -0.0 sorts as
    +0.0, as in :func:`encode_endpoints`."""
    dt = x.dtype
    if jnp.issubdtype(dt, jnp.floating) and dt.itemsize <= 4:
        x = x.astype(jnp.float32)
        bits = lax.bitcast_convert_type(
            jnp.where(x == 0, jnp.float32(0), x), jnp.int32)
        return jnp.where(bits < 0, bits ^ 0x7FFFFFFF, bits)
    if jnp.issubdtype(dt, jnp.integer) and jnp.can_cast(dt, jnp.int32):
        return x.astype(jnp.int32)
    raise ValidationError(
        f"a mesh sorts float bounds of at most 32 bits or integer bounds "
        f"that int32 holds, not {dt}")


def _decode_tags(tags: jax.Array, n: int, m: int):
    """``(is_sub, is_upper, owner)`` of stream tags; ``owner`` is -1 for
    the records of padding extents (index ≥ n or m)."""
    is_upper = (tags & _UPPER) != 0
    is_sub = (tags & _UPD) == 0
    owner = tags & _OWNER
    return is_sub, is_upper, jnp.where(owner < jnp.where(is_sub, n, m),
                                       owner, -1)


def _tag_indicators(tags, n: int, m: int):
    """The four indicator streams of sorted tags; padding records are
    inert wherever they lie."""
    is_sub, is_upper, owner = _decode_tags(tags, n, m)
    real = owner >= 0
    return tuple((sel & real).astype(jnp.int32) for sel in (
        is_sub & ~is_upper, is_sub & is_upper,
        ~is_sub & ~is_upper, ~is_sub & is_upper))


def _shard_endpoints(s_lo, s_hi, u_lo, u_hi, axis_name: str):
    """This chip's endpoint records, unsorted: ``(keys, tags)``."""
    i = lax.axis_index(axis_name)
    ns, nu = s_lo.shape[0], u_lo.shape[0]
    sid = i * ns + jnp.arange(ns, dtype=jnp.int32)
    uid = _UPD | (i * nu + jnp.arange(nu, dtype=jnp.int32))
    keys = jnp.concatenate([_sort_key(v) for v in (s_lo, s_hi, u_lo, u_hi)])
    return keys, jnp.concatenate([sid, sid | _UPPER, uid, uid | _UPPER])


def _count_below(keys, tags, v, t):
    """Records of a sorted local run whose (key, tag) is below (v, t),
    for each query: a binary search of the run."""
    size = keys.shape[0]

    def step(_, bounds):
        lo, hi = bounds
        mid = (lo + hi) >> 1
        at = jnp.minimum(mid, size - 1)
        less = (keys[at] < v) | ((keys[at] == v) & (tags[at] < t))
        live = lo < hi
        return (jnp.where(live & less, mid + 1, lo),
                jnp.where(live & ~less, mid, hi))

    lo = jnp.zeros(v.shape, jnp.int32)
    return lax.fori_loop(0, math.ceil(math.log2(size + 1)), step,
                         (lo, jnp.full(v.shape, size, jnp.int32)))[0]


def _exact_cuts(keys, tags, axis_name: str, num_shards: int):
    """Where this chip's sorted run splits among the shards.

    Piece q, ``[cuts[q], cuts[q+1])``, goes to shard q, and shard q gets
    exactly the global ranks ``[q·S, (q+1)·S)`` (S the run length).  Keys
    are distinct, as a tag names its record, so the key of global rank r
    is the largest with at most r keys below it across the mesh: it is
    built bit by bit, key word first, then tag word.
    """
    size = keys.shape[0]
    ranks = jnp.arange(1, num_shards, dtype=jnp.int32) * size
    zero = jnp.zeros_like(ranks)

    def below(v, t):
        return lax.psum(_count_below(keys, tags, v, t), axis_name)

    def key_bit(b, u):          # u: the key plus 2^31, as uint32
        cand = u | (jnp.uint32(1 << 31) >> b.astype(jnp.uint32))
        v = lax.bitcast_convert_type(cand ^ jnp.uint32(1 << 31), jnp.int32)
        return jnp.where(below(v, zero) <= ranks, cand, u)

    u = lax.fori_loop(0, 32, key_bit, jnp.zeros(ranks.shape, jnp.uint32))
    v = lax.bitcast_convert_type(u ^ jnp.uint32(1 << 31), jnp.int32)

    def tag_bit(b, t):
        cand = t | (jnp.int32(1 << 30) >> b)
        return jnp.where(below(v, cand) <= ranks, cand, t)

    t = lax.fori_loop(0, 31, tag_bit, zero)
    return jnp.concatenate([jnp.zeros((1,), jnp.int32),
                            _count_below(keys, tags, v, t),
                            jnp.full((1,), size, jnp.int32)])


def _exchange(cuts, arrays, axis_name: str, num_shards: int):
    """Send piece q of each sorted local array to shard q with one
    all_to_all of S-record windows (S the run length, the largest a
    piece can be); each shard lays the pieces it gets end to end."""
    size = arrays[0].shape[0]
    got = lax.all_to_all(cuts[1:] - cuts[:-1], axis_name, 0, 0)
    at = jnp.cumsum(got) - got

    def send(x):
        twice = jnp.concatenate([x, x])
        return jnp.stack([lax.dynamic_slice(twice, (cuts[q],), (size,))
                          for q in range(num_shards)])

    out = []
    for x in arrays:
        recv = lax.all_to_all(send(x), axis_name, 0, 0)
        # piece p fills [at[p], at[p] + got[p]); the rest of its window
        # is overwritten by piece p + 1, the last one's falls past S
        buf = jnp.zeros((2 * size,), x.dtype)
        for p in range(num_shards):
            buf = lax.dynamic_update_slice(buf, recv[p], (at[p],))
        out.append(buf[:size])
    return out


def _sort_shard(s_lo, s_hi, u_lo, u_hi, *, axis_name: str,
                num_shards: int) -> jax.Array:
    """Shard body: the sorted tags of this shard's contiguous range of the
    global endpoint stream, S = 2·(local n + local m) records on every
    shard, from this chip's slices of the bounds."""
    with jax.named_scope("ddm.sort"):
        keys, tags = lax.sort(
            _shard_endpoints(s_lo, s_hi, u_lo, u_hi, axis_name), num_keys=2)
    if num_shards == 1:
        return tags
    with jax.named_scope("ddm.exchange"):
        cuts = _exact_cuts(keys, tags, axis_name, num_shards)
        keys, tags = _exchange(cuts, (keys, tags), axis_name, num_shards)
    with jax.named_scope("ddm.sort"):
        return lax.sort((keys, tags), num_keys=2)[1]


def _shard_lane_partials(sub_lo, sub_up, upd_lo, upd_up, axis_name: str):
    """The counting sweep over contiguous shards of the stream: the K's
    four lane partials (:func:`_lane_partial_sums`), psum'd."""
    def cumsum_fn(x):
        return prefix_lib.shard_inclusive_cumsum(x, axis_name)

    emit = _emission_counts(sub_lo, sub_up, upd_lo, upd_up, cumsum_fn)
    return tuple(lax.psum(v, axis_name) for v in _lane_partial_sums(emit))


def sbm_count_shard_body(sub_lo, sub_up, upd_lo, upd_up, *, axis_name: str):
    """Per-shard body (call inside shard_map over contiguous sorted shards).

    Exactly the paper's three phases with "processor" := device:
    local deltas → all-gather master combine → local emission.  The global
    reduction follows the same overflow contract as :func:`sbm_count`:
    per-shard 16-bit lane partials are psum'd (each aggregate provably
    fits int32 under the same < 2²⁸-element realistic bound as
    :func:`_lane_partial_sums`) and the result is exact int64 under x64,
    saturating at 2³¹−1 without — never a silent wrap.
    """
    return combine_lane_partials(*_shard_lane_partials(
        sub_lo, sub_up, upd_lo, upd_up, axis_name))


def _pad_to(x, multiple: int):
    """``x`` padded to a multiple with the top of its dtype."""
    pad = (-x.shape[0]) % multiple
    if pad == 0:
        return x
    top, _ = inert_bounds(x.dtype)
    return jnp.concatenate([x, jnp.full((pad,), top, x.dtype)])


def _sort_count_body(s_lo, s_hi, u_lo, u_hi, *, n, m, axis_name,
                     num_shards):
    tags = _sort_shard(s_lo, s_hi, u_lo, u_hi, axis_name=axis_name,
                       num_shards=num_shards)
    with jax.named_scope("ddm.count"):
        return tags, _shard_lane_partials(*_tag_indicators(tags, n, m),
                                          axis_name)


@functools.partial(jax.jit, static_argnames=("mesh", "axis_name"))
def _sort_count_sharded(subs: Extents, upds: Extents, *, mesh,
                        axis_name: str):
    """The sorted stream's tags, sharded over ``axis_name``, and K's four
    lane partials: the probe of a planned sweep on a mesh.

    The bounds may come in sharded over the axis; sets whose size is not
    a multiple of the shard count are padded with extents whose records
    are inert.  Both sets must hold fewer than 2²⁹ extents.
    """
    from jax.sharding import PartitionSpec as P

    n, m = subs.lo.shape[0], upds.lo.shape[0]
    if max(n, m) >= _UPD:
        raise ValidationError(f"a mesh sweep takes fewer than 2^29 extents "
                              f"a side, not n={n}, m={m}")
    num_shards = mesh.shape[axis_name]
    fn = jax.shard_map(
        functools.partial(_sort_count_body, n=n, m=m, axis_name=axis_name,
                          num_shards=num_shards),
        mesh=mesh, in_specs=(P(axis_name),) * 4,
        out_specs=(P(axis_name), P()), check_vma=False)
    return fn(*(_pad_to(x, num_shards)
                for x in (subs.lo, subs.hi, upds.lo, upds.hi)))


def sbm_count_sharded(subs: Extents, upds: Extents, mesh, axis_name: str):
    """End-to-end distributed SBM count over one mesh axis: the sort across
    the mesh, then the counting sweep over contiguous shards of the stream,
    the active-set carry crossing chips by the two-level scan.  The same
    overflow contract as :func:`sbm_count`."""
    _, partials = _sort_count_sharded(subs, upds, mesh=mesh,
                                      axis_name=axis_name)
    return combine_lane_partials(*partials)


def _collective_bytes(kind: str, nbytes: int, shards: int) -> int:
    """Bytes a collective moves between chips, summed over the chips, for
    ``nbytes`` of operand on each: a ring all-reduce 2(P−1)·X, an
    all-gather P(P−1)·X, an all-to-all or a reduce-scatter (P−1)·X."""
    factor = {"all_reduce": 2 * (shards - 1),
              "all_gather": shards * (shards - 1),
              "all_to_all": shards - 1,
              "reduce_scatter": shards - 1}[kind]
    return factor * nbytes


def _sort_count_exchange_bytes(n: int, m: int, shards: int) -> int:
    """What :func:`_sort_count_sharded`'s collectives move: the splitter
    search (63 psums of P−1 counts), the all_to_all of the piece sizes
    and of two int32 words per record in S-record windows, the scans'
    carries and the lane psums."""
    if shards == 1:
        return 0
    size = 2 * (-(-n // shards) + -(-m // shards))
    return (63 * _collective_bytes("all_reduce", 4 * (shards - 1), shards)
            + _collective_bytes("all_to_all", 4 * shards, shards)
            + 2 * _collective_bytes("all_to_all", 4 * shards * size, shards)
            + 4 * _collective_bytes("all_gather", 4, shards)
            + 4 * _collective_bytes("all_reduce", 4, shards))


# --------------------------------------------------------------------------
# Sequential references (host) — Algorithm 4 verbatim
# --------------------------------------------------------------------------

def sequential_sbm_count_numpy(subs: Extents, upds: Extents) -> int:
    """Paper Algorithm 4 with counting semantics — the serial baseline."""
    n = int(np.asarray(subs.lo).shape[0])
    m = int(np.asarray(upds.lo).shape[0])
    values = np.concatenate([np.asarray(subs.lo), np.asarray(subs.hi),
                             np.asarray(upds.lo), np.asarray(upds.hi)])
    is_upper = np.concatenate([np.zeros(n, bool), np.ones(n, bool),
                               np.zeros(m, bool), np.ones(m, bool)])
    is_sub = np.concatenate([np.ones(2 * n, bool), np.zeros(2 * m, bool)])
    order = np.lexsort((is_upper, values))
    k = 0
    sub_active = 0
    upd_active = 0
    for idx in order:
        if is_sub[idx]:
            if not is_upper[idx]:
                sub_active += 1
            else:
                sub_active -= 1
                k += upd_active
        else:
            if not is_upper[idx]:
                upd_active += 1
            else:
                upd_active -= 1
                k += sub_active
    return k


def sequential_sbm_pairs_numpy_ddim(subs: Extents, upds: Extents,
                                    sweep_dim: int = 0) -> set:
    """Algorithm 4 extended to d dims: 1-d sweep on ``sweep_dim``, then the
    paper-§3 projection filter on every other dimension — the host-side
    reference the selective-dimension and bit-matrix engines are
    property-tested against (any ``sweep_dim`` yields the same set).
    """
    if subs.ndim_space == 1:
        return sequential_sbm_pairs_numpy(subs, upds)
    cand = sequential_sbm_pairs_numpy(subs.dim(sweep_dim),
                                      upds.dim(sweep_dim))
    s_lo = np.asarray(subs.lo)
    s_hi = np.asarray(subs.hi)
    u_lo = np.asarray(upds.lo)
    u_hi = np.asarray(upds.hi)
    out = set()
    for i, j in cand:
        if all((s_lo[d, i] <= u_hi[d, j]) and (u_lo[d, j] <= s_hi[d, i])
               for d in range(subs.ndim_space) if d != sweep_dim):
            out.add((i, j))
    return out


def sequential_sbm_pairs_numpy(subs: Extents, upds: Extents) -> set:
    """Paper Algorithm 4 verbatim (set semantics, emits pairs)."""
    n = int(np.asarray(subs.lo).shape[0])
    m = int(np.asarray(upds.lo).shape[0])
    values = np.concatenate([np.asarray(subs.lo), np.asarray(subs.hi),
                             np.asarray(upds.lo), np.asarray(upds.hi)])
    is_upper = np.concatenate([np.zeros(n, bool), np.ones(n, bool),
                               np.zeros(m, bool), np.ones(m, bool)])
    is_sub = np.concatenate([np.ones(2 * n, bool), np.zeros(2 * m, bool)])
    owner = np.concatenate([np.arange(n), np.arange(n), np.arange(m), np.arange(m)])
    order = np.lexsort((is_upper, values))
    sub_set: set = set()
    upd_set: set = set()
    out = set()
    for idx in order:
        o = int(owner[idx])
        if is_sub[idx]:
            if not is_upper[idx]:
                sub_set.add(o)
            else:
                sub_set.discard(o)
                out.update((o, j) for j in upd_set)
        else:
            if not is_upper[idx]:
                upd_set.add(o)
            else:
                upd_set.discard(o)
                out.update((i, o) for i in sub_set)
    return out
