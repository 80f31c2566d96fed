"""True d-dimensional matching: selective-dimension sweep + bit-matrix AND.

The paper states the DDM problem for d-dimensional axis-parallel rectangles
but evaluates in 1-d; its journal version (arXiv:1911.03456) resolves the
d > 1 case with per-dimension match bit-vectors combined by bitwise AND, and
arXiv:1309.3458 observes the per-dimension passes are embarrassingly
parallel.  This module implements both d-dim strategies on the repo's sweep
substrate (DESIGN.md §8):

* **Selective-dimension sweep** — run the *cheap* counting sweep
  (:func:`repro.core.sweep.sbm_count`) on every projection, pick the
  dimension with the fewest 1-d matches as the candidate generator, then
  enumerate candidates on that dimension only and filter the remaining
  projections pairwise.  ``max_pairs`` must bound the *generator-dimension*
  candidate count — the minimum over dimensions, not the dim-0 count the
  old hardcoded composition required.  Output-sensitive in the most
  selective projection: O(d·(n+m)·log(n+m) + K_best).

* **Bit-matrix AND** — one packed match bitmap per dimension
  (n × ceil(m/32) ``uint32`` words), AND-reduced across dimensions;
  popcount gives the exact d-dim K and ``max_pairs`` needs to bound only
  the *final* match count.  O(d·n·m/32) word operations — the right tool
  when every projection is dense (the tall-thin adversarial regime where
  any candidate-generating dimension explodes).  The Pallas form
  (:func:`repro.kernels.bitmatch.bitmatrix_pallas`) does the blockwise
  pack/AND/popcount in VMEM; :func:`bitmatrix_sharded` runs the same
  scheme across a device mesh axis, sharding the subscription rows.

Both strategies are property-tested against the d-dim brute-force oracle
and the sequential Algorithm-4 sweep extended to d dims
(``tests/test_core_ddim.py``).
"""
from __future__ import annotations

import contextlib
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import prefix as prefix_lib
from repro.core.enumerate import (
    _count_dtype,
    _empty_result,
    enumerate_matches,
    sbm_enumerate,
)
from repro.core import runtime as runtime_lib
from repro.core.intervals import Extents, intersect_1d
from repro.core.runtime import pad_axis as _pad_axis  # noqa: F401 — canonical
from repro.core.sweep import sbm_count
from repro.core.errors import ValidationError


def _dim_rows(e: Extents) -> Tuple[jax.Array, jax.Array]:
    """(d, n) views of lo/hi — promotes the 1-d layout to one row."""
    if e.lo.ndim == 1:
        return e.lo[None, :], e.hi[None, :]
    return e.lo, e.hi


# ---------------------------------------------------------------------------
# Dimension selection (the cheap counting sweep as a selectivity probe)
# ---------------------------------------------------------------------------

def per_dimension_counts(
    subs: Extents, upds: Extents, *, num_segments: int = 8,
    stats: Optional[runtime_lib.MatchStats] = None,
) -> Tuple[int, ...]:
    """1-d match count of every projection — d counting sweeps.

    Each count is the candidate-buffer size a sweep on that dimension would
    need; the counting sweep is O((n+m)·log(n+m)) per dimension, so probing
    all d dimensions costs far less than enumerating candidates on a wrong
    (non-selective) one.  With ``stats``, each count's blocking read runs
    in its ``probe.readback`` span and counts in ``stats.readbacks``.
    """
    counts = []
    for d in range(subs.ndim_space):
        count = sbm_count(subs.dim(d), upds.dim(d), num_segments=num_segments)
        with (contextlib.nullcontext() if stats is None
              else stats.readback("probe")):
            counts.append(int(count))
    return tuple(counts)


def select_dimension(
    subs: Extents, upds: Extents, *, num_segments: int = 8,
    stats: Optional[runtime_lib.MatchStats] = None,
) -> Tuple[int, Tuple[int, ...]]:
    """(most selective dimension, per-dimension 1-d counts).

    The generator dimension is the argmin of the per-projection match
    counts (ties break toward the lower dimension index, making the choice
    deterministic and the d=1 case the identity).  ``stats`` as in
    :func:`per_dimension_counts`.
    """
    counts = per_dimension_counts(subs, upds, num_segments=num_segments,
                                  stats=stats)
    return min(range(len(counts)), key=lambda d: counts[d]), counts


# ---------------------------------------------------------------------------
# Selective-dimension composition (candidates on dim g, filter the rest)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("skip_dim",))
def _filter_other_dims(subs: Extents, upds: Extents, pairs: jax.Array,
                       *, skip_dim: int):
    """Drop candidate pairs whose non-generator projections do not overlap.

    Valid pairs are compacted to the front (stable — candidate order is
    preserved); the returned count is the post-filter pair count.
    """
    s_lo, s_hi = _dim_rows(subs)
    u_lo, u_hi = _dim_rows(upds)
    valid = pairs[:, 0] >= 0
    i = jnp.maximum(pairs[:, 0], 0)
    j = jnp.maximum(pairs[:, 1], 0)
    keep = valid
    for d in range(s_lo.shape[0]):
        if d == skip_dim:
            continue
        keep = keep & intersect_1d(s_lo[d, i], s_hi[d, i],
                                   u_lo[d, j], u_hi[d, j])
    pairs = jnp.where(keep[:, None], pairs, -1)
    order = jnp.argsort(~keep, stable=True)
    return pairs[order], jnp.sum(keep, dtype=_count_dtype())


def enumerate_matches_ddim(
    subs: Extents,
    upds: Extents,
    *,
    max_pairs: int,
    block: int = 256,
    method: str = "sweep",
    num_segments: int = 8,
    generator_dim: Optional[int] = None,
):
    """d-dimensional pair enumeration (paper §3 + DESIGN.md §8).

    ``method``:

    * ``"sweep"`` (default) — **selective-dimension** composition: the
      counting sweep probes every projection and the dimension with the
      fewest 1-d matches generates candidates via :func:`sbm_enumerate`;
      the other projections are filtered pairwise.  ``max_pairs`` must
      bound the *generator-dimension* candidate count (the min over
      dimensions — see :func:`select_dimension`).  Pass ``generator_dim``
      to pin the generator (``generator_dim=0`` reproduces the legacy
      dim-0-then-filter composition, kept as the benchmark baseline).
    * ``"bitmatrix"`` — per-dimension packed bitmaps AND-reduced
      (:func:`bitmatrix_enumerate`); ``max_pairs`` bounds only the final
      d-dim match count K.
    * ``"blocked"`` — the O(n·m) all-pairs oracle on dim 0 + filter.

    Returns ``(pairs, count)`` with the repo-wide contract: a
    ``(max_pairs, 2)`` int32 buffer padded with ``(-1, -1)``; valid pairs
    are compacted to the front.  ``count`` is the exact post-filter pair
    count whenever the generator pass fit its buffer; if the generator
    candidates overflowed ``max_pairs``, ``count`` is the generator's own
    (exact) candidate count instead — greater than ``max_pairs``, so the
    standard "check ``count <= max_pairs``, retry with ``count``" loop
    detects the overflow and the retry returns the exact K.
    """
    if method not in ("sweep", "bitmatrix", "blocked"):
        raise ValidationError(f"unknown method {method!r}")
    if subs.size == 0 or upds.size == 0:
        return _empty_result(max_pairs)
    if method == "bitmatrix":
        return bitmatrix_enumerate(subs, upds, max_pairs=max_pairs)
    if subs.ndim_space == 1:   # before the probe — 1-d needs no selection
        if method == "sweep":
            return sbm_enumerate(subs, upds, max_pairs=max_pairs)
        return enumerate_matches(subs, upds, max_pairs=max_pairs, block=block)
    if method == "sweep":
        if generator_dim is None:
            gen, _counts = select_dimension(subs, upds,
                                            num_segments=num_segments)
        else:
            gen = generator_dim

        def candidates(a: Extents, b: Extents):
            return sbm_enumerate(a, b, max_pairs=max_pairs)
    else:  # blocked
        gen = 0 if generator_dim is None else generator_dim

        def candidates(a: Extents, b: Extents):
            return enumerate_matches(a, b, max_pairs=max_pairs, block=block)

    pairs, cand = candidates(subs.dim(gen), upds.dim(gen))
    pairs, kept = _filter_other_dims(subs, upds, pairs, skip_dim=gen)
    # Overflow contract: if the generator pass overflowed, `kept` counts
    # only the candidates that fit the buffer — a silent undercount.  The
    # generator count (exact past the buffer) is then the needed buffer
    # size, so return it: callers see count > max_pairs, retry with that
    # capacity, and the retry returns the exact post-filter K.
    return pairs, jnp.where(cand > max_pairs, cand.astype(kept.dtype), kept)


def enumerate_matches_ddim_planned(
    subs: Extents,
    upds: Extents,
    *,
    method: str = "sweep",
    block: int = 256,
    num_segments: int = 8,
    generator_dim: Optional[int] = None,
    policy: runtime_lib.CapacityPolicy = runtime_lib.DEFAULT_POLICY,
    recorder: Optional[runtime_lib.StatsRecorder] = None,
):
    """Plan-aware d-dim enumeration: probe → plan → emit, instrumented.

    The per-dimension counting sweeps double as the planner's selectivity
    probe: the generator dimension's 1-d count is exactly the candidate
    buffer the selective sweep needs, so ``max_pairs`` starts at its
    ladder bucket and the run is structurally retry-free.  The bit-matrix
    method probes the final d-dim K (popcount) instead — its buffer
    bounds only the true match count.  Returns ``(pairs, count, stats)``
    with the generator choice recorded as the stats ``regime``
    (DESIGN.md §10).
    """
    if method not in ("sweep", "bitmatrix", "blocked"):
        raise ValidationError(f"unknown method {method!r}")
    stats = runtime_lib.MatchStats(engine="ddim")
    gen = generator_dim
    with stats.phase("probe"):
        if subs.size == 0 or upds.size == 0:
            estimate = 0
            regime = method
        elif method == "bitmatrix":
            count = bitmatrix_count(subs, upds)
            with stats.readback("probe"):
                estimate = int(count)
            regime = "bitmatrix"
        elif subs.ndim_space == 1 or method == "blocked":
            from repro.core.sweep import probe_count

            if method == "sweep":
                estimate = probe_count(subs, upds, stats,
                                       num_segments=num_segments)
            else:
                estimate = None
            regime = method
        else:
            if gen is None:
                gen, counts = select_dimension(subs, upds,
                                               num_segments=num_segments,
                                               stats=stats)
                estimate = counts[gen]
            else:
                count = sbm_count(subs.dim(gen), upds.dim(gen),
                                  num_segments=num_segments)
                with stats.readback("probe"):
                    estimate = int(count)
            regime = f"sweep_dim{gen}"
    stats.regime = regime

    def fn(s, u, *, max_pairs):
        return enumerate_matches_ddim(
            s, u, max_pairs=max_pairs, block=block, method=method,
            num_segments=num_segments, generator_dim=gen)

    return runtime_lib.execute_enumeration(
        fn, subs, upds, estimate=estimate, policy=policy, stats=stats,
        recorder=recorder)


# ---------------------------------------------------------------------------
# Bit-matrix AND (journal version: per-dimension bit-vectors, bitwise AND)
# ---------------------------------------------------------------------------

@jax.jit
def bitmatrix_words(subs: Extents, upds: Extents) -> jax.Array:
    """The packed d-dim match matrix: (n, ceil(m/32)) ``uint32`` words.

    Bit ``j % 32`` of word ``(i, j // 32)`` is set iff S_i ∩ U_j ≠ ∅ in
    *every* dimension — the per-dimension match bit-vectors of the journal
    algorithm AND-reduced.  Pure-XLA form; the Pallas kernel
    (:func:`repro.kernels.bitmatch.bitmatrix_pallas`) computes the same
    words blockwise in VMEM without materializing the boolean mask in HBM.
    """
    s_lo, s_hi = _dim_rows(subs)
    u_lo, u_hi = _dim_rows(upds)
    mask = None
    for d in range(s_lo.shape[0]):
        hit = intersect_1d(s_lo[d, :, None], s_hi[d, :, None],
                           u_lo[d, None, :], u_hi[d, None, :])
        mask = hit if mask is None else mask & hit
    return prefix_lib.pack_bits(mask)


def _lane_safe_sum(x: jax.Array) -> jax.Array:
    """Σ of a nonnegative int32 vector with the repo-wide K ≥ 2³¹ contract.

    The same 16-bit-lane accumulation as the sweep engines
    (:func:`repro.core.sweep._lane_partial_sums` /
    :func:`repro.core.sweep.combine_lane_partials`): exact int64 under
    x64, saturating at the 2³¹−1 sentinel without — never a silent wrap.
    """
    from repro.core.sweep import _lane_partial_sums, combine_lane_partials

    return combine_lane_partials(*_lane_partial_sums(x.reshape(-1)))


def _popcount_total(words: jax.Array) -> jax.Array:
    """Σ popcount of a packed word matrix.

    A matrix of ~10⁸ words (hundreds of MB — comfortably materializable)
    already holds K up to ~3·10⁹ set bits, so a plain int32 sum would wrap
    to positive garbage; the per-word popcounts (each ≤ 32) go through
    :func:`_lane_safe_sum`.
    """
    return _lane_safe_sum(lax.population_count(words).astype(jnp.int32))


def bitmatrix_count(subs: Extents, upds: Extents) -> jax.Array:
    """d-dim K via the packed AND matrix — O(d·n·m/32) words.

    Same overflow contract as :func:`repro.core.sweep.sbm_count`: exact
    int64 under x64, saturating at the 2³¹−1 sentinel without.
    """
    if subs.size == 0 or upds.size == 0:
        return jnp.zeros((), _count_dtype())
    return _popcount_total(bitmatrix_words(subs, upds))


@functools.partial(jax.jit, static_argnames=("m", "max_pairs"))
def _emit_pairs_jit(words: jax.Array, *, m: int, max_pairs: int):
    mask = prefix_lib.unpack_bits(words, m)
    ii, jj = jnp.nonzero(mask, size=max_pairs, fill_value=-1)
    return jnp.stack([ii.astype(jnp.int32), jj.astype(jnp.int32)], axis=-1)


def pairs_from_bitmatrix(words: jax.Array, *, m: int, max_pairs: int,
                         count: Optional[jax.Array] = None):
    """(pairs, count) from packed match words — the shared emission tail.

    Deterministic row-major order (by subscription id, then update id) —
    the same order as the blocked oracle.  ``count`` is exact even when it
    exceeds ``max_pairs`` (the overflow contract of every engine); pass a
    precomputed total (e.g. the Pallas kernel's) to skip the popcount
    pass over the word matrix.
    """
    if count is None:
        count = _popcount_total(words)
    return _emit_pairs_jit(words, m=m, max_pairs=max_pairs), count


def bitmatrix_enumerate(subs: Extents, upds: Extents, *, max_pairs: int):
    """d-dim enumeration via the packed AND matrix.

    ``max_pairs`` bounds only the **final** d-dim match count K — never any
    single-dimension candidate count.  This is the engine for the regime
    where *every* projection is dense (no dimension is selective, so any
    generator explodes); when at least one thin dimension exists — e.g.
    the tall-thin adversary — the selective sweep is both faster and
    lighter (EXPERIMENTS.md §Ddim: 25× at n = m = 4096).
    """
    if subs.size == 0 or upds.size == 0:
        return _empty_result(max_pairs)
    words = bitmatrix_words(subs, upds)
    return pairs_from_bitmatrix(words, m=upds.size, max_pairs=max_pairs)


# ---------------------------------------------------------------------------
# Sharded bit-matrix (subscription rows over a device mesh axis)
# ---------------------------------------------------------------------------

def bitmatrix_sharded(subs: Extents, upds: Extents, mesh, axis_name: str):
    """(words, count) with subscription rows sharded over ``axis_name``.

    Each shard packs/ANDs its own rows against the replicated update set —
    the embarrassingly-parallel decomposition of the per-dimension passes
    (arXiv:1309.3458) — and the global K is a psum of per-shard popcounts.
    Subscription rows are padded to a shard multiple with inert
    ``[+inf, -inf]`` sentinels.  ``words`` keeps that padding: it is
    ``(round_up(n, P), ceil(m/32))``, row-sharded over ``axis_name``, and
    its rows from n on are all zero (an uneven row sharding does not
    exist, so the caller slices ``words[:n]`` where it gathers them).
    """
    from jax.sharding import PartitionSpec as P

    n, m = subs.size, upds.size
    if n == 0 or m == 0:
        return (jnp.zeros((n, max(-(-m // 32), 1)), jnp.uint32),
                jnp.zeros((), _count_dtype()))
    num_shards = mesh.shape[axis_name]
    s_lo, s_hi = _pad_axis(*_dim_rows(subs), num_shards)
    u_lo, u_hi = _dim_rows(upds)

    def body(s_lo, s_hi, u_lo, u_hi):
        # same global-reduction contract as sbm_count_shard_body: psum the
        # 16-bit lane partials, combine via the shared contract helper
        from repro.core.sweep import _lane_partial_sums, combine_lane_partials

        words = bitmatrix_words(Extents(s_lo, s_hi), Extents(u_lo, u_hi))
        pc = lax.population_count(words).astype(jnp.int32).reshape(-1)
        partials = (lax.psum(v, axis_name) for v in _lane_partial_sums(pc))
        return words, combine_lane_partials(*partials)

    fn = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, axis_name), P(None, axis_name), P(), P()),
        out_specs=(P(axis_name), P())))
    return fn(s_lo, s_hi, u_lo, u_hi)
