"""Extent (interval / d-rectangle) containers and DDM workload generators.

Terminology follows the paper: *subscription* extents ``S`` and *update*
extents ``U`` are axis-parallel d-rectangles; the DDM problem asks for all
pairs ``(S_i, U_j)`` with a non-empty closed intersection.

Everything here is structure-of-arrays: an extent set with ``n`` members in
``d`` dimensions is a pair of ``(d, n)`` (or ``(n,)`` for d=1) arrays.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from repro.core.errors import ValidationError


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class Extents:
    """A set of closed intervals (d=1) or d-rectangles (lo/hi of shape (d, n))."""

    lo: jax.Array
    hi: jax.Array

    def tree_flatten(self):
        return (self.lo, self.hi), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def ndim_space(self) -> int:
        return 1 if self.lo.ndim == 1 else self.lo.shape[0]

    @property
    def size(self) -> int:
        return self.lo.shape[-1]

    def dim(self, d: int) -> "Extents":
        """Project onto dimension ``d`` (paper §3: d-dim reduces to 1-dim)."""
        if self.lo.ndim == 1:
            if d != 0:
                raise ValidationError(f"1-d extents have no dimension {d}")
            return self
        return Extents(self.lo[d], self.hi[d])

    def validate(self) -> "Extents":
        if self.lo.shape != self.hi.shape:
            raise ValidationError(f"lo/hi shape mismatch: {self.lo.shape} vs {self.hi.shape}")
        return self


def intersect_1d(x_lo, x_hi, y_lo, y_hi):
    """Algorithm 1 of the paper: closed-interval overlap test (broadcasts)."""
    return jnp.logical_and(x_lo <= y_hi, y_lo <= x_hi)


def intersect_ddim(a: Extents, b: Extents):
    """d-rectangles overlap iff all 1-d projections overlap (paper §3)."""
    if a.ndim_space == 1:
        return intersect_1d(a.lo, a.hi, b.lo, b.hi)
    per_dim = intersect_1d(a.lo[:, :, None], a.hi[:, :, None],
                           b.lo[:, None, :], b.hi[:, None, :])
    return jnp.all(per_dim, axis=0)


def _segment_length(alpha: float, length: float, total: int) -> float:
    """The paper-§5 segment length l = αL/N, guarded.

    With α·L/N > L, ``maxval = length - seg_len`` goes negative and
    ``jax.random.uniform`` silently samples a *reversed* interval — extents
    outside the routing space with lo > maxval, poisoning every matcher's
    ``lo <= hi`` precondition downstream.  Raise at the source instead.
    """
    seg_len = alpha * length / total
    if seg_len > length:
        raise ValidationError(
            f"alpha={alpha} with N={total} regions gives segment length "
            f"{seg_len} > routing space {length} (need alpha <= N); "
            "placement range length - seg_len would be negative")
    return seg_len


def make_uniform_workload(
    key: jax.Array,
    n_sub: int,
    n_upd: int,
    alpha: float,
    length: float = 1.0e6,
    d: int = 1,
) -> Tuple[Extents, Extents]:
    """The paper's §5 benchmark workload.

    ``N = n_sub + n_upd`` extents, each of identical side ``l = alpha * L / N``
    placed uniformly at random on a routing space of side ``L``. ``alpha`` is
    the *overlapping degree* — an indirect control of the match count ``K``.
    """
    total = n_sub + n_upd
    seg_len = _segment_length(alpha, length, total)
    shape = (total,) if d == 1 else (d, total)
    k_lo, = jax.random.split(key, 1)
    lo = jax.random.uniform(k_lo, shape, minval=0.0, maxval=length - seg_len,
                            dtype=jnp.float32)
    hi = lo + jnp.float32(seg_len)
    subs = Extents(lo[..., :n_sub], hi[..., :n_sub])
    upds = Extents(lo[..., n_sub:], hi[..., n_sub:])
    return subs, upds


def make_clustered_workload(
    key: jax.Array,
    n_sub: int,
    n_upd: int,
    alpha: float,
    n_clusters: int = 16,
    length: float = 1.0e6,
    d: int = 1,
) -> Tuple[Extents, Extents]:
    """A skewed workload (hot spots) to stress load balance of the sweep.

    ``d > 1`` places the cluster centers in d-space (each extent is a small
    d-cube around its center) — hot spots in *every* projection.
    """
    total = n_sub + n_upd
    seg_len = _segment_length(alpha, length, total)
    kc, kj = jax.random.split(key)
    shape = (total,) if d == 1 else (d, total)
    centers = jax.random.uniform(kc, (n_clusters,) if d == 1 else (d, n_clusters),
                                 minval=0.0, maxval=length)
    assign = jax.random.randint(kj, (total,), 0, n_clusters)
    jitter = jax.random.normal(jax.random.fold_in(kj, 1), shape) * (length / (20 * n_clusters))
    lo = jnp.clip(centers[..., assign] + jitter, 0.0, length - seg_len).astype(jnp.float32)
    hi = lo + jnp.float32(seg_len)
    return (Extents(lo[..., :n_sub], hi[..., :n_sub]),
            Extents(lo[..., n_sub:], hi[..., n_sub:]))


def make_tall_thin_workload(
    key: jax.Array,
    n_sub: int,
    n_upd: int,
    alpha: float = 1.0,
    length: float = 1.0e6,
    d: int = 2,
    wide_dim: int = 0,
) -> Tuple[Extents, Extents]:
    """The adversarial d-dim workload: dim ``wide_dim`` is non-selective.

    Every extent spans ≥ 98 % of the routing space along ``wide_dim`` (so
    *all* n·m pairs overlap in that projection — the HLA tall/thin routing
    shape), while the remaining dimensions carry the paper-§5 thin
    segments of length αL/N.  A candidate generator hardcoded to the wide
    dimension needs an O(n·m) buffer; the selective-dimension sweep and
    the bit-matrix AND stay proportional to the true K (DESIGN.md §8).
    """
    if d < 2:
        raise ValidationError("tall-thin needs d >= 2 (one wide + one thin dim)")
    total = n_sub + n_upd
    seg_len = _segment_length(alpha, length, total)
    k_lo, k_wide = jax.random.split(key)
    lo = jax.random.uniform(k_lo, (d, total), minval=0.0,
                            maxval=length - seg_len, dtype=jnp.float32)
    hi = lo + jnp.float32(seg_len)
    wide_lo = jax.random.uniform(k_wide, (total,), minval=0.0,
                                 maxval=0.02 * length, dtype=jnp.float32)
    lo = lo.at[wide_dim].set(wide_lo)
    hi = hi.at[wide_dim].set(wide_lo + jnp.float32(0.98 * length))
    return (Extents(lo[:, :n_sub], hi[:, :n_sub]),
            Extents(lo[:, n_sub:], hi[:, n_sub:]))


def brute_force_count_numpy(subs: Extents, upds: Extents) -> int:
    """O(n·m) oracle on host — ground truth for every matching test."""
    s_lo = np.asarray(subs.lo)
    s_hi = np.asarray(subs.hi)
    u_lo = np.asarray(upds.lo)
    u_hi = np.asarray(upds.hi)
    if s_lo.ndim == 1:
        mask = (s_lo[:, None] <= u_hi[None, :]) & (u_lo[None, :] <= s_hi[:, None])
        return int(mask.sum())
    mask = np.ones((s_lo.shape[1], u_lo.shape[1]), dtype=bool)
    for dd in range(s_lo.shape[0]):
        mask &= (s_lo[dd][:, None] <= u_hi[dd][None, :]) & (u_lo[dd][None, :] <= s_hi[dd][:, None])
    return int(mask.sum())


def brute_force_pairs_numpy(subs: Extents, upds: Extents) -> set:
    """Host oracle returning the exact match set {(i, j)}.

    The n × m comparison runs in row blocks of about 2²² cells on a thread
    pool (numpy releases the GIL inside the comparisons), so memory stays
    bounded and n = m = 10⁵ takes seconds, not minutes.
    """
    from concurrent.futures import ThreadPoolExecutor

    s_lo, s_hi, u_lo, u_hi = (np.asarray(x).reshape(
        (-1, np.shape(x)[-1])) for x in (subs.lo, subs.hi, upds.lo, upds.hi))
    n, m = s_lo.shape[1], u_lo.shape[1]
    rows = max(1, (1 << 22) // max(m, 1))

    def block(start):
        sl = slice(start, start + rows)
        mask = np.ones((s_lo[:, sl].shape[1], m), dtype=bool)
        for dd in range(s_lo.shape[0]):
            mask &= s_lo[dd, sl, None] <= u_hi[dd, None, :]
            mask &= u_lo[dd, None, :] <= s_hi[dd, sl, None]
        ii, jj = np.nonzero(mask)
        return zip((ii + start).tolist(), jj.tolist())

    out: set = set()
    with ThreadPoolExecutor(max_workers=os.cpu_count()) as pool:
        for part in pool.map(block, range(0, n, rows)):
            out.update(part)
    return out
