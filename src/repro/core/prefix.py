"""Prefix computations (paper §4, Fig. 5) — the engine of parallel SBM.

Three realizations of the same scan, all exact:

* ``cumsum_two_level`` — the paper's two-level scheme: P local scans, a
  master scan over the P partials, then a broadcast-add.  O(N/P + P).
* ``cumsum_blelloch`` — tree-structured scan (Blelloch 1989) via
  ``jax.lax.associative_scan``.  O(N/P + log P).
* ``shard_exclusive_offsets`` — the two-level scheme *across a device mesh*
  (inside ``shard_map``): each chip reduces its shard, partials are
  all-gathered (the "master" step is replicated — it is O(P) scalars), and
  each chip keeps its own exclusive prefix.  This is the paper's algorithm
  with "OpenMP thread" replaced by "TPU chip" and the shared-memory master
  replaced by an ICI all-gather.

Also provided: the *delta-set monoid* of Algorithm 6 (set semantics), used by
the faithful set-form SBM and the Pallas sweep kernel's bitmask variant.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from repro.core.errors import ValidationError


# --------------------------------------------------------------------------
# Dense scans
# --------------------------------------------------------------------------

def exclusive_from_inclusive(inc: jax.Array, axis: int = -1) -> jax.Array:
    """Shift an inclusive scan to the exclusive scan of the same sequence."""
    zero = jnp.zeros_like(lax.slice_in_dim(inc, 0, 1, axis=axis))
    return jnp.concatenate([zero, lax.slice_in_dim(inc, 0, inc.shape[axis] - 1, axis=axis)], axis=axis)


def cumsum_two_level(x: jax.Array, num_segments: int) -> jax.Array:
    """Inclusive prefix sum via the paper's two-level scheme (Fig. 5).

    Step 1: split into ``P = num_segments`` equal segments, local cumsum.
    Step 2: "master" prefix over the P segment totals.
    Step 3: broadcast-add the exclusive totals back.

    ``x.shape[-1]`` must be divisible by ``num_segments`` (callers pad).
    """
    n = x.shape[-1]
    if n % num_segments:
        raise ValidationError(f"{n=} not divisible by {num_segments=}")
    seg = n // num_segments
    xs = x.reshape(x.shape[:-1] + (num_segments, seg))
    local = jnp.cumsum(xs, axis=-1)                      # step 1 (parallel)
    totals = local[..., -1]                              # (..., P)
    carry = exclusive_from_inclusive(jnp.cumsum(totals, axis=-1))  # step 2 (master)
    out = local + carry[..., None]                       # step 3 (parallel)
    return out.reshape(x.shape)


def cumsum_blelloch(x: jax.Array) -> jax.Array:
    """Tree-structured inclusive scan — O(N/P + log P) work-depth."""
    return lax.associative_scan(jnp.add, x, axis=-1)


# --------------------------------------------------------------------------
# Distributed scan (the two-level scheme across a mesh axis)
# --------------------------------------------------------------------------

def shard_exclusive_offsets(local_total: jax.Array, axis_name: str) -> jax.Array:
    """Exclusive prefix of per-shard totals along ``axis_name``.

    To be called *inside* ``shard_map``: ``local_total`` is this shard's
    reduction (any shape); returns the sum of all *earlier* shards' totals.
    Implementation is the paper's master step: all-gather the P partials
    (tiny: one element per shard) and combine locally.
    """
    idx = lax.axis_index(axis_name)
    gathered = lax.all_gather(local_total, axis_name)      # (P, ...)
    p = gathered.shape[0]
    mask = (jnp.arange(p) < idx).astype(gathered.dtype)
    mask = mask.reshape((p,) + (1,) * (gathered.ndim - 1))
    return jnp.sum(gathered * mask, axis=0)


def shard_inclusive_cumsum(x_shard: jax.Array, axis_name: str) -> jax.Array:
    """Full distributed inclusive cumsum of a sharded 1-D array."""
    local = jnp.cumsum(x_shard, axis=-1)
    carry = shard_exclusive_offsets(local[..., -1], axis_name)
    return local + carry[..., None]


# --------------------------------------------------------------------------
# Delta-set monoid (Algorithm 6, set semantics)
# --------------------------------------------------------------------------
# An element (A, D) denotes the state transformer  S ↦ (S \ D) ∪ A  with the
# invariant A ∩ D = ∅ (an interval cannot both open and close strictly across
# the same segment).  Composition (apply e1 then e2):
#     A' = (A1 \ D2) ∪ A2      D' = (D1 ∪ D2) \ A2
# Identity: (∅, ∅).  Works elementwise on boolean masks or bitmask words.

def delta_combine_bool(e1: Tuple[jax.Array, jax.Array],
                       e2: Tuple[jax.Array, jax.Array]):
    a1, d1 = e1
    a2, d2 = e2
    a = (a1 & ~d2) | a2
    d = (d1 | d2) & ~a2
    return a, d


def delta_combine_bits(e1: Tuple[jax.Array, jax.Array],
                       e2: Tuple[jax.Array, jax.Array]):
    """Same monoid on packed uint32 bitmask words (TPU-friendly form)."""
    a1, d1 = e1
    a2, d2 = e2
    a = (a1 & ~d2) | a2
    d = (d1 | d2) & ~a2
    return a, d


def delta_scan_exclusive(add: jax.Array, rem: jax.Array):
    """Exclusive scan of per-segment delta sets.

    ``add``/``rem``: (P, n) boolean masks — Algorithm 6's Sadd[p]/Sdel[p] —
    or (P, W) packed uint32 words (the combine is elementwise bitwise, so
    both representations share this one implementation).  Returns
    ``active``: same shape/dtype — SubSet[p], the active set *entering*
    segment p (paper: the value sequential SBM has right after T_{p-1}).
    For the Pallas emission pass this is each block's starting VMEM mask.
    """
    inc_a, _inc_d = lax.associative_scan(
        lambda e1, e2: delta_combine_bool(e1, e2), (add, rem), axis=0)
    # Active set entering segment p = inclusive combine of segments [0, p-1]
    # applied to ∅  →  it is just the A component of the exclusive scan.
    p = add.shape[0]
    zero = jnp.zeros_like(add[:1])
    active = jnp.concatenate([zero, inc_a[: p - 1]], axis=0)
    return active


def pack_bits(mask: jax.Array) -> jax.Array:
    """Pack a (..., n) boolean mask into (..., ceil(n/32)) uint32 words."""
    n = mask.shape[-1]
    pad = (-n) % 32
    if pad:
        mask = jnp.concatenate(
            [mask, jnp.zeros(mask.shape[:-1] + (pad,), mask.dtype)], axis=-1)
    m = mask.reshape(mask.shape[:-1] + ((n + pad) // 32, 32)).astype(jnp.uint32)
    weights = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))
    return jnp.sum(m * weights, axis=-1, dtype=jnp.uint32)


def unpack_bits(words: jax.Array, n: int) -> jax.Array:
    """Inverse of :func:`pack_bits`."""
    bits = (words[..., :, None] >> jnp.arange(32, dtype=jnp.uint32)) & jnp.uint32(1)
    flat = bits.reshape(words.shape[:-1] + (words.shape[-1] * 32,))
    return flat[..., :n].astype(jnp.bool_)
