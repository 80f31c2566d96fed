"""Brute-force (region-based) matching — paper §3.1, Algorithm 2.

O(n·m) compare-everything baseline.  Embarrassingly parallel; the blocked
form bounds peak memory to ``block × m`` so large instances stream through
VMEM-sized tiles instead of materializing the full n×m mask.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.intervals import Extents, intersect_1d


@functools.partial(jax.jit, static_argnames=("block",))
def bf_count(subs: Extents, upds: Extents, *, block: int = 1024) -> jax.Array:
    """Exact match count via blocked all-pairs comparison."""
    n = subs.lo.shape[0]
    pad = (-n) % block
    s_lo = jnp.pad(subs.lo, (0, pad), constant_values=jnp.inf)
    s_hi = jnp.pad(subs.hi, (0, pad), constant_values=-jnp.inf)
    s_lo = s_lo.reshape(-1, block)
    s_hi = s_hi.reshape(-1, block)

    def body(carry, blk):
        b_lo, b_hi = blk
        mask = intersect_1d(b_lo[:, None], b_hi[:, None],
                            upds.lo[None, :], upds.hi[None, :])
        return carry + jnp.sum(mask, dtype=jnp.int32), None

    total, _ = lax.scan(body, jnp.int32(0), (s_lo, s_hi))
    return total


def bf_count_sharded(subs: Extents, upds: Extents, mesh, axis_name: str,
                     *, block: int = 1024):
    """Paper §3.1 parallel BF: subscriptions sharded, updates replicated."""
    from jax.sharding import PartitionSpec as P

    # Pad to a shard multiple with inert [+inf, -inf] extents.
    num_shards = mesh.shape[axis_name]
    pad = (-subs.lo.shape[0]) % num_shards
    s_lo = jnp.concatenate([subs.lo, jnp.full((pad,), jnp.inf, subs.lo.dtype)])
    s_hi = jnp.concatenate([subs.hi, jnp.full((pad,), -jnp.inf, subs.hi.dtype)])

    def body(s_lo, s_hi, u_lo, u_hi):
        local = bf_count(Extents(s_lo, s_hi), Extents(u_lo, u_hi), block=block)
        return lax.psum(local, axis_name)

    fn = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis_name), P(axis_name), P(), P()),
        out_specs=P(), check_vma=False))  # scan carry is shard-local
    return fn(s_lo, s_hi, upds.lo, upds.hi)
