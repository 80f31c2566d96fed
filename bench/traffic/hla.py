"""HLA region sets with integer bounds, made on the device from the seed
and sharded over a mesh as they are made.

IEEE 1516.1 §9 gives each dimension of a routing space as a range of
non-negative integers.  N extents, the first ``n_sub`` subscriptions and
the rest updates, each of identical length l = αL/N units, placed
uniformly at random on the integers [0, L - l]: the paper's §5 placement
(arXiv:1703.06680) on an integer dimension, in int32.  Nothing here
imports the system under test.
"""
from __future__ import annotations

import functools


def segment_units(alpha: float, length: int, n_extents: int) -> int:
    """l = αL/N, which must be a whole number of units."""
    seg = alpha * length / n_extents
    if seg != int(seg) or not 0 <= seg <= length:
        raise ValueError(f"l = alpha L / N = {seg} is not a whole number "
                         f"of units in [0, L]")
    return int(seg)


@functools.lru_cache(maxsize=None)
def _set_fn(n_sub: int, n_upd: int, seg: int, length: int, mesh):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    sharded = NamedSharding(mesh, P(mesh.axis_names[0]))

    @functools.partial(jax.jit, out_shardings=(sharded,) * 4)
    def one_set(key):
        ks, ku = jax.random.split(key)
        s_lo = jax.random.randint(ks, (n_sub,), 0, length - seg + 1,
                                  dtype=jnp.int32)
        u_lo = jax.random.randint(ku, (n_upd,), 0, length - seg + 1,
                                  dtype=jnp.int32)
        return s_lo, s_lo + seg, u_lo, u_lo + seg

    return one_set


def uniform_sets(key, n_sets: int, n_extents: int, n_sub: int, alpha: float,
                 length: int, mesh):
    """``n_sets`` sets ``(s_lo, s_hi, u_lo, u_hi)``, int32 on the device,
    each array sharded over the mesh's one axis; set k from ``fold_in(key,
    k)``."""
    import jax

    if length >= 2**31:
        raise ValueError(f"L = {length} does not fit int32")
    seg = segment_units(alpha, length, n_extents)
    make = _set_fn(n_sub, n_extents - n_sub, seg, length, mesh)
    return [make(jax.random.fold_in(key, k)) for k in range(n_sets)]
