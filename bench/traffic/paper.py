"""Paper §5 region sets (arXiv:1703.06680), made on the device from the seed.

N extents, the first ``n_sub`` subscriptions and the rest updates, each
of identical length l = αL/N, placed uniformly at random on [0, L - l] in
float32: the placement of ``repro.core.intervals.make_uniform_workload``,
copied here so that a change to the program cannot move the yardstick.
"""
from __future__ import annotations

import functools

import numpy as np

_MASK64 = 2**64 - 1


def seed_sequence(seed: int, stream: int) -> np.random.SeedSequence:
    """Any whole number as a seed, split into independent streams."""
    return np.random.SeedSequence([seed & _MASK64, stream])


def host_rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(seed_sequence(seed, stream))


def device_key(seed: int, stream: int):
    """A JAX key from any whole number (a seed past 32 bits included)."""
    import jax

    key = jax.random.PRNGKey(0)
    for word in seed_sequence(seed, stream).generate_state(2):
        key = jax.random.fold_in(key, np.uint32(word))
    return key


def segment_length(alpha: float, length: float, n_extents: int) -> float:
    """l = αL/N, the paper's identical extent length."""
    return alpha * length / n_extents


@functools.lru_cache(maxsize=None)
def _sets_fn(n_sets: int, n_extents: int, seg: float, length: float):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def sets(key):
        lo = jax.random.uniform(key, (n_sets, n_extents), minval=0.0,
                                maxval=length - seg, dtype=jnp.float32)
        return lo, lo + jnp.float32(seg)

    return sets


def uniform_sets(key, n_sets: int, n_extents: int, alpha: float,
                 length: float):
    """``(lo, hi)``, each ``(n_sets, n_extents)`` float32 on the device, in
    one jitted call."""
    seg = segment_length(alpha, length, n_extents)
    return _sets_fn(n_sets, n_extents, seg, length)(key)
