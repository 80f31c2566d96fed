"""Context parallelism: sequence-sharded attention via shard_map.

The §Perf diagnosis for window/local-attention prefill (gemma2-style): with
Megatron TP, every layer pays a (b, s, d) psum although the *data
dependency* between sequence shards is only the attention window.  Context
parallelism shards the sequence over the model axis with replicated (bf16)
weights, making norms/MLP/projections entirely local; the only
communication is what attention truly needs:

* ``halo_window_attention`` — local/sliding-window layers: one
  ``ppermute`` of the last ``window`` KV positions from the left neighbor
  (O(b·w·kv·hd) per layer, independent of s);
* ``ring_attention`` — full-causal layers: rotate KV chunks around the
  ring with a running online-softmax (Liu et al., Ring Attention), wire
  O(b·s·kv·hd / P) per hop × (P−1) hops — vs the TP psum's O(b·s·d).

Both are exact (tests/test_context_parallel.py: equal to dense attention
on an emulated mesh, including window edges and ring tie-breaks).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1.0e30


def _attend(q, k, v, mask, scale, softcap):
    """One masked block: returns (m, l, acc) online-softmax partials.

    q: (b, kvh, g, sq, hd); k/v: (b, kvh, sk, hd); mask: (sq, sk) or
    broadcastable.  All f32.
    """
    s = jnp.einsum("bkgqd,bksd->bkgqs", q, k) * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.where(mask, jnp.exp(s - m[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bkgqs,bksd->bkgqd", p, v)
    return m, l, acc


def _merge(m1, l1, a1, m2, l2, a2):
    """Combine two online-softmax partials (flash-decoding merge)."""
    m = jnp.maximum(m1, m2)
    c1 = jnp.exp(m1 - m)
    c2 = jnp.exp(m2 - m)
    return m, l1 * c1 + l2 * c2, a1 * c1[..., None] + a2 * c2[..., None]


def _split(q, kvh):
    b, h, s, hd = q.shape
    return q.reshape(b, kvh, h // kvh, s, hd)


def halo_window_attention(q, k, v, *, window: int, axis_name: str,
                          scale: Optional[float] = None,
                          softcap: Optional[float] = None) -> jax.Array:
    """Sliding-window causal attention over a seq-sharded layout.

    Call inside shard_map.  q (b,H,s_l,hd), k/v (b,KV,s_l,hd) hold this
    shard's contiguous s_l tokens; requires window ≤ s_l (one-neighbor
    halo).  Wire: one ppermute of (b,KV,window,hd) ×2.
    """
    b, h, s_l, hd = q.shape
    kvh = k.shape[1]
    if scale is None:
        scale = hd ** -0.5
    idx = lax.axis_index(axis_name)
    p = lax.axis_size(axis_name)
    num_halo = -(-window // s_l)                   # whole-chunk halos
    if num_halo >= p:
        raise ValueError(f"{window=} spans the whole ring; use ring_attention")
    perm = [(i, i + 1) for i in range(p - 1)]      # shift right (to me+1)
    k_chunks, v_chunks = [k], [v]
    ck, cv = k, v
    for _ in range(num_halo):
        ck = lax.ppermute(ck, axis_name, perm)
        cv = lax.ppermute(cv, axis_name, perm)
        k_chunks.insert(0, ck)
        v_chunks.insert(0, cv)
    k_ext = jnp.concatenate(k_chunks, axis=2).astype(jnp.float32)
    v_ext = jnp.concatenate(v_chunks, axis=2).astype(jnp.float32)

    q_pos = (idx * s_l + jnp.arange(s_l))[:, None]
    # extended keys start num_halo chunks to the left; shards near the ring
    # start hold garbage halos → masked by k_pos ≥ 0.
    ext = s_l * (num_halo + 1)
    k_pos = (idx * s_l - num_halo * s_l + jnp.arange(ext))[None, :]
    mask = (k_pos >= 0) & (k_pos <= q_pos) & (k_pos > q_pos - window)

    q5 = _split(q, kvh).astype(jnp.float32)
    m, l, acc = _attend(q5, k_ext, v_ext, mask, scale, softcap)
    safe = jnp.where(l > 0, l, 1.0)
    out = (acc / safe[..., None]).reshape(b, h, s_l, hd)
    return out.astype(q.dtype)


def ring_attention(q, k, v, *, axis_name: str,
                   scale: Optional[float] = None,
                   softcap: Optional[float] = None) -> jax.Array:
    """Full-causal attention over a seq-sharded layout (Ring Attention).

    KV chunks rotate around the ring; each hop contributes a masked partial
    merged with the running online softmax.  Wire per shard:
    (P−1) × (b·KV·s_l·hd·2 bytes) — vs the TP alternative's per-layer
    (b·s·d) psum.
    """
    b, h, s_l, hd = q.shape
    kvh = k.shape[1]
    if scale is None:
        scale = hd ** -0.5
    idx = lax.axis_index(axis_name)
    p = lax.axis_size(axis_name)
    perm = [(i, (i + 1) % p) for i in range(p)]    # rotate right
    q5 = _split(q, kvh).astype(jnp.float32)
    q_pos = (idx * s_l + jnp.arange(s_l))[:, None]

    def hop(carry, t):
        m, l, acc, kc, vc = carry
        src = (idx - t) % p                        # whose chunk we hold
        k_pos = (src * s_l + jnp.arange(s_l))[None, :]
        mask = k_pos <= q_pos
        m2, l2, a2 = _attend(q5, kc.astype(jnp.float32),
                             vc.astype(jnp.float32), mask, scale, softcap)
        m, l, acc = _merge(m, l, acc, m2, l2, a2)
        kc = lax.ppermute(kc, axis_name, perm)
        vc = lax.ppermute(vc, axis_name, perm)
        return (m, l, acc, kc, vc), None

    g = h // kvh
    m0 = jnp.full((b, kvh, g, s_l), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kvh, g, s_l), jnp.float32)
    a0 = jnp.zeros((b, kvh, g, s_l, hd), jnp.float32)
    (m, l, acc, _, _), _ = lax.scan(hop, (m0, l0, a0, k, v),
                                    jnp.arange(p, dtype=jnp.int32))
    safe = jnp.where(l > 0, l, 1.0)
    out = (acc / safe[..., None]).reshape(b, h, s_l, hd)
    return out.astype(q.dtype)


def cp_specs(mesh, batch_axes: Tuple[str, ...] = ("data",),
             seq_axis: str = "model"):
    """Convenience in/out specs for a seq-sharded (b, h, s, hd) tensor."""
    from jax.sharding import PartitionSpec as P
    return P(batch_axes, None, seq_axis, None)
