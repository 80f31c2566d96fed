"""Public jit'd wrappers around the Pallas kernels.

``interpret`` is an explicit keyword on every wrapper: ``False`` compiles
the kernel for the TPU, ``True`` runs the Pallas interpreter (the CPU test
path).  Nothing picks it from the backend.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import prefix as prefix_lib
from repro.core.intervals import Extents
from repro.core.sweep import encode_endpoints, _indicator_deltas, _pad_stream
from repro.kernels import flash_attention as fa
from repro.kernels import sbm_sweep as sweep_kernels


# ---------------------------------------------------------------------------
# SBM counting sweep
# ---------------------------------------------------------------------------

def sbm_count_kernel(subs: Extents, upds: Extents, *, interpret: bool,
                     block_size: int = 2048) -> jax.Array:
    """K via the Pallas two-pass sweep (sort on XLA, sweep on the kernel)."""
    ep = _pad_stream(encode_endpoints(subs, upds), block_size)
    deltas = jnp.stack(_indicator_deltas(ep))          # (4, total)
    _, k = sweep_kernels.sweep_count_pallas(
        deltas, block_size=block_size, interpret=interpret)
    return k


def sbm_delta_bitmasks(subs: Extents, upds: Extents, *, interpret: bool,
                       block_size: int = 1024):
    """Algorithm 6's (Sadd, Sdel, Uadd, Udel) as per-segment bitmask words."""
    n, m = subs.lo.shape[0], upds.lo.shape[0]
    ep = _pad_stream(encode_endpoints(subs, upds), block_size)
    up = ep.is_upper.astype(jnp.int32)
    valid_s = (ep.is_sub & (ep.owner >= 0)).astype(jnp.int32)
    valid_u = (~ep.is_sub & (ep.owner >= 0)).astype(jnp.int32)
    sw = -(-n // 32)
    uw = -(-m // 32)
    sadd, sdel = sweep_kernels.delta_bitmasks_pallas(
        ep.owner, up, valid_s, num_words=max(sw, 1), block_size=block_size,
        interpret=interpret)
    uadd, udel = sweep_kernels.delta_bitmasks_pallas(
        ep.owner, up, valid_u, num_words=max(uw, 1), block_size=block_size,
        interpret=interpret)
    return (sadd, sdel, uadd, udel)


@functools.partial(jax.jit, static_argnames=("max_pairs", "cap"))
def _stitch_blocks(out_i, out_j, block_sums, k_total, *, max_pairs: int,
                   cap: int):
    """Final (max_pairs, 2) buffer from per-block emission regions.

    Slot s lives in the block whose exclusive pair-offset range contains it
    (the output-space analogue of the counting master step).
    """
    num_blocks = out_i.shape[0]
    incl = jnp.cumsum(block_sums)
    slots = jnp.arange(max_pairs, dtype=jnp.int32)
    b = jnp.minimum(jnp.searchsorted(incl, slots, side="right"),
                    num_blocks - 1).astype(jnp.int32)
    r = slots - (incl[b] - block_sums[b])
    valid = (slots < jnp.minimum(k_total, max_pairs)) & (r < cap)
    r = jnp.clip(r, 0, cap - 1)
    pairs = jnp.stack([out_i[b, r], out_j[b, r]], axis=-1)
    return jnp.where(valid[:, None], pairs, -1)


def sbm_enumerate_kernel(subs: Extents, upds: Extents, *, max_pairs: int,
                         interpret: bool, block_size: int = 1024,
                         max_pairs_per_block: Optional[int] = None
                         ) -> Tuple[jax.Array, jax.Array]:
    """All matching (i, j) pairs via the three-pass Pallas sweep.

    Pass A/B (counting kernel) size the output: per-block emission totals
    and their exclusive scan are the cross-block pair offsets.  The bitmask
    delta pass plus the Algorithm-6 monoid combine seed each block's active
    sets, and pass C walks those SMEM bitmasks at every upper endpoint,
    scattering pairs into per-block regions that are stitched by the offset
    table.  Same contract as :func:`repro.core.sbm_enumerate` (pairs padded
    with -1; count exact even past ``max_pairs``).

    ``max_pairs_per_block`` is the static per-block region size; by default
    it is sized from the observed maximum block total (one host sync + one
    recompile per new high-water mark).
    """
    n, m = subs.lo.shape[0], upds.lo.shape[0]
    if n == 0 or m == 0:
        return jnp.full((max_pairs, 2), -1, jnp.int32), jnp.int32(0)

    ep = _pad_stream(encode_endpoints(subs, upds), block_size)
    deltas = jnp.stack(_indicator_deltas(ep))
    emit, k_total = sweep_kernels.sweep_count_pallas(
        deltas, block_size=block_size, interpret=interpret)
    block_sums = emit.reshape(-1, block_size).sum(axis=-1)
    if max_pairs_per_block is None:
        cap = max(int(jnp.max(block_sums)), 1)
    else:
        cap = max_pairs_per_block

    up = ep.is_upper.astype(jnp.int32)
    sb = ep.is_sub.astype(jnp.int32)
    valid = (ep.owner >= 0).astype(jnp.int32)
    valid_s = (ep.is_sub & (ep.owner >= 0)).astype(jnp.int32)
    valid_u = (~ep.is_sub & (ep.owner >= 0)).astype(jnp.int32)
    ws = max(-(-n // 32), 1)
    wu = max(-(-m // 32), 1)
    sadd, sdel = sweep_kernels.delta_bitmasks_pallas(
        ep.owner, up, valid_s, num_words=ws, block_size=block_size,
        interpret=interpret)
    uadd, udel = sweep_kernels.delta_bitmasks_pallas(
        ep.owner, up, valid_u, num_words=wu, block_size=block_size,
        interpret=interpret)
    sub_active0 = prefix_lib.delta_scan_exclusive(sadd, sdel)
    upd_active0 = prefix_lib.delta_scan_exclusive(uadd, udel)

    out_i, out_j = sweep_kernels.sweep_emit_pairs_pallas(
        jnp.clip(ep.owner, 0, None), up, sb, valid,
        sub_active0, upd_active0, block_size=block_size, cap=cap,
        interpret=interpret)
    pairs = _stitch_blocks(out_i, out_j, block_sums, k_total,
                           max_pairs=max_pairs, cap=cap)
    return pairs, k_total


# ---------------------------------------------------------------------------
# Interest-managed (block-sparse) flash attention
# ---------------------------------------------------------------------------

def build_block_structure(
    seq_len_q: int,
    seq_len_kv: int,
    *,
    block_q: int = 128,
    block_k: int = 128,
    causal: bool = True,
    window: Optional[int] = None,
    num_global_blocks: int = 0,
    extra_block_mask: Optional[np.ndarray] = None,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Static block sparsity via DDM interest matching (host-side).

    Query-block subscription extents vs KV-block update extents are matched
    with the core engine; the result is the (kv_index, kv_count) gather
    schedule consumed by the kernel.  Static by construction — attention
    structure is a function of shape parameters, not of data.
    """
    nq = seq_len_q // block_q
    nk = seq_len_kv // block_k
    # decode-style (Sq < Skv): query block i covers absolute positions
    # [off + i*bq, off + (i+1)*bq) where off right-aligns q to the kv window.
    off = seq_len_kv - seq_len_q
    q_start = np.arange(nq) * block_q + off
    q_end = q_start + block_q - 1
    lo = np.zeros(nq) if causal else np.zeros(nq)
    hi = q_end.astype(np.float64) if causal else np.full(nq, seq_len_kv - 1)
    if window is not None:
        lo = np.maximum(q_start - window + 1, 0).astype(np.float64)
    if num_global_blocks:
        lo[:num_global_blocks] = 0.0
        hi[:num_global_blocks] = seq_len_kv - 1
    k_start = np.arange(nk) * block_k
    k_end = k_start + block_k - 1
    # 1-D interval matching (the DDM primitive)
    bm = (lo[:, None] <= k_end[None, :]) & (k_start[None, :] <= hi[:, None])
    if extra_block_mask is not None:
        bm |= np.asarray(extra_block_mask, bool)
    counts = bm.sum(axis=1).astype(np.int32)
    max_nk = max(int(counts.max()), 1)
    kv_index = np.zeros((nq, max_nk), np.int32)
    for i in range(nq):
        idx = np.nonzero(bm[i])[0]
        kv_index[i, :len(idx)] = idx
    return kv_index, counts, bm


def flash_attention(
    q: jax.Array,            # (B, H, Sq, D)
    k: jax.Array,            # (B, Hkv, Skv, D)
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    q_segments: Optional[jax.Array] = None,
    kv_segments: Optional[jax.Array] = None,
    num_global_blocks: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool,
) -> jax.Array:
    """Interest-managed flash attention (public API).

    The block schedule comes from DDM matching over the (causal, window,
    global) interest extents; within-block masking handles the residual
    token-level structure (diagonal causality, window edges, document
    boundaries via segments).
    """
    B, H, Sq, D = q.shape
    Skv = k.shape[2]
    kv_index, kv_count, _ = build_block_structure(
        Sq, Skv, block_q=block_q, block_k=block_k, causal=causal,
        window=window, num_global_blocks=num_global_blocks)
    return fa.flash_attention_kernel(
        q, k, v, jnp.asarray(kv_index), jnp.asarray(kv_count),
        q_segments, kv_segments,
        causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_k=block_k, q_offset=Skv - Sq,
        interpret=interpret)
