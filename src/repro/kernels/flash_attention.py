"""Block-sparse FlashAttention forward kernel (Pallas TPU).

The sparsity structure is *interest-managed*: query blocks subscribe to key
ranges (causal prefix, sliding window, global sections, document spans) and
KV blocks update their token span; the DDM matching engine (repro.core)
turns those extents into the per-query-block KV index lists this kernel
consumes via scalar prefetch.  Blocks that match nothing are never visited —
the kernel's work is O(matched blocks), which is what makes 512k-token
contexts tractable.

Features: GQA (grouped KV heads), causal masking, sliding window, logit
soft-capping (Gemma-2), packed-document segment masking, online softmax with
f32 accumulation.  Layout: q (B, H, Sq, D), kv (B, Hkv, Skv, D); block sizes
are multiples of the (8, 128) VPU tile and D ∈ {64, 128} feeds the MXU.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.errors import ValidationError

NEG_INF = -1.0e30  # finite mask value: keeps exp() well-defined on dead rows
_LANES = 128       # m/l scratch replicated across VPU lanes


def _flash_kernel(kidx_ref, kcnt_ref,            # scalar prefetch
                  q_ref, k_ref, v_ref, qseg_ref, kseg_ref,  # VMEM blocks
                  o_ref,                           # output block
                  acc_ref, m_ref, l_ref,           # VMEM scratch
                  *, scale: float, causal: bool, window: Optional[int],
                  softcap: Optional[float], block_q: int, block_k: int,
                  use_segments: bool, q_offset: int):
    i = pl.program_id(2)          # query block
    t = pl.program_id(3)          # position in this block's KV index list

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(t < kcnt_ref[i])
    def _compute():
        k_blk = kidx_ref[i, t]
        q = q_ref[0, 0].astype(jnp.float32)          # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)          # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)          # (bk, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)

        q_pos = q_offset + i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = k_blk * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        if use_segments:
            mask &= qseg_ref[0, :][:, None] == kseg_ref[0, :][None, :]
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, 0]                          # (bq,)
        l_prev = l_ref[:, 0]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        p = jnp.where(mask, p, 0.0)                   # dead lanes contribute 0
        l_cur = l_prev * alpha + jnp.sum(p, axis=-1)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + pv
        m_ref[...] = jnp.broadcast_to(m_cur[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_cur[:, None], l_ref.shape)

    @pl.when(t == pl.num_programs(3) - 1)
    def _finalize():
        l = l_ref[:, 0]
        safe = jnp.where(l > 0.0, l, 1.0)
        o_ref[0, 0] = (acc_ref[...] / safe[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "causal", "window", "softcap", "block_q",
                     "block_k", "q_offset", "interpret"))
def flash_attention_kernel(
    q: jax.Array,            # (B, H, Sq, D)
    k: jax.Array,            # (B, Hkv, Skv, D)
    v: jax.Array,            # (B, Hkv, Skv, D)
    kv_index: jax.Array,     # (nq_blocks, max_nk) int32, padded with 0
    kv_count: jax.Array,     # (nq_blocks,) int32
    q_segments: Optional[jax.Array] = None,   # (B, Sq) int32
    kv_segments: Optional[jax.Array] = None,  # (B, Skv) int32
    *,
    scale: Optional[float] = None,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    q_offset: int = 0,
    interpret: bool = False,
) -> jax.Array:
    """Raw kernel entry — most callers use :func:`repro.kernels.ops.flash_attention`.

    ``q_offset``: absolute position of q[.., 0, ..] within the KV window
    (nonzero for chunked prefill, where Sq < Skv and q is right-aligned).
    """
    B, H, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    if Sq % block_q or Skv % block_k:
        raise ValidationError(f"{Sq=}/{Skv=} must be multiples of {block_q=}/{block_k=}")
    if H % Hkv:
        raise ValidationError(f"{H=} must be a multiple of {Hkv=}")
    group = H // Hkv
    nq = Sq // block_q
    max_nk = kv_index.shape[1]
    if scale is None:
        scale = 1.0 / (D ** 0.5)

    use_segments = q_segments is not None
    if not use_segments:
        q_segments = jnp.zeros((B, Sq), jnp.int32)
        kv_segments = jnp.zeros((B, Skv), jnp.int32)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, block_q=block_q, block_k=block_k,
        use_segments=use_segments, q_offset=q_offset)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, H, nq, max_nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, i, t, kidx, kcnt: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, t, kidx, kcnt, g=group: (b, h // g, kidx[i, t], 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, t, kidx, kcnt, g=group: (b, h // g, kidx[i, t], 0)),
            pl.BlockSpec((1, block_q),
                         lambda b, h, i, t, kidx, kcnt: (b, i)),
            pl.BlockSpec((1, block_k),
                         lambda b, h, i, t, kidx, kcnt: (b, kidx[i, t])),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, i, t, kidx, kcnt: (b, h, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
    )

    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(kv_index, kv_count, q, k, v, q_segments, kv_segments)
