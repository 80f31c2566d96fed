"""Pallas TPU kernel for the d-dim bit-matrix AND (DESIGN.md §8).

The journal version of the source paper (arXiv:1911.03456) combines
per-dimension match bit-vectors with bitwise AND.  On TPU that maps onto a
2-D grid over *subscription row blocks* × *word blocks*: each grid step
holds one ``(BLOCK_N, d)`` slice of subscription extents and the updates
of ``WORD_BLOCK`` packed words, evaluates the d closed-interval overlap
masks on the VPU, AND-reduces them, and ORs them straight into packed
words.  The updates arrive bit-major — row ``32·dim + b`` holds the
updates ``32·w + b`` for every word ``w`` — so bit ``b`` of every word is
one ``(BLOCK_N, WORD_BLOCK)`` compare, shift and OR: no lane reshape, no
unsigned reduction.  Words are int32 inside the kernel (bit 31 is the
sign bit; the bits are disjoint, so OR equals sum) and are bitcast to
``uint32`` outside.  Per-row match counts accumulate across the word axis.
The boolean n × m mask never exists anywhere: only the packed words and
the per-row counts leave the kernel.

VMEM per grid step: a few ``(BLOCK_N, WORD_BLOCK)`` int32 tiles —
4·256·512 = 512 KiB each at the defaults — plus the double-buffered
update block, 2·2·(32·d)·WORD_BLOCK·4 bytes; both are independent of n
and m, so every size stays within the v5e scoped-VMEM default.  Padding
rows and updates are inert ``[+inf, -inf]`` sentinels whose bits are
always zero.

The pure-jnp oracle is :func:`repro.core.ddim.bitmatrix_words`; agreement
(words, counts, and the emitted pair set) is pinned in
``tests/test_kernels_bitmatch.py``.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import ddim as ddim_lib
from repro.core.intervals import Extents

WORD_BLOCK = 512


def _bitmatch_kernel(s_lo_ref, s_hi_ref, u_lo_ref, u_hi_ref,
                     words_ref, counts_ref):
    """One grid step = one subscription row block against one word block.

    s_lo/s_hi: (BLOCK_N, d) f32; u_lo/u_hi: (32·d, WB) f32, bit-major.
    words_ref: (BLOCK_N, WB) int32; counts_ref: (BLOCK_N, 1) int32,
    accumulated over the word axis of the grid.
    """
    d = s_lo_ref.shape[1]
    words = jnp.zeros(words_ref.shape, jnp.int32)
    hits = jnp.zeros(words_ref.shape, jnp.int32)
    for b in range(32):  # static unroll — bit b of every word at once
        mask = None
        for dd in range(d):
            r = 32 * dd + b
            hit = (s_lo_ref[:, dd:dd + 1] <= u_hi_ref[r:r + 1, :]) & (
                u_lo_ref[r:r + 1, :] <= s_hi_ref[:, dd:dd + 1])
            mask = hit if mask is None else mask & hit
        bit = mask.astype(jnp.int32)
        words = words | jnp.left_shift(bit, b)
        hits = hits + bit
    words_ref[...] = words

    @pl.when(pl.program_id(1) == 0)
    def _():
        counts_ref[...] = jnp.zeros(counts_ref.shape, jnp.int32)

    counts_ref[...] += jnp.sum(hits, axis=-1, keepdims=True)


@functools.partial(
    jax.jit, static_argnames=("block_n", "word_block", "interpret")
)
def _bitmatrix_pallas_jit(s_lo, s_hi, u_lo, u_hi, *, block_n: int,
                          word_block: int, interpret: bool):
    """``s_*``: (n_pad, d) rows; ``u_*``: (32·d, W_pad) bit-major words."""
    n_pad, d = s_lo.shape
    w_pad = u_lo.shape[1]
    grid = (n_pad // block_n, w_pad // word_block)
    ext_spec = pl.BlockSpec((block_n, d), lambda i, j: (i, 0))
    upd_spec = pl.BlockSpec((32 * d, word_block), lambda i, j: (0, j))
    words, counts = pl.pallas_call(
        _bitmatch_kernel,
        grid=grid,
        in_specs=[ext_spec, ext_spec, upd_spec, upd_spec],
        out_specs=[
            pl.BlockSpec((block_n, word_block), lambda i, j: (i, j)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, w_pad), jnp.int32),
            jax.ShapeDtypeStruct((n_pad, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(s_lo, s_hi, u_lo, u_hi)
    return lax.bitcast_convert_type(words, jnp.uint32), counts[:, 0]


def _bit_major(lo, hi, num_words: int):
    """(d, m) update columns → (32·d, num_words): row 32·dim + b, column w
    holds update 32·w + b (inert sentinels past m)."""
    d = lo.shape[0]
    lo, hi = ddim_lib._pad_axis(lo, hi, 32 * num_words)
    def perm(x):
        return x.reshape(d, num_words, 32).transpose(0, 2, 1).reshape(
            32 * d, num_words)
    return perm(lo), perm(hi)


def bitmatrix_pallas(
    subs: Extents,
    upds: Extents,
    *,
    interpret: bool,
    block_n: int = 256,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(words, row_counts, k_total) via the blockwise VMEM pack/AND kernel.

    ``words`` is ``(n, ceil(m/32))`` uint32 — bit-identical to
    :func:`repro.core.ddim.bitmatrix_words` (padding words sliced off);
    ``row_counts`` is the per-subscription d-dim match count (int32 —
    exact, each row is bounded by m); ``k_total`` is their lane-safe sum
    (``repro.core.ddim._popcount_total``): exact int64 under x64,
    saturating at 2³¹−1 without — never a silent wrap.
    """
    n, m = subs.size, upds.size
    num_words = max(-(-m // 32), 1)
    if n == 0 or m == 0:
        return (
            jnp.zeros((n, num_words), jnp.uint32),
            jnp.zeros((n,), jnp.int32),
            jnp.zeros((), ddim_lib._count_dtype()),
        )
    # rows: a multiple of 8 sublanes; words: one block when they fit,
    # else whole WORD_BLOCK lane tiles
    block_n = min(block_n, -(-n // 8) * 8)
    word_block = (num_words if num_words <= WORD_BLOCK else WORD_BLOCK)
    w_pad = -(-num_words // word_block) * word_block
    s_lo, s_hi = ddim_lib._pad_axis(*ddim_lib._dim_rows(subs), block_n)
    u_lo, u_hi = _bit_major(*ddim_lib._dim_rows(upds), w_pad)
    words, counts = _bitmatrix_pallas_jit(
        s_lo.T, s_hi.T, u_lo, u_hi, block_n=block_n, word_block=word_block,
        interpret=interpret,
    )
    words = words[:n, :num_words]
    counts = counts[:n]
    # total from the kernel's own row counts (n terms, lane-safe) —
    # no second pass over the n x ceil(m/32) word matrix
    return words, counts, ddim_lib._lane_safe_sum(counts)


def sbm_bitmatrix_kernel(
    subs: Extents,
    upds: Extents,
    *,
    max_pairs: int,
    interpret: bool,
    block_n: int = 256,
) -> Tuple[jax.Array, jax.Array]:
    """d-dim (pairs, count) with the kernel-packed bit matrix as the engine.

    Same contract as :func:`repro.core.ddim.bitmatrix_enumerate` —
    ``max_pairs`` bounds only the final d-dim K; pairs emit in row-major
    order, padded with (-1, -1); count exact past the buffer.
    """
    n, m = subs.size, upds.size
    if n == 0 or m == 0:
        return (
            jnp.full((max_pairs, 2), -1, jnp.int32),
            jnp.zeros((), ddim_lib._count_dtype()),
        )
    words, _counts, k_total = bitmatrix_pallas(
        subs, upds, interpret=interpret, block_n=block_n
    )
    return ddim_lib.pairs_from_bitmatrix(
        words, m=m, max_pairs=max_pairs, count=k_total
    )
