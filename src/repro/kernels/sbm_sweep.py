"""Pallas TPU kernels for the parallel SBM sweep (paper Algorithms 5+6).

Hardware mapping (see DESIGN.md §2): the paper's "P OpenMP threads over a
shared sorted array" becomes a Pallas grid over blocks of the sorted
endpoint stream; the paper's shared-memory master scan becomes a tiny
exclusive scan between the two kernel passes.

Two kernel families:

* **Counting sweep** (two passes, vector unit):
    pass A  — per-block partial sums of the four ±1 indicator streams
              (sub-lower, sub-upper, upd-lower, upd-upper);
    (XLA)   — exclusive scan of the (num_blocks, 4) partials — Fig. 5 step 2;
    pass B  — per-block local cumsums + carried offsets → per-endpoint
              emission counts.  Σ = K.
  Both passes are branch-free int32 lane code; the in-block cumsum is a
  log-step (Hillis–Steele) scan over lane rotations.

* **Delta-set bitmask scan** (Algorithm 6 lines 1–17 verbatim, scalar
  unit): each grid block performs the *sequential* local scan of its
  segment, maintaining Add/Del bitmasks as SMEM words — unions and
  differences are bitwise ops, replacing the paper's std::set.  The
  per-segment parallelism is across grid blocks, exactly like the paper's
  per-thread segments.

* **Pair-emission pass C** (the paper's Algorithm 4 emission, set form,
  scalar unit): each grid block re-runs its segment's sequential scan with
  *active-set* bitmasks in SMEM scratch (seeded by the monoid-combined
  Add/Del deltas of the bitmask pass), and at every upper endpoint walks
  the counterpart bitmask emitting (i, j) records at consecutive slots of
  a per-block output region.  The active sets are hierarchical bitmaps
  (each level one bit per nonzero word of the level below), so a walk
  costs O(log₃₂ W + pairs emitted) instead of O(W).  The cross-block pair
  offsets are the exclusive scan of pass B's per-block emission totals —
  the same two-level scheme as the counting master step, applied to the
  output space.

Block shapes: the vector passes take (4, BLOCK) int32 blocks with BLOCK a
multiple of 128; the scalar kernels take rank-1 (BLOCK,) SMEM blocks, which
must cover whole 1024-word HBM tiles, so there BLOCK is a multiple of 1024.
They keep their bitmask words in SMEM (1 MiB on v5e): ceil(n/32) words per
set, padded to a multiple of 1024.  The delta pass holds two double-buffered
output sets; pass C holds the seed sets, their hierarchical scratch and two
double-buffered pair regions of ``cap`` (the largest per-block pair total)
words each.  :func:`emit_pairs_smem_bytes` and
:func:`delta_bitmasks_smem_bytes` give the footprints and the compiled
wrappers refuse a size past :data:`SMEM_BUDGET` before the compiler does.
At n = m = 5·10⁵ with 1024-endpoint blocks pass C takes a cap of up to
37888 pairs per block: the paper's uniform sets need 335 (α = 1) and
29974 (α = 100), the clustered ones over 3·10⁶.  Words cross the kernel
boundary as int32 and are bitcast to uint32 outside.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from repro.core.errors import ValidationError

# XLA lays rank-1 int32 arrays out in HBM in tiles of 1024 words; a
# rank-1 block must cover whole tiles
_SMEM_TILE = 1024

# SMEM of one v5e TensorCore (the compiler reports 1.00M) less a reserve
# for the compiler's own scalars (about 3 KiB measured)
SMEM_BUDGET = (1 << 20) - 4096


def _round_up(x: int, k: int) -> int:
    return -(-x // k) * k


def _smem_block(size: int):
    """A rank-1 SMEM block of ``size`` words, stepping with the grid."""
    return pl.BlockSpec((size,), lambda i: (i,), memory_space=pltpu.SMEM)


def _check_smem(kernel: str, need: int, sizes: str) -> None:
    if need > SMEM_BUDGET:
        raise ValidationError(
            f"{kernel} needs {need} bytes of SMEM at {sizes}, over the "
            f"{SMEM_BUDGET}-byte budget of one v5e core")


# ---------------------------------------------------------------------------
# Counting sweep — pass A: per-block partial sums
# ---------------------------------------------------------------------------

def _block_sums_kernel(deltas_ref, sums_ref):
    # deltas_ref: (4, BLOCK) int32; sums_ref: (4, 1) int32
    sums_ref[...] = jnp.sum(deltas_ref[...], axis=-1, keepdims=True)


# ---------------------------------------------------------------------------
# Counting sweep — pass B: local scan + carry → emission counts
# ---------------------------------------------------------------------------

def _lane_cumsum(x: jax.Array) -> jax.Array:
    """Inclusive cumsum along the last (lane) axis by log-step rotations.

    Each step adds the element ``s`` lanes back.  The rotated lane index
    rides along, so the select holds whichever way the rotation turns.
    """
    axis = x.ndim - 1
    n = x.shape[axis]
    idx = lax.broadcasted_iota(jnp.int32, x.shape, axis)
    s = 1
    while s < n:
        src = pltpu.roll(idx, s, axis)
        x = x + jnp.where(src == idx - s, pltpu.roll(x, s, axis), 0)
        s *= 2
    return x


def _emission_kernel(deltas_ref, offsets_ref, emit_ref):
    # deltas_ref: (4, BLOCK) int32 — [sub_lo, sub_up, upd_lo, upd_up]
    # offsets_ref: (4, 1) int32 — exclusive cross-block carry (master scan)
    # emit_ref: (1, BLOCK) int32 — per-endpoint emission counts
    deltas = deltas_ref[...]
    c = _lane_cumsum(deltas) + offsets_ref[...]
    sub_up = deltas[1:2]
    upd_up = deltas[3:4]
    active_sub_before = c[0:1] - (c[1:2] - sub_up)
    active_upd_before = c[2:3] - (c[3:4] - upd_up)
    emit_ref[...] = sub_up * active_upd_before + upd_up * active_sub_before


@functools.partial(jax.jit, static_argnames=("block_size", "interpret"))
def sweep_count_pallas(deltas: jax.Array, *, block_size: int,
                       interpret: bool):
    """Counting sweep over pre-sorted indicator deltas.

    ``deltas``: (4, total) int32 — the four indicator streams of the sorted
    endpoint stream, ``total`` padded to a multiple of ``block_size``
    (callers use :func:`repro.kernels.ops.sbm_count_kernel` which handles
    encoding/sorting/padding).  Returns (emission_counts (total,), K).
    """
    _, total = deltas.shape
    if total % block_size:
        raise ValidationError(f"{total=} not a multiple of {block_size=}")
    num_blocks = total // block_size
    stream_spec = pl.BlockSpec((4, block_size), lambda i: (0, i))
    carry_spec = pl.BlockSpec((None, 4, 1), lambda i: (i, 0, 0))

    # Pass A — paper Fig. 5 step 1 (parallel over blocks).
    sums = pl.pallas_call(
        _block_sums_kernel,
        grid=(num_blocks,),
        in_specs=[stream_spec],
        out_specs=carry_spec,
        out_shape=jax.ShapeDtypeStruct((num_blocks, 4, 1), jnp.int32),
        interpret=interpret,
    )(deltas)

    # Master step — Fig. 5 step 2: exclusive scan over P partials (tiny).
    offsets = jnp.cumsum(sums, axis=0) - sums

    # Pass B — Fig. 5 step 3 + emission (parallel over blocks).
    emit = pl.pallas_call(
        _emission_kernel,
        grid=(num_blocks,),
        in_specs=[stream_spec, carry_spec],
        out_specs=pl.BlockSpec((1, block_size), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, total), jnp.int32),
        interpret=interpret,
    )(deltas, offsets)

    emit = emit.reshape(total)
    return emit, jnp.sum(emit)


# ---------------------------------------------------------------------------
# Scalar bit helpers (int32 words; bit 31 is the sign bit)
# ---------------------------------------------------------------------------

def _i32(x: int) -> int:
    """The int32 with the bit pattern of the uint32 ``x``."""
    return int(np.uint32(x).view(np.int32))


# (mask of the bit positions whose index has bit k set, 2^k), k = 4..0
_CTZ_MASKS = tuple((_i32(mask), weight) for mask, weight in zip(
    (0xFFFF0000, 0xFF00FF00, 0xF0F0F0F0, 0xCCCCCCCC, 0xAAAAAAAA),
    (16, 8, 4, 2, 1)))


def _bit(i):
    return jnp.left_shift(jnp.int32(1), i & 31)


def _lowest_bit_index(x):
    """Index of the lowest set bit of a nonzero int32 word."""
    low = x & (-x)
    idx = jnp.int32(0)
    for mask, weight in _CTZ_MASKS:
        idx = idx + jnp.where((low & mask) != 0, weight, 0)
    return idx


# ---------------------------------------------------------------------------
# Delta-set bitmask scan (Algorithm 6 lines 1-17, set semantics, on-chip)
# ---------------------------------------------------------------------------

def _delta_bitmask_kernel(owner_ref, is_upper_ref, valid_ref,
                          add_ref, del_ref):
    """One grid block = one segment T_p; sequential local scan (the paper's
    per-thread loop), sets as bitmask words in SMEM.

    owner_ref/is_upper_ref/valid_ref: (BLOCK,) int32 endpoint records of
    ONE extent type (sub or upd) — records of the other type have valid=0.
    add_ref/del_ref: (W,) int32 — Sadd[p]/Sdel[p] bitmask words.
    """
    def zero(w, c):
        add_ref[w] = jnp.int32(0)
        del_ref[w] = jnp.int32(0)
        return c

    lax.fori_loop(0, add_ref.shape[0], zero, 0)

    def body(t, c):
        owner = owner_ref[t]
        w = owner >> 5
        bit = _bit(owner)

        # lower endpoint: Add ∪= {i}
        # upper endpoint: if i ∈ Add: Add \= {i}  else  Del ∪= {i}
        @pl.when(valid_ref[t] != 0)
        def _():
            add_w = add_ref[w]
            in_add = (add_w & bit) != 0
            upper = is_upper_ref[t] != 0
            add_ref[w] = jnp.where(upper, add_w & ~bit, add_w | bit)
            del_w = del_ref[w]
            del_ref[w] = jnp.where(upper & ~in_add, del_w | bit, del_w)
        return c

    lax.fori_loop(0, owner_ref.shape[0], body, 0)


@functools.partial(jax.jit, static_argnames=("num_words", "block_size",
                                             "interpret"))
def delta_bitmasks_pallas(owner: jax.Array, is_upper: jax.Array,
                          valid: jax.Array, *, num_words: int,
                          block_size: int, interpret: bool):
    """Per-segment Add/Del bitmasks for one extent type.

    Inputs are (total,) int32 slices of the sorted endpoint stream with
    ``valid`` selecting this extent type; ``total`` must be a multiple of
    ``block_size``.  Returns (add, del): (num_blocks, num_words) uint32 —
    exactly Algorithm 6's Sadd[p]/Sdel[p] (or Uadd/Udel).
    """
    total = owner.shape[0]
    if total % block_size:
        raise ValidationError(f"{total=} not a multiple of {block_size=}")
    if not interpret:
        _check_smem("delta_bitmasks_pallas",
                    delta_bitmasks_smem_bytes(block_size, num_words),
                    f"{block_size=}, {num_words=}")
    num_blocks = total // block_size
    wp = _round_up(num_words, _SMEM_TILE)
    ep_spec = _smem_block(block_size)
    add, rem = pl.pallas_call(
        _delta_bitmask_kernel,
        grid=(num_blocks,),
        in_specs=[ep_spec, ep_spec, ep_spec],
        out_specs=[_smem_block(wp), _smem_block(wp)],
        out_shape=[jax.ShapeDtypeStruct((num_blocks * wp,), jnp.int32)] * 2,
        interpret=interpret,
    )(jnp.clip(owner, 0, None), is_upper, valid)

    def words(x):
        return lax.bitcast_convert_type(
            x.reshape(num_blocks, wp)[:, :num_words], jnp.uint32)

    return words(add), words(rem)


def delta_bitmasks_smem_bytes(block_size: int, num_words: int) -> int:
    """SMEM bytes of :func:`delta_bitmasks_pallas`: three endpoint blocks
    and two word sets, each double-buffered."""
    return 4 * 2 * (3 * block_size + 2 * _round_up(num_words, _SMEM_TILE))


# ---------------------------------------------------------------------------
# Pair-emission pass C (Algorithm 4 emission with bitmask active sets)
# ---------------------------------------------------------------------------

def _level_layout(num_words: int):
    """(offset, size) of each level of a hierarchical bitmap over
    ``num_words`` leaf words, leaves first, up to a single top word."""
    levels, off, size = [], 0, num_words
    while True:
        levels.append((off, size))
        off += size
        if size == 1:
            return tuple(levels), off
        size = -(-size // 32)


class _HierBitmap:
    """A set of ids as leaf bitmask words plus summary levels, in one SMEM
    scratch array.  Bit b of word w at level L+1 is set iff word 32·w + b
    of level L is nonzero."""

    def __init__(self, ref, num_words: int):
        self.ref = ref
        self.levels, _ = _level_layout(num_words)

    def load(self, seed_ref):
        """Leaves := the seed words; summaries rebuilt from them."""
        ref = self.ref
        for off, size in self.levels[1:]:
            def zero(w, c, off=off):
                ref[off + w] = jnp.int32(0)
                return c
            lax.fori_loop(0, size, zero, 0)

        def copy(w, c):
            ref[w] = seed_ref[w]
            return c

        lax.fori_loop(0, self.levels[0][1], copy, 0)
        for (lo_off, lo_size), (hi_off, _) in zip(self.levels,
                                                   self.levels[1:]):
            def mark(w, c, lo_off=lo_off, hi_off=hi_off):
                @pl.when(ref[lo_off + w] != 0)
                def _():
                    ref[hi_off + (w >> 5)] = ref[hi_off + (w >> 5)] | _bit(w)
                return c
            lax.fori_loop(0, lo_size, mark, 0)

    def add(self, i):
        ref = self.ref
        for off, _ in self.levels:
            w = i >> 5
            ref[off + w] = ref[off + w] | _bit(i)
            i = w

    def remove(self, i):
        ref = self.ref
        emptied = jnp.bool_(True)
        for off, _ in self.levels:
            w = i >> 5
            old = ref[off + w]
            new = jnp.where(emptied, old & ~_bit(i), old)
            ref[off + w] = new
            emptied = emptied & (new == 0)
            i = w

    def walk(self, emit, carry):
        """``carry = emit(id, carry)`` for every member, ids ascending."""
        ref = self.ref

        def visit(depth, word_idx, carry):
            off, _ = self.levels[depth]

            def cond(state):
                return state[0] != 0

            def step(state):
                x, carry = state
                child = word_idx * 32 + _lowest_bit_index(x)
                if depth == 0:
                    carry = emit(child, carry)
                else:
                    carry = visit(depth - 1, child, carry)
                return x & (x - 1), carry

            _, carry = lax.while_loop(cond, step, (ref[off + word_idx], carry))
            return carry

        return visit(len(self.levels) - 1, jnp.int32(0), carry)


def _emission_pairs_kernel(owner_ref, is_upper_ref, is_sub_ref, valid_ref,
                           sub0_ref, upd0_ref, out_i_ref, out_j_ref,
                           sub_bits, upd_bits, *, ws: int, wu: int,
                           cap: int):
    """One grid block = one segment T_p: sequential sweep with emission.

    owner/is_upper/is_sub/valid: (BLOCK,) int32 endpoint records in SMEM
    (owner pre-clipped to >= 0; valid=0 marks padding).
    sub0/upd0: (Ws,)/(Wu,) int32 words — active sets *entering* the segment
    (the exclusive monoid combine of the per-segment Add/Del bitmasks).
    out_i/out_j: (CAP,) int32 — this block's pairs, in emission order,
    -1 padded; slots at or past ``cap`` are dropped.
    sub_bits/upd_bits: SMEM scratch, the live hierarchical active sets.
    """
    def clear(s, c):
        out_i_ref[s] = jnp.int32(-1)
        out_j_ref[s] = jnp.int32(-1)
        return c

    lax.fori_loop(0, out_i_ref.shape[0], clear, 0)
    subs = _HierBitmap(sub_bits, ws)
    upds = _HierBitmap(upd_bits, wu)
    subs.load(sub0_ref)
    upds.load(upd0_ref)

    def step(t, ptr):
        o = owner_ref[t]
        v = valid_ref[t] != 0
        up = is_upper_ref[t] != 0
        sb = is_sub_ref[t] != 0

        def write(i, j, ptr):
            @pl.when(ptr < cap)
            def _():
                out_i_ref[ptr] = i
                out_j_ref[ptr] = j
            return ptr + 1

        # an upper endpoint emits against every active counterpart, in
        # ascending counterpart id (slot ptr + d for the d-th one)
        ptr = lax.cond(v & up & sb,
                       lambda p: upds.walk(lambda j, q: write(o, j, q), p),
                       lambda p: p, ptr)
        ptr = lax.cond(v & up & ~sb,
                       lambda p: subs.walk(lambda i, q: write(i, o, q), p),
                       lambda p: p, ptr)

        # active-set maintenance: lower opens, upper closes (own type only)
        for own, is_own in ((subs, sb), (upds, ~sb)):
            @pl.when(v & is_own & ~up)
            def _(own=own):
                own.add(o)

            @pl.when(v & is_own & up)
            def _(own=own):
                own.remove(o)
        return ptr

    lax.fori_loop(0, owner_ref.shape[0], step, jnp.int32(0))


@functools.partial(jax.jit, static_argnames=("block_size", "cap",
                                             "interpret"))
def sweep_emit_pairs_pallas(owner: jax.Array, is_upper: jax.Array,
                            is_sub: jax.Array, valid: jax.Array,
                            sub_active0: jax.Array, upd_active0: jax.Array,
                            *, block_size: int, cap: int, interpret: bool):
    """Pass C: per-block pair emission from per-block starting active sets.

    ``owner``/``is_upper``/``is_sub``/``valid``: (total,) int32 sorted
    endpoint records, total a multiple of ``block_size`` (owner clipped
    to >= 0, padding marked valid=0).  ``sub_active0``/``upd_active0``:
    (num_blocks, W) uint32 active-set bitmasks entering each block.
    Returns (out_i, out_j): (num_blocks, cap) int32, each block's pairs at
    slots [0, block_emission_total), -1 elsewhere.  Callers stitch blocks
    together with the exclusive scan of pass B's per-block totals.
    """
    total = owner.shape[0]
    if total % block_size:
        raise ValidationError(f"{total=} not a multiple of {block_size=}")
    num_blocks = total // block_size
    ws = sub_active0.shape[1]
    wu = upd_active0.shape[1]
    if not interpret:
        _check_smem("sweep_emit_pairs_pallas",
                    emit_pairs_smem_bytes(block_size, ws, wu, cap),
                    f"{block_size=}, {ws=}, {wu=}, {cap=}")
    wsp, wup, capp = (_round_up(x, _SMEM_TILE) for x in (ws, wu, cap))

    def seed(words, wp):
        words = lax.bitcast_convert_type(words, jnp.int32)
        return jnp.pad(words, ((0, 0), (0, wp - words.shape[1]))).reshape(-1)

    ep_spec = _smem_block(block_size)
    out_i, out_j = pl.pallas_call(
        functools.partial(_emission_pairs_kernel, ws=ws, wu=wu, cap=cap),
        grid=(num_blocks,),
        in_specs=[ep_spec, ep_spec, ep_spec, ep_spec,
                  _smem_block(wsp), _smem_block(wup)],
        out_specs=[_smem_block(capp), _smem_block(capp)],
        out_shape=[jax.ShapeDtypeStruct((num_blocks * capp,), jnp.int32)] * 2,
        scratch_shapes=[pltpu.SMEM((_level_layout(ws)[1],), jnp.int32),
                        pltpu.SMEM((_level_layout(wu)[1],), jnp.int32)],
        interpret=interpret,
    )(owner, is_upper, is_sub, valid,
      seed(sub_active0, wsp), seed(upd_active0, wup))
    return (out_i.reshape(num_blocks, capp)[:, :cap],
            out_j.reshape(num_blocks, capp)[:, :cap])


def emit_pairs_smem_bytes(block_size: int, ws: int, wu: int, cap: int) -> int:
    """SMEM bytes of :func:`sweep_emit_pairs_pallas`: four endpoint blocks,
    two seed sets and two pair regions, each double-buffered, plus the two
    hierarchical active sets of scratch."""
    wsp, wup, capp = (_round_up(x, _SMEM_TILE) for x in (ws, wu, cap))
    words = (2 * (4 * block_size + wsp + wup + 2 * capp)
             + _level_layout(ws)[1] + _level_layout(wu)[1])
    return 4 * words
