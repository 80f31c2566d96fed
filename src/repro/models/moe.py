"""Mixture-of-Experts with *sort-based dispatch* — the paper's algorithm
skeleton (sort + prefix offsets + matched gather/scatter) applied to
token→expert routing.

Dispatch = matching the paper's way:
  1. every (token, choice) pair is a record keyed by expert id;
  2. records are *sorted* by expert (``argsort`` — the paper's phase 1);
  3. per-expert segment offsets come from ``searchsorted`` on the sorted
     keys (rank computation — the prefix phase);
  4. records are scattered into (E, capacity) expert bins (the emission).

Sorting is per batch row (vmapped), so data-parallel shards never sort
across each other, and the (E, capacity, d) dispatch tensor carries the
"experts" logical axis for EP sharding (or "expert_ffn" TP when the expert
count doesn't divide the mesh axis — see parallel.sharding.rules_for_config).

Aux outputs follow Switch/GShard: load-balancing loss + router z-loss.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models.api import ModelConfig, ParamDef


def moe_defs(cfg: ModelConfig):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    return {
        "router": ParamDef((d, e), ("embed", "experts"), "normal"),
        "w_gate": ParamDef((e, d, f), ("experts", "embed", "expert_ffn"),
                           "normal", scale_dim=d),
        "w_up": ParamDef((e, d, f), ("experts", "embed", "expert_ffn"),
                         "normal", scale_dim=d),
        "w_down": ParamDef((e, f, d), ("experts", "expert_ffn", "embed"),
                           "normal", scale_dim=f),
    }


def _capacity(tokens_per_group: int, cfg: ModelConfig) -> int:
    """Per-expert capacity for a dispatch group of ``tokens_per_group``
    tokens (records = tokens × top-k)."""
    cap = int(tokens_per_group * cfg.num_experts_per_token
              * cfg.moe_capacity_factor / cfg.num_experts)
    return max(8, -(-cap // 8) * 8)   # multiple of 8 lanes


def sort_based_dispatch(expert_ids: jax.Array, capacity: int,
                        num_experts: int):
    """Per-row dispatch schedule via sort + rank (the SBM skeleton).

    expert_ids: (R,) int32 — expert choice of each (token × top-k) record.
    Returns (bin_token (E, C) int32 record index or -1, kept (R,) bool,
    slot (R,) int32 — the capacity slot each record landed in (or -1)).
    """
    r = expert_ids.shape[0]
    order = jnp.argsort(expert_ids, stable=True)           # phase 1: sort
    sorted_e = expert_ids[order]
    pos = jnp.arange(r, dtype=jnp.int32)
    seg_start = jnp.searchsorted(sorted_e,
                                 jnp.arange(num_experts, dtype=sorted_e.dtype))
    rank = pos - seg_start[jnp.clip(sorted_e, 0, num_experts - 1)]  # phase 2
    keep = rank < capacity
    # phase 3: scatter records into (E, C) bins
    bins = jnp.full((num_experts, capacity), -1, jnp.int32)
    bins = bins.at[jnp.where(keep, sorted_e, num_experts),
                   jnp.clip(rank, 0, capacity - 1)].set(
        jnp.where(keep, order, -1), mode="drop")
    slot_sorted = jnp.where(keep, rank, -1)
    slot = jnp.zeros((r,), jnp.int32).at[order].set(slot_sorted)
    kept = jnp.zeros((r,), bool).at[order].set(keep)
    return bins, kept, slot


def select_moe_mode(cfg: ModelConfig, mesh, cap: int) -> str:
    """Pick the manual expert-apply strategy for this arch × mesh.

    * "ep"  — true expert parallelism (experts divide the model axis);
    * "cap" — capacity slots sharded, small expert weights replicated;
    * "ffn" — expert-FFN dim sharded (weights too big to replicate);
    * "gspmd" — fall back to the einsum path (no model axis / no fit).
    """
    if cfg.moe_impl != "auto":
        return cfg.moe_impl
    if mesh is None or "model" not in mesh.axis_names:
        return "gspmd"
    msize = mesh.shape["model"]
    if cfg.num_experts % msize == 0:
        return "ep"
    w_bytes = 3 * cfg.num_experts * cfg.d_model * cfg.d_ff * 2   # bf16
    if w_bytes <= 1.0e9 and cap % msize == 0:
        return "cap"
    if cfg.d_ff % msize == 0:
        return "ffn"
    return "gspmd"


def _moe_apply_shard_map(params, x, bin_token, bin_gate, cfg, sharder,
                         cap: int, mode: str):
    """Manual expert apply under shard_map (measured §Perf iteration).

    GSPMD's scatter partitioning all-gathers the (b, E, cap, d) update
    tensor around the dispatch/combine scatters (the dominant collective of
    every MoE train cell in the baseline dry-run).  These bodies do what
    the partitioner won't:

    * "ep":  experts sharded — local gather → local expert GEMMs → local
             scatter; one psum of the (b, s, d) partial output.
    * "cap": capacity slots sharded, weights replicated (small experts —
             granite's 40×512); same psum(b,s,d).
    * "ffn": expert-FFN dim sharded (grok-scale experts); the psum is over
             (b, E·cap, d) pre-combine activations — with top-2 routing
             E·cap ≈ 1.25·s so this stays O(s·d).

    All reductions happen in bf16.
    """
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = sharder.mesh
    dt = cfg.dtype
    b, s, d = x.shape
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    bspec = P(batch_axes if batch_axes else None)

    if mode == "ep":
        w_specs = (P("model", None, None),) * 3
        bt_spec = P(*bspec, "model", None)
    elif mode == "cap":
        rep = NamedSharding(mesh, P())
        w_specs = (P(), P(), P())
        bt_spec = P(*bspec, None, "model")
    else:  # ffn
        w_specs = (P(None, None, "model"), P(None, None, "model"),
                   P(None, "model", None))
        bt_spec = P(*bspec, None, None)

    wg = params["w_gate"].astype(dt)
    wu = params["w_up"].astype(dt)
    wd = params["w_down"].astype(dt)
    if mode == "cap":   # force one replicating (bf16) gather outside the body
        rep = NamedSharding(mesh, P())
        wg = jax.lax.with_sharding_constraint(wg, rep)
        wu = jax.lax.with_sharding_constraint(wu, rep)
        wd = jax.lax.with_sharding_constraint(wd, rep)

    def body(x_l, bt_l, bg_l, wg, wu, wd):
        bl = x_l.shape[0]
        e_l, cap_l = bt_l.shape[1], bt_l.shape[2]
        safe = jnp.maximum(bt_l, 0)
        xe = jnp.take_along_axis(
            x_l, safe.reshape(bl, -1)[..., None], axis=1
        ).reshape(bl, e_l, cap_l, d)
        xe = jnp.where((bt_l >= 0)[..., None], xe, 0.0)
        g = jnp.einsum("becd,edf->becf", xe, wg)
        u = jnp.einsum("becd,edf->becf", xe, wu)
        ye = jnp.einsum("becf,efd->becd", jax.nn.silu(g) * u, wd)
        if mode == "ffn":       # partial over the contracted f shard
            ye = jax.lax.psum(ye, "model")
        contrib = ye * bg_l[..., None].astype(ye.dtype)
        out = jnp.zeros((bl, s, d), ye.dtype)
        out = out.at[jnp.arange(bl)[:, None],
                     safe.reshape(bl, -1)].add(contrib.reshape(bl, -1, d))
        if mode != "ffn":
            out = jax.lax.psum(out, "model")
        return out

    fn = shard_map(
        body, mesh=mesh,
        in_specs=(P(*bspec, None, None), bt_spec, bt_spec) + w_specs,
        out_specs=P(*bspec, None, None),
        check_vma=False)
    return fn(x.astype(dt), bin_token, bin_gate, wg, wu, wd)


def moe_layer(params, x: jax.Array, cfg: ModelConfig, sharder
              ) -> Tuple[jax.Array, dict]:
    """x: (B, S, D) → (out, aux losses)."""
    dt = cfg.dtype
    b0, s0, d = x.shape
    e, k = cfg.num_experts, cfg.num_experts_per_token
    # dispatch groups: rows are merged into groups of `moe_group_rows` so
    # short-sequence (decode) dispatch amortizes the capacity floor across
    # the batch instead of paying E·cap_min per row.
    g_rows = max(1, min(cfg.moe_group_rows, b0))
    if b0 % g_rows:
        g_rows = 1
    if sharder.mesh is not None:
        # keep the grouped row count divisible by the batch shards, or the
        # divisibility fallback would silently drop data parallelism
        bs = 1
        for a in ("pod", "data"):
            if a in sharder.mesh.axis_names:
                bs *= sharder.mesh.shape[a]
        while g_rows > 1 and (b0 // g_rows) % bs:
            g_rows //= 2
    b, s = b0 // g_rows, g_rows * s0
    if g_rows > 1:
        x = x.reshape(b, s, d)
    cap = _capacity(s, cfg)

    logits = jnp.einsum("bsd,de->bse", x, params["router"].astype(dt)
                        ).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, choice = jax.lax.top_k(probs, k)            # (B,S,k)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # ---- aux losses (Switch §4: load balance; ST-MoE: router z-loss)
    density = jnp.mean(jax.nn.one_hot(choice[..., 0], e, dtype=jnp.float32),
                       axis=(0, 1))
    density_proxy = jnp.mean(probs, axis=(0, 1))
    aux_loss = e * jnp.sum(density * density_proxy)
    z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)

    # ---- sort-based dispatch, vmapped over the batch row (DP-local sorts)
    flat_choice = choice.reshape(b, s * k)
    bins, kept, slot = jax.vmap(
        lambda ids: sort_based_dispatch(ids, cap, e))(flat_choice)
    # bins: (B, E, C) record indices into the s*k records of that row

    rec_token = jnp.arange(s * k, dtype=jnp.int32) // k     # record → token
    safe_bins = jnp.maximum(bins, 0)
    bin_token = jnp.take_along_axis(
        jnp.broadcast_to(rec_token, (b, s * k)), safe_bins.reshape(b, -1),
        axis=1).reshape(b, e, cap)
    bin_valid = bins >= 0

    # combine weights per bin (needed by both apply paths)
    rec_gate_pre = gate_vals.reshape(b, s * k)
    bin_gate_pre = jnp.take_along_axis(rec_gate_pre, safe_bins.reshape(b, -1),
                                       axis=1).reshape(b, e, cap)
    bin_gate_pre = jnp.where(bin_valid, bin_gate_pre, 0.0)

    # manual shard_map path (EP / capacity-shard / ffn-TP)
    mesh = sharder.mesh
    mode = select_moe_mode(cfg, mesh, cap)
    if mode in ("ep", "cap", "ffn"):
        # shard_map needs the batch to split exactly over the batch axes
        bs = 1
        for a in ("pod", "data"):
            if a in mesh.axis_names:
                bs *= mesh.shape[a]
        if b % bs:
            mode = "gspmd"          # e.g. batch-1 long-context decode
    if mode in ("ep", "cap", "ffn"):
        out = _moe_apply_shard_map(params, x, bin_token,
                                   bin_gate_pre.astype(jnp.float32), cfg,
                                   sharder, cap, mode)
        out = out.astype(dt)
        if g_rows > 1:
            out = out.reshape(b0, s0, d)
        out = sharder.constrain(out, ("batch", None, None))
        dropped = 1.0 - jnp.mean(kept.astype(jnp.float32))
        return out, {"moe_aux_loss": aux_loss, "moe_z_loss": z_loss,
                     "moe_drop_fraction": dropped}

    # gather tokens into expert bins: (B, E, C, D)
    xe = jnp.take_along_axis(
        x[:, :, None, :], bin_token.reshape(b, e * cap)[:, :, None, None],
        axis=1).reshape(b, e, cap, d)
    xe = jnp.where(bin_valid[..., None], xe, 0.0)
    xe = sharder.constrain(xe, ("batch", "experts", "moe_cap", None))

    # expert FFNs (grouped GEMMs over the E axis)
    g = jnp.einsum("becd,edf->becf", xe, params["w_gate"].astype(dt))
    u = jnp.einsum("becd,edf->becf", xe, params["w_up"].astype(dt))
    g = sharder.constrain(g, ("batch", "experts", "moe_cap", "expert_ffn"))
    h = jax.nn.silu(g) * u
    ye = jnp.einsum("becf,efd->becd", h, params["w_down"].astype(dt))
    ye = sharder.constrain(ye, ("batch", "experts", "moe_cap", None))

    # combine: scatter-add expert outputs back to tokens, weighted by gates
    rec_gate = gate_vals.reshape(b, s * k)
    bin_gate = jnp.take_along_axis(rec_gate, safe_bins.reshape(b, -1),
                                   axis=1).reshape(b, e, cap)
    bin_gate = jnp.where(bin_valid, bin_gate, 0.0)
    contrib = ye * bin_gate[..., None].astype(ye.dtype)
    out = jnp.zeros((b, s, d), ye.dtype)
    out = out.at[jnp.arange(b)[:, None], bin_token.reshape(b, -1)].add(
        contrib.reshape(b, e * cap, d), mode="drop")
    out = out.astype(dt)
    if g_rows > 1:
        out = out.reshape(b0, s0, d)
    out = sharder.constrain(out, ("batch", None, None))

    dropped = 1.0 - jnp.mean(kept.astype(jnp.float32))
    return out, {"moe_aux_loss": aux_loss, "moe_z_loss": z_loss,
                 "moe_drop_fraction": dropped}
