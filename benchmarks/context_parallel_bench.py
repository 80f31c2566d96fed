"""Measured TP-vs-CP comparison on gemma2's repeating block at the real
prefill_32k shape (b=32, s=32768, 512-device mesh) — the §Perf iteration 3
evidence for the gemma2 cell.

TP: the production pjit path (one pattern block, f32-promoted psum/layer).
CP: shard_map with sequence sharded over the model axis, replicated bf16
weights; the local layer uses halo windows, the global layer ring
attention; norms/projections/MLP fully local.

Both are lowered and compiled; wire bytes come from the same scan-aware HLO
accounting as every other number in EXPERIMENTS.md.
"""
from __future__ import annotations

import os
from typing import List


def run(rows: List[str]) -> None:
    if "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        rows.append("context_parallel_SKIP,0,needs 512-device env "
                    "(run via: python -m benchmarks.context_parallel_bench)")
        return
    _run(rows)


def _run(rows: List[str]) -> None:
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import SHAPES, get_config
    from repro.launch.hlo_analysis import collective_bytes
    from repro.launch.mesh import make_production_mesh
    from repro.models import transformer as tf_lib
    from repro.models.common import rmsnorm
    from repro.parallel.context_parallel import (halo_window_attention,
                                                 ring_attention)
    from repro.parallel.sharding import make_sharder

    cfg = get_config("gemma2-2b")
    shape = SHAPES["prefill_32k"]
    b, s, d = shape.global_batch, shape.seq_len, cfg.d_model
    h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    mesh = make_production_mesh(multi_pod=False)
    sharder = make_sharder(cfg, mesh)
    defs = tf_lib.block_defs(cfg, cfg.pattern)
    params_sds = jax.tree.map(
        lambda pd: jax.ShapeDtypeStruct(pd.shape, jnp.bfloat16,
                                        sharding=NamedSharding(mesh, P())),
        defs, is_leaf=lambda x: hasattr(x, "axes") and hasattr(x, "shape"))
    x_sds = jax.ShapeDtypeStruct((b, s, d), jnp.bfloat16,
                                 sharding=NamedSharding(
                                     mesh, P("data", "model", None)))

    # ---------------- TP (production path, one block) ----------------
    params_tp = jax.tree.map(
        lambda pd: jax.ShapeDtypeStruct(
            pd.shape, jnp.bfloat16,
            sharding=sharder.named(pd.axes, pd.shape)),
        defs, is_leaf=lambda x: hasattr(x, "axes"))
    x_tp = jax.ShapeDtypeStruct((b, s, d), jnp.bfloat16,
                                sharding=sharder.named(("batch", None, None),
                                                       (b, s, d)))

    def tp_block(params, x):
        out, _, _ = tf_lib._apply_block(cfg, sharder, cfg.pattern, params, x,
                                        jnp.broadcast_to(jnp.arange(s), (b, s)),
                                        None)
        return out

    with mesh:
        tp = jax.jit(tp_block).lower(params_tp, x_tp).compile()
    tp_wire = collective_bytes(tp.as_text())

    # ---------------- CP (shard_map, seq-sharded) ----------------
    def cp_attn(sub, x_l, *, window, q_off):
        dt = jnp.bfloat16
        w = sub["mixer"]
        q = jnp.einsum("bsd,dhk->bhsk", x_l, w["wq"].astype(dt))
        k = jnp.einsum("bsd,dhk->bhsk", x_l, w["wk"].astype(dt))
        v = jnp.einsum("bsd,dhk->bhsk", x_l, w["wv"].astype(dt))
        # (rope elided for the wire comparison — positionless probe)
        if window is not None:
            o = halo_window_attention(q, k, v, window=window,
                                      axis_name="model",
                                      softcap=cfg.attn_softcap)
        else:
            o = ring_attention(q, k, v, axis_name="model",
                               softcap=cfg.attn_softcap)
        return jnp.einsum("bhsk,hkd->bsd", o, w["wo"].astype(dt))

    def cp_block(params, x_l):
        dt = jnp.bfloat16
        for i, spec in enumerate(cfg.pattern):
            sub = params[f"layer{i}"]
            hdn = rmsnorm(sub["norm_mixer"], x_l, cfg.norm_eps)
            window = cfg.window if spec.mixer == "attn_local" else None
            x_l = x_l + cp_attn(sub, hdn, window=window, q_off=0)
            hdn = rmsnorm(sub["norm_mlp"], x_l, cfg.norm_eps)
            g = jnp.einsum("bsd,df->bsf", hdn, sub["mlp"]["w_gate"].astype(dt))
            u = jnp.einsum("bsd,df->bsf", hdn, sub["mlp"]["w_up"].astype(dt))
            x_l = x_l + jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u,
                                   sub["mlp"]["w_down"].astype(dt))
        return x_l

    fn = shard_map(cp_block, mesh=mesh,
                   in_specs=(P(), P("data", "model", None)),
                   out_specs=P("data", "model", None), check_vma=False)
    with mesh:
        cp = jax.jit(fn).lower(params_sds, x_sds).compile()
    cp_wire = collective_bytes(cp.as_text())

    blocks = cfg.num_blocks
    tpw = tp_wire["wire_bytes_adj"]
    cpw = cp_wire["wire_bytes_adj"]
    rows.append(f"cp_gemma2_block_tp_wire_gb,{tpw/1e9:.3f},x{blocks}blocks")
    rows.append(f"cp_gemma2_block_cp_wire_gb,{cpw/1e9:.3f},x{blocks}blocks")
    rows.append(f"cp_gemma2_block_wire_ratio,{tpw/max(cpw,1):.1f},"
                f"t_coll_full_model_cp={cpw*blocks/50e9:.4f}s")


def main() -> None:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    rows: List[str] = []
    _run(rows)
    for r in rows:
        print(r)


if __name__ == "__main__":
    main()
