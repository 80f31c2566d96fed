"""Paper Figs. 7b / 9 / 10: parallel SBM scaling with P.

One process, one mesh per P ∈ {1, 2, 4, 8} that the visible devices allow
(``jax.devices()[:P]``): a chip belongs to one process, so the sweep for
every P runs here.  On one chip that is P = 1; on the CPU backend
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (set before JAX
starts) gives eight virtual devices.  Two measurements per P:

* wall-clock of the shard_mapped sweep.  On host-emulated devices the
  numbers say nothing about speedup — the devices share the host's cores.
* the *structural* cost-model check: per-device sweep work from the
  compiled HLO must follow the paper's O(N/P + P) law — per-device flops
  ≈ a·N/P + b·P.  This is hardware-independent and is the reproducible
  form of the paper's scaling analysis on any host.
"""
from __future__ import annotations

import functools
import time
from typing import List


def _measure(subs, upds, p: int) -> dict:
    import jax
    from jax.sharding import AxisType, PartitionSpec as P

    from repro.core import sbm_count_sharded
    from repro.core.sweep import (_indicator_deltas, _pad_stream,
                                  encode_endpoints, sbm_count_shard_body)

    mesh = jax.make_mesh((p,), ("p",), axis_types=(AxisType.Auto,),
                         devices=jax.devices()[:p])
    out = jax.block_until_ready(sbm_count_sharded(subs, upds, mesh, "p"))
    reps = 5
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(sbm_count_sharded(subs, upds, mesh, "p"))
    wct = (time.perf_counter() - t0) / reps
    # per-device structural cost from the compiled artifact
    deltas = _indicator_deltas(_pad_stream(encode_endpoints(subs, upds), p))
    fn = jax.shard_map(functools.partial(sbm_count_shard_body, axis_name="p"),
                       mesh=mesh, in_specs=(P("p"),) * 4, out_specs=P())
    cost = jax.jit(fn).lower(*deltas).compile().cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    return {"p": p, "wct_us": wct * 1e6,
            "flops_per_device": float(cost.get("flops", 0)),
            "k": int(out)}


def run(rows: List[str]) -> None:
    import jax

    from repro.core import make_uniform_workload

    n = 2_000_000
    subs, upds = make_uniform_workload(jax.random.PRNGKey(0), n // 2, n // 2,
                                       alpha=100.0)
    results = [_measure(subs, upds, p) for p in (1, 2, 4, 8)
               if p <= len(jax.devices())]
    for rec in results:
        rows.append(f"scaling_sbm_p{rec['p']},{rec['wct_us']:.1f},"
                    f"flops_per_dev={rec['flops_per_device']:.3e}")
    if len(results) >= 3 and all(r["flops_per_device"] > 0 for r in results):
        # paper cost law O(N/P + P): per-device work should shrink ~1/P
        f1 = results[0]["flops_per_device"]
        fp = results[-1]["flops_per_device"]
        rows.append(f"scaling_sbm_workdiv_f1_over_f{results[-1]['p']},"
                    f"{f1 / fp:.2f},ideal={results[-1]['p']}")
    ks = {r["k"] for r in results}
    rows.append(f"scaling_sbm_k_consistent,{1 if len(ks) == 1 else 0},K={ks}")
