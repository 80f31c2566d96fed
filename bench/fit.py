"""Whether a configuration's audit fits a chip: the planned sweep's two
programs, the probe's count and the emission, compiled for a described
TPU v5e (no chip attached) at the configuration's sizes.

    python3 bench/fit.py --config bench/configs/hla_static_1d_4chip.json \
        --chips 1

``--chips 1`` compiles the one-chip programs (``_sbm_count_partials`` and
``_sbm_enumerate_jit``), ``--chips 4`` the mesh programs
(``_sort_count_sharded`` and ``_emit_sharded``) over a described v5e:2x2.
The pair buffer is the ladder bucket of the expected K of the paper's
uniform placement at α = 1, n·m·(2l + 1)/(L − l + 1).  Each program's
line gives the compiler's memory a chip (arguments, outputs,
temporaries, in bytes) or its refusal; nothing runs.

The one-chip emission's offset table is a saturating tree scan, which
the TPU compiler takes tens of minutes over at this scale.
``--offset-scan plain`` compiles it with ``jnp.cumsum`` in that scan's
place, as the mesh emission has it, to read the memory the rest of the
program needs; the line says which scan it compiled.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def expected_pairs(n: int, m: int, seg: int, length: int) -> int:
    """K of uniform integer placement: P(|lo - lo'| <= l) is about
    (2l + 1)/(L - l + 1)."""
    return round(n * m * (2 * seg + 1) / (length - seg + 1))


def report(name: str, lowered) -> dict:
    t = time.perf_counter()
    try:
        mem = lowered.compile().memory_analysis()
    except Exception as exc:           # the compiler's refusal is the answer
        return {"program": name, "fits": False,
                "error": str(exc).splitlines()[0][:400],
                "compile_s": time.perf_counter() - t}
    return {"program": name, "fits": True,
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "compile_s": time.perf_counter() - t}


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--offset-scan", choices=("tree", "plain"),
                    default="tree")
    ap.add_argument("--only", choices=("probe", "emission"))
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT / "src"))

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding

    from repro.core import Extents
    from repro.core.runtime import round_up_pow2

    # a compile for a described chip cannot be read back from the cache
    jax.config.update("jax_enable_compilation_cache", False)
    cfg = json.loads(Path(args.config).read_text())
    n = int(cfg["n_sub"])
    m = int(cfg["n_extents"]) - n
    length = int(cfg["length"])
    seg = length // int(cfg["n_extents"])          # l = αL/N at α = 1
    k = expected_pairs(n, m, seg, length)
    max_pairs = round_up_pow2(max(k, 1))
    dtype = jnp.dtype(cfg.get("bounds_dtype", "float32"))
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    print(json.dumps({"n": n, "m": m, "expected_k": k,
                      "max_pairs": max_pairs, "chips": args.chips,
                      "device_kind": topo.devices[0].device_kind}),
          flush=True)

    if args.chips == 1:
        from repro.core import enumerate as enumerate_lib
        from repro.core.sweep import _sbm_count_partials

        if args.offset_scan == "plain":
            enumerate_lib._offset_cumsum = lambda c: jnp.cumsum(
                c, dtype=jnp.int32)

        where = SingleDeviceSharding(topo.devices[0])

        def ext(size):
            return Extents(*(jax.ShapeDtypeStruct((size,), dtype,
                                                  sharding=where)
                             for _ in range(2)))

        subs, upds = ext(n), ext(m)
        lines = {
            "probe": lambda: report(
                "probe _sbm_count_partials", _sbm_count_partials.lower(
                    subs, upds, num_segments=8, scan_impl="two_level")),
            "emission": lambda: report(
                f"emission _sbm_enumerate_jit, {args.offset_scan} offset "
                f"scan", enumerate_lib._sbm_enumerate_jit.lower(
                    subs, upds, max_pairs=max_pairs, num_segments=8,
                    scan_impl="two_level"))}
    else:
        from repro.core.enumerate import _emit_sharded
        from repro.core.sweep import _sort_count_sharded

        mesh = jax.sharding.Mesh(topo.devices, ("p",))
        where = NamedSharding(mesh, P("p"))

        def ext(size):
            return Extents(*(jax.ShapeDtypeStruct((size,), dtype,
                                                  sharding=where)
                             for _ in range(2)))

        subs, upds = ext(n), ext(m)
        probe = jax.jit(lambda s, u: _sort_count_sharded(
            s, u, mesh=mesh, axis_name="p"))
        tags = jax.eval_shape(probe, subs, upds)[0]
        tags = jax.ShapeDtypeStruct(tags.shape, tags.dtype, sharding=where)
        lines = {
            "probe": lambda: report("probe _sort_count_sharded",
                                    probe.lower(subs, upds)),
            "emission": lambda: report(
                "emission _emit_sharded", _emit_sharded.lower(
                    tags, n=n, m=m, max_pairs=max_pairs, mesh=mesh,
                    axis_name="p"))}
    for name, line in lines.items():
        if args.only in (None, name):
            print(json.dumps(line()), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
