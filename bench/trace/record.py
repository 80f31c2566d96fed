#!/usr/bin/env python3
"""Record the small device trace that the reduction's tests read.

    python3 bench/trace/record.py --out DIR

Run on a chip.  Traces, under the harness's profiler options, a window of
a few jitted sorts inside ``tick.flush`` spans with host sleeps inside
``tick.move`` spans between them, copies the ``.xplane.pb`` to
``DIR/recorded.xplane.pb`` and prints each plane and line of it with its
first events.
"""
from __future__ import annotations

import argparse
import shutil
import sys
import tempfile
import time
from pathlib import Path

sys.path[0] = str(Path(__file__).resolve().parents[2])

from bench.trace import reduce as trace_reduce  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.cumsum(jnp.sort(x)))
    x = jax.random.uniform(jax.random.PRNGKey(0), (1 << 16,))
    f(x).block_until_ready()
    tmp = tempfile.mkdtemp(prefix="bench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
        for _ in range(5):
            with jax.profiler.TraceAnnotation("tick.flush"):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("tick.move"):
                time.sleep(0.002)
    jax.profiler.stop_trace()
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    dest = out / "recorded.xplane.pb"
    shutil.copy(trace_reduce.find_xplane(tmp), dest)
    shutil.rmtree(tmp, ignore_errors=True)

    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(str(dest)).planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            print(f"  line {line.name!r}: {len(evs)} events; first "
                  + "; ".join(f"{e.name!r} @{e.start_ns:.0f} "
                              f"+{e.duration_ns:.0f}" for e in evs[:4]))
    s = trace_reduce.reduce_dir(str(out), ("tick.flush", "tick.move"))
    print(f"reduced: {s}")


if __name__ == "__main__":
    main()
