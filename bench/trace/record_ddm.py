#!/usr/bin/env python3
"""Record the small trace of the matcher that ``bench/tests/test_trace_ddm.py``
reads.

    python3 bench/trace/record_ddm.py --out DIR

Run on a chip.  Traces, under the harness's profiler options, a window of
planned audits (``repro.core.sbm_enumerate_planned``) of two small uniform
sets whose pair counts fall in different buffer buckets, so the emission
program runs in two variants: each inside an ``audit.match`` span, its
pairs pulled to the host inside ``audit.d2h``, as the static audit driver
does.  Copies the ``.xplane.pb`` to ``DIR/recorded_ddm.xplane.pb`` and
prints the programs it ran, the readbacks of each call and the reduction
by stage and by span.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
from pathlib import Path

sys.path[0] = str(Path(__file__).resolve().parents[2])
sys.path.insert(1, str(Path(__file__).resolve().parents[2] / "src"))

from bench.trace import reduce as trace_reduce  # noqa: E402
from bench.trace import scopes  # noqa: E402

N = 1 << 15          # extents per side
ALPHAS = (0.5, 8.0)  # K about 1.6·10⁴ and 2.6·10⁵: two buffer buckets
AUDITS = 3           # per set
SPANS = ("audit.match", "audit.d2h")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args()

    import jax
    import numpy as np
    from jax.profiler import TraceAnnotation

    from repro.core import make_uniform_workload, sbm_enumerate_planned

    sets = [make_uniform_workload(jax.random.PRNGKey(k), N, N, alpha)
            for k, alpha in enumerate(ALPHAS)]
    for subs, upds in sets:                       # compile outside the trace
        np.asarray(sbm_enumerate_planned(subs, upds)[0])
    tmp = tempfile.mkdtemp(prefix="bench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    calls = []
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with TraceAnnotation(trace_reduce.WINDOW_SPAN):
        for subs, upds in sets:
            for _ in range(AUDITS):
                with TraceAnnotation("audit.match"):
                    pairs, _, stats = sbm_enumerate_planned(subs, upds)
                with TraceAnnotation("audit.d2h"):
                    np.asarray(pairs)
                calls.append({"call": stats.call, "count": stats.count,
                              "capacity": stats.capacity,
                              "readbacks": stats.readbacks})
    jax.profiler.stop_trace()
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    dest = out / "recorded_ddm.xplane.pb"
    shutil.copy(trace_reduce.find_xplane(tmp), dest)
    shutil.rmtree(tmp, ignore_errors=True)

    from jax.profiler import ProfileData

    profile = ProfileData.from_file(str(dest))
    for plane in profile.planes:
        for line in plane.lines:
            if line.name == trace_reduce.PROGRAMS_LINE:
                names = sorted({ev.name for ev in line.events})
                print(f"{plane.name} programs: {names}")
            for ev in line.events:
                if ev.name.startswith(scopes.PREFIX):
                    print(f"first span {ev.name!r} stats "
                          f"{dict(ev.stats)}")
                    break
    print("calls: " + json.dumps(calls))
    s = scopes.reduce_file(str(dest), SPANS)
    print(f"size: {dest.stat().st_size} bytes")
    print("stages: " + json.dumps(s.stages))
    print(f"busy_s={s.busy_s} scoped_s={s.scoped_s} window_s={s.window_s}")
    print("idle_by_span: " + json.dumps(s.idle_by_span))
    print("device_ops: " + json.dumps(trace_reduce.top(s.ops, 30)))


if __name__ == "__main__":
    main()
