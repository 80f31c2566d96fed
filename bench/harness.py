"""The benchmark harness: one cell, one process, one result line.

Everything is found by name.  ``BENCHMARK.json`` names the cell's
configuration (its file) and traffic mix (``bench/traffic/<mix>.json``);
the configuration names its driver (``bench/drivers/<driver>.py``, the
entry the window drives); each metric is read by
``bench/metrics/<metric>.py``.  A later cell, configuration, mix or
metric is new files and new entries, with no edit here.

A run: set-up (JAX and the chip, data from the seed, registration,
warm-up of the cell's own shapes), then a window of ``--seconds`` in
which the driver's timed operation runs in a closed loop, then the
device's peak memory, then the program's state is freed and the plain
reference judges what the window produced.  ``--trace 1`` runs the same
window under the profiler and reports the cell's per-layer metrics.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

from bench import reference
from bench.trace import reduce as trace_reduce

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class SpecError(Exception):
    """The cell, its configuration, mix, driver or a metric is not there."""


class NoDevice(Exception):
    """JAX sees no TPU, or fewer chips than the cell asks for."""


def load_json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise SpecError(f"missing {path}") from None


def load_module(path: Path):
    if not path.is_file():
        raise SpecError(f"missing {path}")
    name = "bench_" + "_".join(path.relative_to(path.parents[1]).with_suffix(
        "").parts).replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    root: Path

    def driver(self):
        return load_module(self.root / "bench" / "drivers"
                           / f"{self.config['driver']}.py")


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    work = [w for w in bench["workloads"] if w["name"] == name]
    if not work:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json; have "
                        f"{[w['name'] for w in bench['workloads']]}")
    work = work[0]
    configs = {c["name"]: c for c in bench["configs"]}
    if work["config"] not in configs:
        raise SpecError(f"no configuration {work['config']!r}")
    config = load_json(root / configs[work["config"]]["file"])
    traffic = load_json(root / "bench" / "traffic"
                        / f"{work['traffic']}.json")

    def here(metric: dict) -> bool:
        return name in metric.get("workloads", [name])

    e2e = [m for m in bench["end_to_end"] if here(m)]
    moved = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if here(m) and m["moves"] in moved]
    return Cell(name, int(work["chips"]), config, traffic, e2e, layer, root)


def metric_reader(cell: Cell, metric: str) -> Callable:
    return load_module(cell.root / "bench" / "metrics"
                       / f"{metric}.py").read


@dataclasses.dataclass
class Context:
    """What a driver is given, and what it hands back to the readers."""

    config: dict
    traffic: dict
    seed: int
    trace: bool
    system: str = "program"          # or "control": see bench/control.py
    phases: Dict[str, float] = dataclasses.field(default_factory=dict)
    log: Callable[[str], None] = lambda line: print(line, file=sys.stderr)

    def phase(self, name: str, t0: float) -> float:
        """Record a set-up phase that began at ``t0``; return now."""
        now = time.perf_counter()
        self.phases[name] = self.phases.get(name, 0.0) + now - t0
        return now


@dataclasses.dataclass
class Run:
    """The window's record, as the metric readers see it."""

    setup_s: float
    window_s: float
    latencies: List[float]
    spans: List[dict]
    counters: Dict[str, float]
    trace: Optional[trace_reduce.TraceSummary]
    peaks: dict


def device_info(chips: int) -> dict:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoDevice(f"JAX's first device is on {devices[0].platform!r}, "
                       "not a TPU")
    if len(devices) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX sees "
                       f"{len(devices)}")
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def peaks_for(kind: str, root: Path = ROOT) -> dict:
    table = load_json(root / "bench" / "peaks.json")["devices"]
    if kind not in table:
        raise SpecError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def memory_peak(chips: int) -> int:
    import jax

    peaks = []
    for dev in jax.devices()[:chips]:
        stats = dev.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def run_window(driver, seconds: float, trace: bool):
    """The closed loop; returns (window seconds, latencies, trace dir)."""
    import jax

    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    latencies: List[float] = []
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
        t0 = time.perf_counter()
        deadline = t0 + seconds
        while True:
            latencies.append(driver.step())
            if time.perf_counter() >= deadline:
                break
        window = time.perf_counter() - t0
    if trace:
        jax.profiler.stop_trace()
    return window, latencies, trace_dir


def execute(cell: Cell, seed: int, seconds: float, trace: bool, *,
            t_start: float, require_tpu: bool = True,
            system: str = "program") -> dict:
    """Run one cell; return the result line as a dict (``checks`` last)."""
    ctx = Context(cell.config, cell.traffic, seed, trace, system)
    import jax
    src = str(cell.root / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    try:
        from repro.compile_cache import use_compile_cache
    except ImportError as exc:
        raise SpecError(f"no system under test under {cell.root / 'src'}: "
                        f"{exc}") from None

    use_compile_cache()
    t = ctx.phase("imports", t_start)
    compiles = {"n": 0}

    def on_duration(event: str, duration: float, **kwargs) -> None:
        if event == COMPILE_EVENT:
            compiles["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    if require_tpu:
        device = device_info(cell.chips)
        peaks = peaks_for(device["kind"], cell.root)
    else:
        device = {"platform": jax.devices()[0].platform,
                  "kind": jax.devices()[0].device_kind, "count": 1}
        peaks = {}
    ctx.phase("tpu_start", t)

    driver = cell.driver().build(ctx)
    setup_s = time.perf_counter() - t_start
    before = compiles["n"]
    window, latencies, trace_dir = run_window(driver, seconds, trace)
    in_window = compiles["n"] - before
    summary = None
    if trace_dir is not None:
        try:
            summary = trace_reduce.reduce_dir(trace_dir, driver.SPANS)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
    device["memory_peak_bytes"] = memory_peak(cell.chips) if require_tpu \
        else 0
    t_check = time.perf_counter()
    driver.finish()
    checks = driver.check()
    t_check = time.perf_counter() - t_check

    ctx.log("setup: " + " ".join(f"{k}={v:.3f}s" for k, v in
                                 ctx.phases.items())
            + f" total={setup_s:.3f}s")
    ctx.log(f"window: ops={len(latencies)} seconds={window:.3f} "
            f"compiles_in_window={in_window} check_s={t_check:.3f}")
    run = Run(setup_s, window, latencies, getattr(driver, "spans", []),
              getattr(driver, "counters", {}), summary, peaks)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = metric_reader(cell, m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    line = {"correct": reference.limits_met(checks) and driver.failed == 0,
            "attempted": len(latencies), "failed": driver.failed,
            "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        line["breakdown"] = {
            "device_ops": trace_reduce.top(summary.ops),
            "idle_gaps": trace_reduce.top(summary.idle_by_span)}
        ctx.log("programs: " + json.dumps(trace_reduce.top(summary.programs)))
    line["checks"] = {name: {"value": v, "limit": lim}
                      for name, (v, lim) in checks.items()}
    for name, (v, lim) in checks.items():
        ctx.log(f"check {name}={v} limit={lim}")
    return line


def parse(argv: List[str]) -> argparse.Namespace:
    ap = argparse.ArgumentParser(
        prog="bench/run.py",
        description="Run one benchmark cell and print its result line.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv: List[str], *, t_start: float) -> int:
    args = parse(argv)
    try:
        cell = load_cell(args.workload)
        line = execute(cell, args.seed, args.seconds, bool(args.trace),
                       t_start=t_start)
    except (SpecError, NoDevice) as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 3
    print(json.dumps(line), flush=True)
    return 0
