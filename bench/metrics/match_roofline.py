"""Share of the HBM roofline of the audits' device work.

The least bytes an audit moves are its bounds read once and its pairs
written once, 8·(n + m) + 8·K (float32 lo and hi; two int32 per pair),
whatever implements it.  The least time is those bytes over the chip's
HBM bandwidth (``bench/peaks.json``); the share is that over the device's
busy time in the traced window.
"""


def read(run):
    if run.trace is None or run.trace.busy_s <= 0 or not run.peaks:
        return None
    least = run.counters["least_bytes"] / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / run.trace.busy_s
