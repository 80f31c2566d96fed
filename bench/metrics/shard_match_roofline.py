"""Share of the four chips' HBM roofline of the audits' device work.

The least bytes an audit moves are ``match_roofline``'s, 8·(n + m) + 8·K
(the bounds read once, the pairs written once), spread over the chips:
the least time is those bytes over the chips' summed HBM bandwidth
(``bench/peaks.json``), and the share is that over the average busy time
per chip in the traced window.
"""


def read(run):
    if run.trace is None or run.trace.busy_s <= 0 or not run.peaks:
        return None
    least = run.counters["least_bytes"] / (run.trace.chips
                                           * run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / run.trace.busy_s
