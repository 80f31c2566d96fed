"""Device time of the audit's cross-chip collectives (all-to-all,
all-gather, all-reduce, reduce-scatter), averaged over the chips, per
audit, from the trace's operations."""
from bench.metrics._collectives import seconds_per_chip


def read(run):
    sec = seconds_per_chip(run)
    if sec is None:
        return None
    return 1000.0 * sec / len(run.latencies)
