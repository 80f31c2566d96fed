"""Share of the interconnect's roofline of the audits' collectives.

The bytes are the program's own count (``MatchStats.exchange_bytes``,
from the collectives' shapes, summed over the chips); the least time is
those bytes over the chips' summed interconnect bandwidth
(``bench/interconnect.json``), and the share is that over the
collectives' device time per chip in the traced window.
"""
from bench.metrics._collectives import seconds_per_chip


def read(run):
    sec = seconds_per_chip(run)
    ici = run.counters.get("ici_bytes_per_s")
    if sec is None or not ici or not run.counters.get("exchange_bytes"):
        return None
    least = run.counters["exchange_bytes"] / (run.trace.chips * ici)
    return 100.0 * least / sec
