"""The planned emission (MatchStats ``emit``, ends in a device sync), mean
per audit."""
from bench.metrics._common import span_mean_ms


def read(run):
    return span_mean_ms(run, "emit")
