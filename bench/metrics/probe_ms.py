"""The planner's probe count (MatchStats ``probe``), mean per audit."""
from bench.metrics._common import span_mean_ms


def read(run):
    return span_mean_ms(run, "probe")
