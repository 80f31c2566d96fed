"""The pair buffer pulled to the host and its padding dropped (the
benchmark's own span), mean per audit."""
from bench.metrics._common import span_mean_ms


def read(run):
    return span_mean_ms(run, "d2h")
