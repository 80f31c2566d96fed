"""Window seconds over audits completed."""


def read(run):
    return run.window_s / len(run.latencies)
