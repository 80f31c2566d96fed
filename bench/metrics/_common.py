"""Helpers the metric readers share."""
from __future__ import annotations

from typing import Optional


def span_mean_ms(run, name: str) -> Optional[float]:
    """Mean of one span over the window's operations, in milliseconds."""
    values = [s[name] for s in run.spans if name in s]
    if not values:
        return None
    return 1000.0 * sum(values) / len(values)


def idle_pct(run) -> Optional[float]:
    """Share of the traced window in which no operation ran on the device."""
    if run.trace is None or run.trace.chips == 0:
        return None
    return 100.0 * run.trace.idle_share
