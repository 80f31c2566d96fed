"""Device time of the cross-chip collectives in a traced window."""
from __future__ import annotations

from typing import Optional

KINDS = ("all-to-all", "all-gather", "all-reduce", "reduce-scatter",
         "collective-permute")


def seconds_per_chip(run) -> Optional[float]:
    """Device seconds of the window's collective operations, averaged over
    the chips; None where the trace holds none.  An operation's kind is
    the last word of its name in ``TraceSummary.ops``.

    Only whole, synchronous collectives are read: on a v5e the mesh
    programs compile every collective, the slot marks' reduce-scatter
    included, into one such all-reduce or all-to-all, none fused
    (``tests/test_tpu_compile.py``), so their intervals hold the
    transfers.  An asynchronous collective's transfer runs between its
    ``-start`` and ``-done`` halves, under other operations, and no sum
    of durations gives it: a trace that holds such halves reads nothing.
    """
    if run.trace is None or run.trace.chips == 0:
        return None
    kinds = [(name.rpartition(" ")[2], sec)
             for name, sec in run.trace.ops.items()]
    if any(kind.startswith(KINDS) and kind not in KINDS for kind, _ in kinds):
        return None
    total = sum(sec for kind, sec in kinds if kind in KINDS)
    return total / run.trace.chips if total > 0 else None
