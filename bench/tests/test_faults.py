"""A run whose timed path is broken underneath comes out not correct.

Each cell's run is driven past the look for a chip, at test size, with
one fault planted in the program: a step that leaves its state unchanged,
half of the batch left out, an answer altered where it is made.  (No
cell spans chips, so there is no exchange between chips to leave out.)
"""
import numpy as np
import pytest


def _static_fault(monkeypatch, kind):
    import repro.core as core

    real = core.sbm_enumerate_planned
    last = {}

    def faulty(subs, upds, **kw):
        pairs, count, stats = real(subs, upds, **kw)
        if kind == "unchanged":
            # hands back the previous audit's answer
            out = last.get("out", (pairs, count, stats))
            last["out"] = (pairs, count, stats)
            return out
        arr = np.asarray(pairs).copy()
        k = int(np.count_nonzero(arr[:, 0] >= 0))
        if kind == "half":
            arr[k // 2:k] = -1
        else:
            arr[0, 1] = (arr[0, 1] + 1) % upds.size
        return arr, count, stats

    monkeypatch.setattr(core, "sbm_enumerate_planned", faulty)


@pytest.mark.parametrize("kind", ["unchanged", "half", "altered"])
@pytest.mark.parametrize("cell", ["static_a0.01", "static_a100"])
def test_static_fault_is_not_correct(run_tiny, monkeypatch, cell, kind):
    _static_fault(monkeypatch, kind)
    line = run_tiny(cell, seconds=0.3)
    assert line["correct"] is False, line["checks"]


@pytest.mark.parametrize("cell", ["static_a0.01", "static_a100"])
def test_sound_run_is_correct(run_tiny, cell):
    line = run_tiny(cell, seconds=0.3)
    assert line["correct"] is True, line["checks"]
    assert all(c["value"] == 0 for c in line["checks"].values())
