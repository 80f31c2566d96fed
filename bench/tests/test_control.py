"""The control, the reference in bfloat16 in the program's place, comes
out not correct in every cell (test size; ``bench/control.py`` runs it at
the cells' own size on the chip)."""
import pytest


@pytest.mark.parametrize("seed", [7, 2**32 + 9])
@pytest.mark.parametrize("cell", ["static_a0.01", "static_a100"])
def test_control_is_not_correct(run_tiny, cell, seed):
    line = run_tiny(cell, seconds=0.3, seed=seed, system="control")
    assert line["correct"] is False
    assert sum(c["value"] for c in line["checks"].values()) > 0
