"""The control must come out not correct at the committed limits.  On the
CPU the program's precision setting changes nothing, so its stand-in, the
reference one precision step down in the analysis' place, runs here."""
import pytest

from test_faults import CELLS


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(run_tiny, workload):
    line = run_tiny(workload, control=True)
    assert not line["correct"], line["checks"]
    check = line["checks"]["gap_ratio_median"]
    assert check["value"] == 1.0 > check["limit"]
