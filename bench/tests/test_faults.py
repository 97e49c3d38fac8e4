"""A whole run on the CPU, with the timed path broken underneath: each
fault the cells can have must turn ``correct`` false.  The cells run on
one chip, so there is no exchange between chips to leave out, and their
analysis keeps no state from one pane to the next, so none to freeze."""
import numpy as np
import pytest

CELLS = ["synth128.sat"]


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(run_tiny, workload):
    line = run_tiny(workload)
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0


def _half_batch(monkeypatch):
    """Half of each pane left out of the batched window solve."""
    from repro.analysis import dmd
    solve = dmd.batched_window_dmd
    monkeypatch.setattr(dmd, "batched_window_dmd",
                        lambda panes, **kw: solve([list(p)[: len(list(p)) // 2 + 2]
                                                   for p in panes], **kw))


def _value_altered(monkeypatch):
    """One float of every record corrupted where the wire codec decodes it
    (set to 100 times the record's largest magnitude)."""
    from repro.core import records
    decode = records._dequant_rows

    def altered(qb, sb, b, n):
        rows = np.array(decode(qb, sb, b, n))
        rows[:, 0] = 100.0 * np.abs(rows).max(axis=1)
        return rows
    monkeypatch.setattr(records, "_dequant_rows", altered)


def _answer_altered(monkeypatch):
    """Every eigenvalue the window solve returns moved by 0.1%."""
    from repro.analysis import dmd
    solve = dmd.batched_window_dmd
    monkeypatch.setattr(dmd, "batched_window_dmd",
                        lambda panes, **kw: [np.asarray(e) * 1.001
                                             for e in solve(panes, **kw)])


FAULTS = [("synth128.sat", _half_batch), ("synth128.sat", _value_altered),
          ("synth128.sat", _answer_altered)]


@pytest.mark.parametrize("workload,fault", FAULTS,
                         ids=[f"{w}-{f.__name__[1:]}" for w, f in FAULTS])
def test_fault_makes_the_run_incorrect(run_tiny, monkeypatch, workload, fault):
    fault(monkeypatch)
    line = run_tiny(workload)
    assert not line["correct"], line["checks"]
