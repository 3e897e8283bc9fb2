"""A whole run of each tiny stage cell on the CPU, past the look for a chip:
sound, it is correct; with the timed path broken underneath (see
``_faults.py``), ``correct`` comes out false."""
from __future__ import annotations

import pytest

import _tiny
from _faults import STAGE_FAULTS

CELLS = ["nanogpt.stage", "cnn-paper.stage"]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_stage_run_is_correct(cell):
    result, checks = _tiny.run(cell)
    assert result["correct"], checks


@pytest.mark.parametrize("fault", sorted(STAGE_FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_stage_fault_is_caught(monkeypatch, cell, fault):
    STAGE_FAULTS[fault](monkeypatch)
    result, checks = _tiny.run(cell)
    assert not result["correct"], checks
