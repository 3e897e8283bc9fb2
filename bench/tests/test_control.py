"""The control at a size a test run can hold: the reference computed in
bfloat16, put in the program's place, must fail one of the cell's limits,
while the program on the same seed passes them all."""
from __future__ import annotations

import jax
import pytest

import _tiny
from bench import control


def _fails(numbers: dict, limits: dict) -> list:
    return [k for k, lim in limits.items()
            if not numbers.get(k, float("inf")) <= float(lim)]


@pytest.mark.parametrize("cell", ["nanogpt.stage", "cnn-paper.stage"])
def test_control_fails_and_program_passes(cell):
    cfg, wl = _tiny.cell(cell)
    got = control.readings(cell, 2 ** 32 + 5, jax.devices()[:1],
                           cfg=cfg, workload=wl)
    assert _fails(got["program"], wl["limits"]) == [], got
    assert _fails(got["control"], wl["limits"]), got
