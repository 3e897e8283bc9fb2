"""Faults planted in the program's timed path, for the tests that see
``correct`` come out false: a step that returns its state unchanged, half of
each batch left out (the mean taken over the rest), and an answer altered
where it is produced."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _half_batch(monkeypatch):
    from repro.fl.tasks import ClassificationTask, GenerationTask
    for cls in (ClassificationTask, GenerationTask):
        orig = cls.make_batch

        def half(self, x, y, orig=orig):
            n = x.shape[0] // 2
            return orig(self, x[:n], y[:n])
        monkeypatch.setattr(cls, "make_batch", half)


def _stage_program(monkeypatch, alter):
    from repro.fl.simulator import FLSimulator
    orig = FLSimulator._get_stage_program

    def broken(self, *a, **k):
        prog = orig(self, *a, **k)
        return lambda *args: alter(*prog(*args))
    monkeypatch.setattr(FLSimulator, "_get_stage_program", broken)


def _unchanged(final, round_in, hist, norms):
    round_in = jax.tree.map(lambda r: jnp.broadcast_to(r[0], r.shape), round_in)
    return jax.tree.map(lambda r: r[0], round_in), round_in, hist, norms


def _slices_altered(final, round_in, hist, norms):
    return final, round_in, hist * 1.5, norms


STAGE_FAULTS = {
    "unchanged": lambda mp: _stage_program(mp, _unchanged),
    "half_batch": _half_batch,
    "answer_altered": lambda mp: _stage_program(mp, _slices_altered),
}
