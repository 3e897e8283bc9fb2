"""CPU tests of the trace reduction: interval arithmetic on a made trace,
and a trace recorded here, whose harness annotations are found and which,
having no device plane, reads as no device time (never as zero)."""
from __future__ import annotations

import time

import pytest

import _tiny  # noqa: F401  (puts the repo on sys.path)
from bench.harness import trace as T
from bench.harness.cell import annotate, profiled, trace_summary


def made_trace():
    # ns; device busy [0,10) [20,30) [35,60); host: stage [0,40), prep
    # [10,20) inside it, serve [45,100)
    ops = {"/device:TPU:0": [("fusion.1", 0, 10), ("dot.2", 20, 30),
                             ("fusion.1", 35, 60)]}
    notes = [("bench.stage", 0, 40), ("bench.prep", 10, 20),
             ("bench.serve", 45, 100)]
    return T.Trace(ops, notes)


def test_merge_and_overlap():
    assert T.merge([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)]) == [(0, 3), (5, 8)]
    assert T.overlap([(0, 10), (20, 30)], [(5, 25)]) == 10


def test_busy_window_and_intervals():
    tr = made_trace()
    assert tr.window() == (0, 100)
    assert tr.busy([tr.window()]) == 45
    assert tr.busy(tr.intervals("bench.serve")) == 15
    assert tr.busy([(100, 200)]) == 0


def test_top_ops_and_gaps_are_named_by_annotation():
    tr = made_trace()
    assert tr.top_ops((0, 100)) == [["fusion.1", 35e-9], ["dot.2", 10e-9]]
    gaps = tr.idle_gaps((0, 100))
    assert gaps == [["serve", 40e-9], ["prep", 10e-9], ["stage", 5e-9]]


def test_summary_of_made_trace():
    s = trace_summary(made_trace(), busy_in="serve")
    assert s["window_s"] == pytest.approx(100e-9)
    assert s["busy_s"] == pytest.approx(45e-9)
    assert s["in_serve"] == {"span_s": pytest.approx(55e-9),
                             "busy_s": pytest.approx(15e-9)}
    assert len(s["breakdown"]["device_ops"]) == 2


def test_recorded_trace_reads_annotations():
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    found = {}
    with profiled(found):
        for _ in range(2):
            with annotate("serve"):
                f(x).block_until_ready()
            with annotate("wait"):
                time.sleep(0.01)
    tr = found["trace"]
    names = [n for n, _, _ in tr.annotations]
    assert names.count("bench.serve") == 2 and names.count("bench.wait") == 2
    lo, hi = tr.window()
    assert hi - lo >= 0.02e9
    # the CPU has no device plane: no busy time, rather than a zero
    assert tr.busy([tr.window()]) is None
    s = trace_summary(tr, busy_in="serve")
    assert s["busy_s"] is None and s["in_serve"]["busy_s"] is None
