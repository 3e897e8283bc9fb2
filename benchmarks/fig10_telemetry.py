"""Tracer overhead A/B/C on the fig6 stage-engine scenario.

Three configurations of the SAME workload (one coded-store ``stage``-engine
training stage at scale ``sc``, the fig6 steady-state protocol):

* ``off``     — the default ``NULL_TRACER``: every instrumentation site costs
  one ``get_tracer()`` call plus a no-op context manager.  The acceptance
  budget is < 2% over an untraced run; fig10 reports the measured wall so the
  dispatch-budget table (ROADMAP) can carry the real number.
* ``on``      — full span recording (wall + virtual clocks, labels, the
  metrics registry absorbing per-stage StoreStats).
* ``export``  — recording plus a Chrome/Perfetto ``trace.json`` export and
  validation after the timed stages (export cost amortized per stage).

Emits the per-stage median wall for each mode, the relative overheads, the
span count and export size for the traced modes, and restores the disabled
tracer afterwards so later suites see the default.
"""
from __future__ import annotations

import json
import os
import statistics
import tempfile

from benchmarks.common import Scale, build_image_sim, emit, timed

ITERS = 3


def _stage_wall(sc: Scale) -> float:
    """Median wall (us) of a steady-state stage-engine training stage."""
    from repro.fl.experiment import train_stage

    sim, _ = build_image_sim(sc, iid=True)
    train_stage(sim, store_kind="coded", engine="stage")   # warm the jit cache
    walls = []
    for _ in range(ITERS):
        _, us = timed(train_stage, sim, store_kind="coded", engine="stage")
        walls.append(us)
    return statistics.median(walls)


def run(sc: Scale):
    from repro.telemetry import (configure, get_tracer, set_tracer,
                                 to_chrome_trace, validate_chrome_trace,
                                 NULL_TRACER)

    set_tracer(NULL_TRACER)
    off_us = _stage_wall(sc)
    emit("fig10_tracer_off", off_us,
         f"stage engine;coded;G={sc.global_rounds};median_of={ITERS}")

    configure(enabled=True)
    on_us = _stage_wall(sc)
    tr = get_tracer()
    spans = len(tr.all_spans())
    emit("fig10_tracer_on", on_us,
         f"spans={spans};overhead_vs_off={(on_us / off_us - 1) * 100:.2f}pct")

    configure(enabled=True)
    export_us = _stage_wall(sc)
    tr = get_tracer()
    trace = to_chrome_trace(tr)
    errors = validate_chrome_trace(trace)
    payload = json.dumps(trace)
    path = os.path.join(tempfile.gettempdir(), "fig10_trace.json")
    with open(path, "w") as f:
        f.write(payload)
    emit("fig10_tracer_export", export_us,
         f"spans={len(tr.all_spans())};trace_bytes={len(payload)};"
         f"schema_errors={len(errors)};"
         f"overhead_vs_off={(export_us / off_us - 1) * 100:.2f}pct")

    set_tracer(NULL_TRACER)                 # leave later suites untraced


if __name__ == "__main__":  # PYTHONPATH=src python -m benchmarks.fig10_telemetry
    run(Scale())
