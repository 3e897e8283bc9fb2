#!/usr/bin/env python3
"""Split a stage cell's idle time and device time by layer, on the chip.

    python3 bench/layers.py --workload nanogpt.stage --seed <n> --seconds <s> [--pairs <k>]

Sets the cell up as ``bench/run.py`` does (client data, simulator, the
compiling first stage), then:

- with ``--pairs k``, k pairs of untraced windows of ``--seconds`` each, one
  with the program's tracer off and one with it on
  (``repro.telemetry.configure``), alternating which runs first: each
  window's ``stage_s``, so the tracer's cost when on;
- one profiled part of the workload's ``trace_stages`` stages with the
  tracer on, reduced by ``bench/harness/layers.py``: ``idle.stage`` as
  ``bench/run.py`` reads it, the numbers of ``layers.METRICS``, the idle
  time by span and the device time by scope.

The last line on standard output is the result as JSON.  Without an
accelerator the run exits 2 and prints no result.
"""
from __future__ import annotations

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def window(run, sim, seconds: float) -> float:
    """Stages back to back until ``seconds`` have passed; wall per stage."""
    from bench.harness import program
    t0 = time.perf_counter()
    stages = 0
    while True:
        program.run_stage(program.session(sim, run.cfg))
        stages += 1
        if time.perf_counter() - t0 >= seconds:
            return (time.perf_counter() - t0) / stages


def stage_program_hlo(sim, cfg: dict, clients, record) -> str:
    """The compiled text of the stage program that ``record``'s stage ran,
    lowered again from its argument shapes (a compile-cache hit)."""
    import jax
    import numpy as np
    from bench.harness.drivers.stage import stacked_data
    from repro.models import init_params
    fed = cfg["federation"]
    xs, ys = stacked_data(clients, record.plan.shard_clients)
    shape = lambda a: jax.ShapeDtypeStruct(  # noqa: E731
        a.shape, jax.dtypes.canonicalize_dtype(a.dtype))
    w0 = jax.eval_shape(lambda: init_params(sim.cfg, jax.random.key(0)))
    store = record.store
    enc = np.asarray(store.scheme.encode_matrix(), np.float32)
    prog = sim._get_stage_program(
        fed["local_epochs"], "flat", fed["global_rounds"], encode=True,
        out_dtype=store.slice_dtype, use_kernel=store.use_kernel)
    return prog.lower(w0, shape(xs), shape(ys), shape(enc)).compile().as_text()


def traced(run, sim, clients, stages: int):
    """``stages`` stages under the profiler with the program's tracer on;
    the reduced ``LayerTrace``, with the stage program's scope paths."""
    import jax
    from bench.harness import program
    from bench.harness.cell import annotate
    from bench.harness.layers import LayerTrace
    from repro.telemetry import configure
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    with tempfile.TemporaryDirectory(prefix="bench-layers-") as d:
        configure(enabled=True)
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            # let the device tracer settle first: a stage launched at once can
            # lose the start of its program's events (seen on a TPU v5e)
            jax.block_until_ready(jax.numpy.ones(()) + 1)
            time.sleep(1.0)
            for _ in range(stages):
                with annotate("stage"):
                    record = program.run_stage(program.session(sim, run.cfg))
        finally:
            jax.profiler.stop_trace()
            configure(enabled=False)
        tr = LayerTrace.from_dir(d)
    tr.add_hlo(stage_program_hlo(sim, run.cfg, clients, record))
    return tr


def timeline(tr) -> dict:
    """Each span's and each stage program execution's [start, end] in
    seconds from the traced window's start: where the host was while the
    device ran the program."""
    from bench.harness.layers import PROGRAM
    window = tr.window()
    if window is None:
        return {}
    rel = lambda a, b: [(a - window[0]) / 1e9, (b - window[0]) / 1e9]  # noqa: E731
    out = {}
    for name, a, b in tr.spans:
        out.setdefault(name, []).append(rel(a, b))
    out[PROGRAM] = [rel(a, b) for runs in tr.modules.values()
                    for m, a, b in runs if m == PROGRAM]
    return out


def run_layers(name: str, seed: int, seconds: float, pairs: int, devices,
               started: float, cfg: dict = None, workload: dict = None) -> dict:
    """One run of ``name`` on ``devices``; returns the result dict.
    ``cfg`` and ``workload`` replace the files' contents (tests run tiny
    copies of a cell on the CPU this way)."""
    from bench.harness import common
    from bench.harness.cell import Run, trace_summary
    from bench.harness.drivers import stage as stage_driver
    from bench.harness.layers import METRICS, UNSCOPED, layer_summary
    from repro.telemetry import configure
    wl = workload or common.workload(name)
    model = common.config_module(wl["config"])
    run = Run(name=name, workload=wl, cfg=cfg or model.CONFIG, model=model,
              seed=seed, seconds=seconds, trace=True, devices=devices,
              started=started)
    clients, sim, _ = stage_driver.setup(run)
    setup_s = time.perf_counter() - started
    cost = {"off": [], "on": []}
    for i in range(pairs):
        for on in ((False, True) if i % 2 == 0 else (True, False)):
            configure(enabled=on)
            try:
                cost["on" if on else "off"].append(window(run, sim, seconds))
            finally:
                configure(enabled=False)
        run.log(f"pair {i}: stage_s off {cost['off'][-1]!r}, on {cost['on'][-1]!r}")
    stages = int(wl["trace_stages"])
    tr = traced(run, sim, clients, stages)
    run.log("traced part reduced")
    base = trace_summary(tr)
    summary = layer_summary(tr, stages)
    busy, scopes = summary.get("busy_s"), summary.get("scope_s")
    d0 = devices[0]
    return {"device": {"platform": d0.platform, "kind": d0.device_kind,
                       "count": len(devices)},
            "setup_s": setup_s, "stage_s": cost,
            "idle.stage": None if base.get("busy_s") is None
            else 100.0 * (1.0 - base["busy_s"] / base["window_s"]),
            "metrics": {k: read(summary) for k, read in METRICS.items()},
            "unscoped_share": None if not busy or scopes is None
            else scopes[UNSCOPED] / busy,
            "summary": summary,
            "timeline_s": timeline(tr),
            "spans": sorted({n for n, _, _ in tr.spans})}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--pairs", type=int, default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.harness import common, peaks
    from bench.run import _die, cell_entry
    chips = cell_entry(common.benchmark(), args.workload)["chips"]
    try:
        devices = common.accelerator(chips)
        peaks.peak(devices[0].device_kind)
    except (common.NoAccelerator, peaks.UnknownDevice) as e:
        _die(str(e), 2)
    import jax
    from repro.compile_cache import configure_compile_cache
    configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    result = run_layers(args.workload, args.seed, args.seconds, args.pairs,
                        devices, STARTED)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
