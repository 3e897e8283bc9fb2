#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip, and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``.  Its workload
file ``bench/workloads/<cell>.json`` names its configuration
(``bench/configs/<config>.json`` with its reference beside it), its driver
(``bench/harness/drivers/<driver>.py``) and the traffic's parameters; each
per-layer metric is read by ``bench/metrics/<metric>.py``.

With ``--trace 0`` the result's metrics are the cell's end-to-end metrics;
with ``--trace 1`` its per-layer metrics, read from the same window and from
a profiled part after it.  Every run compares what the timed path produced
with the plain reference and prints each compared number beside its limit.
Without an accelerator, or with fewer chips than the cell needs, the run
exits 2 and prints no result.
"""
from __future__ import annotations

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def _die(msg: str, code: int) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def cell_entry(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no cell {name!r} in BENCHMARK.json")


def applies(metric: dict, cell: str, reported: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in reported


def run_cell(name: str, seed: int, seconds: float, trace: bool, devices,
             started: float, cfg: dict = None, workload: dict = None):
    """One run of ``name`` on ``devices``; returns (result dict, checks).
    ``cfg`` and ``workload`` replace the files' contents (tests run tiny
    copies of a cell on the CPU this way)."""
    from bench.harness import common, peaks
    from bench.harness.cell import Run
    bench = common.benchmark()
    wl = workload or common.workload(name)
    model = common.config_module(wl["config"])
    run = Run(name=name, workload=wl, cfg=cfg or model.CONFIG, model=model,
              seed=seed, seconds=seconds, trace=trace, devices=devices,
              started=started)
    if devices[0].platform != "cpu":
        run.peak_flops = peaks.peak(devices[0].device_kind)["flops_per_s"]
    driver = importlib.import_module(f"bench.harness.drivers.{wl['driver']}")
    out = driver.execute(run)

    e2e = {m["name"]: m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]}
    values = dict(out.end_to_end, setup_s=run.setup_s)
    metrics = {}
    if not trace:
        for m in e2e.values():
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        for m in bench["per_layer"]:
            if applies(m, name, set(e2e)):
                value = common.metric_reader(m["name"]).read(out.layer_data)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": run.memory_peak}
    result = {"correct": out.failed == 0 and all(c.ok for c in out.checks),
              "attempted": out.attempted, "failed": out.failed,
              "metrics": metrics, "device": device}
    if trace and out.trace:
        device["busy_s"] = out.trace.get("busy_s")
        device["window_s"] = out.trace.get("window_s")
        result["breakdown"] = out.trace.get("breakdown")
    return result, out.checks


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        _die(f"no program under {ROOT / 'src'}; run from a full checkout", 2)
    if not (ROOT / "bench" / "workloads" / f"{args.workload}.json").is_file():
        _die(f"no workload file for {args.workload!r}", 2)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.harness import common, peaks
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
    try:
        result, checks = run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace), devices, STARTED)
    except Exception:
        traceback.print_exc()
        _die("the run failed", 1)
    common.emit(result, checks)


if __name__ == "__main__":
    main()
