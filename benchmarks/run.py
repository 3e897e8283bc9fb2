"""Benchmark driver — one module per paper table/figure. Prints
``name,us_per_call,derived`` CSV rows; ``--json-dir`` additionally writes one
machine-readable ``BENCH_<suite>.json`` per suite (the suite's rows plus any
session/unlearn trajectories collected via ``common.collect_report``).

    PYTHONPATH=src python -m benchmarks.run [--only fig3,...] [--full]
        [--fast] [--json-dir out/]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _parse_row(row: str) -> dict:
    name, us, derived = row.split(",", 2)
    return {"name": name, "us_per_call": float(us), "derived": derived}


def main(argv=None) -> None:
    sys.path.insert(0, "src")
    from repro.compile_cache import configure_compile_cache
    print(f"# compile cache: {configure_compile_cache()}", flush=True)
    from benchmarks import (fig3_single_request, fig4_concurrent, fig5_storage,
                            fig6_batched_unlearn, fig7_service, fig8_faults,
                            fig9_durability, fig10_telemetry, fig11_tiering,
                            kernels_bench, table1_f1_time, theory_check,
                            verify_bench)
    from benchmarks import common
    from benchmarks.common import Scale, emit

    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: fig3,fig4,fig5,fig6,fig7,fig8,fig9,"
                         "fig10,fig11,table1,verify,theory,kernels")
    ap.add_argument("--full", action="store_true",
                    help="paper-scale (100 clients, G=30, L=10) — slow on CPU")
    ap.add_argument("--fast", action="store_true",
                    help="minimal scale for CI")
    ap.add_argument("--json-dir", default=None,
                    help="write BENCH_<suite>.json per suite to this directory")
    ap.add_argument("--trace-summary", action="store_true",
                    help="run the suites under the span tracer and print the "
                         "aggregated span tree at the end")
    args = ap.parse_args(argv)

    if args.trace_summary:
        from repro.telemetry import configure
        configure(enabled=True)

    sc = Scale.full() if args.full else Scale()
    if args.fast:
        sc = Scale(num_clients=8, clients_per_round=8, num_shards=2,
                   local_epochs=2, global_rounds=2, samples_per_client=40,
                   image_size=12, seq_len=32, test_n=120)

    suites = {
        "theory": theory_check.run,
        "kernels": kernels_bench.run,
        "fig3": fig3_single_request.run,
        "fig4": fig4_concurrent.run,
        "fig5": fig5_storage.run,
        "fig6": fig6_batched_unlearn.run,
        "fig7": fig7_service.run,
        "fig8": fig8_faults.run,
        "fig9": fig9_durability.run,
        "fig10": fig10_telemetry.run,
        "fig11": fig11_tiering.run,
        "table1": table1_f1_time.run,
        "verify": verify_bench.run,
    }
    only = args.only.split(",") if args.only else list(suites)
    if args.json_dir:
        os.makedirs(args.json_dir, exist_ok=True)
    t0 = time.time()
    for name in only:
        print(f"# --- {name} ---", flush=True)
        rows_before = len(common.ROWS)
        reports_before = set(common.REPORTS)
        t_suite = time.time()
        suites[name](sc)
        if args.json_dir:
            payload = {
                "suite": name,
                "wall_s": time.time() - t_suite,
                "scale": vars(sc),
                "rows": [_parse_row(r) for r in common.ROWS[rows_before:]],
                "reports": {k: v for k, v in common.REPORTS.items()
                            if k not in reports_before},
            }
            path = os.path.join(args.json_dir, f"BENCH_{name}.json")
            with open(path, "w") as f:
                json.dump(payload, f, indent=2)
            print(f"# wrote {path}", flush=True)
    emit("bench_total_wall", (time.time() - t0) * 1e6, f"suites={len(only)}")
    if args.trace_summary:
        from repro.telemetry import get_tracer, render_tree
        print("# --- trace summary ---", flush=True)
        print(render_tree(get_tracer()), flush=True)


if __name__ == "__main__":
    main()
