#!/usr/bin/env python3
"""Readings for the limits of a stage cell's comparison: the program's
numbers, the control's, and those of a fault planted in the reference (each
SGD step on half its batch), on the chip, at the cell's own size, over
several seeds.

    python3 bench/control.py --workload <cell> --seeds 11,12,13

The program's numbers come from its timed path as a run drives it (the
set-up stage, the one a run compares).  The control is the reference put in
the program's place, computed in bfloat16 throughout, the precision below
the configuration's float32.  Each seed prints one JSON line with each set
of numbers; the benchmark's own runs never run this.
"""
from __future__ import annotations

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def readings(name: str, seed: int, devices, cfg=None, workload=None) -> dict:
    import jax.numpy as jnp
    from bench.harness import common
    from bench.harness.cell import Run
    from bench.harness.drivers import stage
    wl = workload or common.workload(name)
    model = common.config_module(wl["config"])
    cfg = cfg or model.CONFIG
    run = Run(name=name, workload=wl, cfg=cfg, model=model, seed=seed,
              seconds=0.0, trace=False, devices=devices,
              started=time.perf_counter())
    t = time.perf_counter()
    rounds = int(wl["check_rounds"])
    clients, sim, first = stage.setup(run)
    side = stage.program_side(first, rounds)
    del sim, first
    gc.collect()
    t_prog = time.perf_counter() - t
    ref, keep = stage.reference_side(cfg, model, clients, run.seeds, rounds)
    t_ref = time.perf_counter() - t - t_prog
    ctl, _ = stage.reference_side(cfg, model, clients, run.seeds, rounds,
                                  dtype=jnp.bfloat16)
    half, _ = stage.reference_side(cfg, model, clients, run.seeds, rounds,
                                   half_batch=True)
    return {"seed": seed, "program": stage.numbers(side, ref, keep),
            "control": stage.numbers(ctl, ref, keep),
            "half_batch": stage.numbers(half, ref, keep), "program_s": t_prog,
            "reference_s": t_ref,
            "worst_leaves": {who: worst_stored(s, ref, keep)
                             for who, s in (("program", side), ("control", ctl))}}


def worst_stored(side, ref, keep, k: int = 4) -> list:
    """The leaves with the largest gaps of the stored models' change."""
    from bench.harness import compare
    worst = {}
    for g, by_shard in enumerate(ref["stored"]):
        for s, by_client in by_shard.items():
            for c, tree in by_client.items():
                gaps = compare.leaf_gaps(
                    compare.leaf_norms(compare.tree_sub(side["stored"][g][s][c],
                                                        side["globals"][g][s])),
                    compare.leaf_norms(compare.tree_sub(tree, ref["globals"][g][s])),
                    keep)
                for leaf, v in gaps.items():
                    if v > worst.get(leaf, (0,))[0]:
                        worst[leaf] = (v, g)
    return sorted(([leaf, v, g] for leaf, (v, g) in worst.items()),
                  key=lambda x: -x[1])[:k]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.harness import common
    chips = next(w["chips"] for w in common.benchmark()["workloads"]
                 if w["name"] == args.workload)
    try:
        devices = common.accelerator(chips)
    except common.NoAccelerator as e:
        print(f"control: {e}", file=sys.stderr)
        sys.exit(2)
    import jax
    from repro.compile_cache import configure_compile_cache
    configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(readings(args.workload, seed, devices)),
              flush=True)


if __name__ == "__main__":
    main()
