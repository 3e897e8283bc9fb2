"""Tiny copies of the benchmark's cells, for the CPU tests: the models at
their published widths, the federation cut to 8 clients, 4 per stage in 2
shards, 1 local epoch, 3 rounds and 20 examples per client."""
from __future__ import annotations

import copy
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench.harness import common  # noqa: E402


def cell(name: str):
    """(cfg, workload) of a tiny copy of cell ``name``."""
    wl = dict(common.workload(name))
    cfg = copy.deepcopy(common.config_module(wl["config"]).CONFIG)
    cfg["federation"].update(num_clients=8, clients_per_stage=4,
                             num_shards=2, local_epochs=1, global_rounds=3)
    cfg["data"]["samples_per_client"] = 20
    return cfg, wl


def run(name: str, seed: int = 2 ** 33 + 7, trace: bool = False):
    """One run of the tiny cell on the CPU, past the look for a chip."""
    import time

    import jax
    from bench import run as bench_run
    cfg, wl = cell(name)
    return bench_run.run_cell(name, seed, 1.0, trace, jax.devices()[:1],
                              time.perf_counter(), cfg=cfg, workload=wl)
