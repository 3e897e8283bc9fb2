"""What every cell shares: finding configurations, workloads and metric
readers by name, seeds, the device, and the result line."""
from __future__ import annotations

import importlib.util
import json
import math
import sys
from dataclasses import dataclass, field
from pathlib import Path
from typing import List, Optional

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _module(path: Path, name: str):
    if not path.is_file():
        raise FileNotFoundError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def workload(name: str) -> dict:
    return load_json(BENCH / "workloads" / f"{name}.json")


def config_module(name: str):
    """The configuration's reference module; ``.CONFIG`` is its JSON."""
    return _module(BENCH / "configs" / f"{name}.py",
                   f"bench_config_{name.replace('-', '_').replace('.', '_')}")


def metric_reader(name: str):
    return _module(BENCH / "metrics" / f"{name}.py",
                   f"bench_metric_{name.replace('-', '_').replace('.', '_')}")


def subseeds(seed: int, checkpoint: Optional[int] = None) -> dict:
    """Independent 31-bit seeds for each use, drawn from ``--seed``.  Given
    the ``checkpoint_seed`` of a configuration that fine-tunes one fixed
    checkpoint, the program's seed (the frozen base, the first adapters and
    the stage plan) is drawn from it instead, as ``--seed checkpoint`` would
    draw it, and only the client data follows ``--seed``."""
    import numpy as np

    def draw(s: int) -> dict:
        words = np.random.SeedSequence(int(s)).generate_state(2)
        return {n: int(w) % (2 ** 31 - 64)
                for n, w in zip(("data", "program"), words)}
    out = draw(seed)
    if checkpoint is not None:
        out["program"] = draw(checkpoint)["program"]
    return out


class NoAccelerator(RuntimeError):
    pass


def accelerator(chips: int):
    """The devices of a run; refuses a CPU or too few chips."""
    import jax
    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise NoAccelerator("JAX finds no accelerator (platform 'cpu'); the "
                            "benchmark measures only on the chip")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX finds "
                            f"{len(devices)}")
    return devices[:chips]


def memory_peak(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


@dataclass
class Check:
    """One number compared with its limit; it passes when value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclass
class Outcome:
    """What a driver hands back to ``run.py``."""
    attempted: int
    failed: int
    end_to_end: dict = field(default_factory=dict)
    layer_data: dict = field(default_factory=dict)
    checks: List[Check] = field(default_factory=list)
    trace: Optional[dict] = None


def emit(result: dict, checks: List[Check]) -> None:
    """Each compared number beside its limit as the last lines on standard
    error, and the result as the last line on standard output, with the
    checks under the last key."""
    for c in checks:
        print(f"check {c.name}: {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr, flush=True)
    result = dict(result)
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    print(json.dumps(result), flush=True)
