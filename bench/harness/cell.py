"""One run of one cell: its inputs, its clocks, and the traced part."""
from __future__ import annotations

import sys
import tempfile
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Optional

from bench.harness import common
from bench.harness.trace import Trace

_COMPILES = {"n": 0, "listening": False}


def _count(event, duration, **_):
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILES["n"] += 1


def compiles() -> int:
    """Backend compiles (persistent-cache hits included) so far."""
    if not _COMPILES["listening"]:
        import jax
        jax.monitoring.register_event_duration_secs_listener(_count)
        _COMPILES["listening"] = True
    return _COMPILES["n"]


@dataclass
class Run:
    name: str                   # the cell
    workload: dict              # bench/workloads/<name>.json
    cfg: dict                   # the configuration's JSON, as run
    model: object               # the configuration's reference module
    seed: int
    seconds: float
    trace: bool
    devices: list
    started: float              # perf_counter at process start
    peak_flops: Optional[float] = None
    setup_s: Optional[float] = None
    memory_peak: Optional[int] = None
    window_compiles: Optional[int] = None
    seeds: dict = field(default_factory=dict)

    def __post_init__(self):
        self.seeds = common.subseeds(self.seed, self.cfg.get("checkpoint_seed"))

    def log(self, what: str) -> None:
        print(f"bench: {time.perf_counter() - self.started:9.3f} s  {what}",
              file=sys.stderr, flush=True)

    def open_window(self) -> float:
        """Ends set-up; returns the window's start on ``perf_counter``."""
        now = time.perf_counter()
        self.setup_s = now - self.started
        self.window_compiles = compiles()
        self.log("window opens")
        return now

    def close_window(self) -> None:
        self.memory_peak = common.memory_peak(self.devices)
        self.window_compiles = compiles() - self.window_compiles
        self.log(f"window closed; {self.window_compiles} compile(s) inside it")


@contextmanager
def profiled(found: dict):
    """Profile the body; on exit ``found["trace"]`` holds the reduced
    ``Trace`` (the raw trace is written to a temporary directory and
    removed)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # no event per Python call
    opts.host_tracer_level = 1        # the harness's annotations, no more
    opts.enable_hlo_proto = False
    with tempfile.TemporaryDirectory(prefix="bench-trace-") as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            yield
        finally:
            t = time.perf_counter()
            jax.profiler.stop_trace()
        t_stop = time.perf_counter() - t
        tr = found["trace"] = Trace.from_dir(d)
        print(f"trace: stop {t_stop:.3f} s, read {time.perf_counter() - t - t_stop:.3f} s, "
              f"{sum(len(v) for v in tr.ops.values())} device ops on "
              f"{len(tr.ops)} device(s), {len(tr.annotations)} annotations",
              file=sys.stderr, flush=True)


def annotate(what: str):
    import jax
    return jax.profiler.TraceAnnotation(f"bench.{what}")


def trace_summary(tr: Trace, busy_in=None) -> dict:
    """busy_s / window_s over the traced window, the breakdown, and (when
    ``busy_in`` names an annotation) busy seconds inside its intervals."""
    window = tr.window()
    if window is None:
        return {}
    lo, hi = window
    busy = tr.busy([window])
    out = {"window_s": (hi - lo) / 1e9,
           "busy_s": None if busy is None else busy / 1e9,
           "breakdown": {"device_ops": tr.top_ops(window),
                         "idle_gaps": tr.idle_gaps(window)}}
    if busy_in is not None:
        spans = tr.intervals(f"bench.{busy_in}")
        inside = tr.busy(spans)
        out["in_" + busy_in] = {
            "span_s": sum(b - a for a, b in spans) / 1e9,
            "busy_s": None if inside is None else inside / 1e9}
    return out
