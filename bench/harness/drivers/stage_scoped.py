"""Stage training, as ``stage`` runs it, with a traced stage split by the
stage program's layer scopes.

Set-up, the window and the comparison are ``stage.execute`` itself: the same
client data, simulator and compiling first stage, back-to-back ``run_stage``
calls until ``--seconds`` have passed, and the first stage's round globals,
update norms and decoded client models held against the reference.  An
untraced run is nothing more.

A traced run leaves out ``stage``'s own traced part.  After the comparison
it builds the simulator again (``stage.setup``: the same data, seed and first
stage, whose program the compile cache holds), profiles ``trace_stages``
stages with the program's tracer on, reads the trace as a ``LayerTrace``
(``bench/harness/layers.py``) with the stage program's compiled HLO, as
``bench/layers.py`` does, and adds ``layer_data["layers"]``: the device's self
time per traced stage under each scope of ``SCOPES``, innermost first, among
them the model's own layers (``mla.attention``, ``moe.route``,
``moe.experts``, ``moe.shared``, ``lora.adapter``, ``embed.gather``).  The
tokens that the first stage (the run's set-up stage again) routed to each
held expert are kept in ``layer_data["expert_tokens"]``, and the traced
stage's ``moe.expert_tokens`` gauges are logged.
"""
from __future__ import annotations

import functools
import gc
import tempfile
import time

import jax
import numpy as np

from bench.harness import layers, program
from bench.harness.cell import annotate, trace_summary
from bench.harness.common import Outcome
from bench.harness.drivers import stage

# innermost first: a path holds every scope it is nested in
SCOPES = ("lora.adapter", "mla.attention", "moe.route", "moe.experts",
          "moe.shared", "embed.gather") + layers.SCOPES


def expert_tokens(record) -> dict:
    """Mean and max over the held experts (of every MoE layer) of the tokens
    the stage routed to each; empty without held experts."""
    if record.expert_load is None:
        return {}
    per_expert = np.asarray(jax.device_get(record.expert_load))
    return {"mean": float(per_expert.mean()), "max": float(per_expert.max())}


def stage_program_hlo(sim, cfg: dict, clients, record) -> str:
    """The compiled text of the stage program that ``record``'s stage ran,
    lowered again from its argument shapes (a compile-cache hit); a
    program over a frozen base is lowered with the base it was given."""
    fed = cfg["federation"]
    xs, ys = stage.stacked_data(clients, record.plan.shard_clients)
    shape = lambda a: jax.ShapeDtypeStruct(  # noqa: E731
        a.shape, jax.dtypes.canonicalize_dtype(a.dtype))
    w0 = jax.tree.map(shape, record.round_globals[sorted(record.round_globals)[0]][0])
    store = record.store
    enc = np.asarray(store.scheme.encode_matrix(), np.float32)
    prog = sim._get_stage_program(
        fed["local_epochs"], "flat", fed["global_rounds"], encode=True,
        out_dtype=store.slice_dtype, use_kernel=store.use_kernel)
    bound = ()
    if isinstance(prog, functools.partial):
        prog, bound = prog.func, prog.args
    return prog.lower(*bound, w0, shape(xs), shape(ys),
                      shape(enc)).compile().as_text()


def traced(run, sim, clients, stages: int):
    """``stages`` stages under the profiler with the program's tracer on;
    the ``LayerTrace`` with the stage program's scope paths, and the last
    stage's expert gauges."""
    from repro.telemetry import configure, get_tracer
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    with tempfile.TemporaryDirectory(prefix="bench-scoped-") as d:
        configure(enabled=True)
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            # let the device tracer settle: a stage launched at once can lose
            # the start of its program's events
            jax.block_until_ready(jax.numpy.ones(()) + 1)
            time.sleep(1.0)
            for _ in range(stages):
                with annotate("stage"):
                    record = program.run_stage(program.session(sim, run.cfg))
            gauges = {k: v for k, v in get_tracer().metrics.snapshot()
                      .get("gauges", {}).items() if "moe.expert_tokens" in k}
        finally:
            jax.profiler.stop_trace()
            configure(enabled=False)
        tr = layers.LayerTrace.from_dir(d)
    tr.add_hlo(stage_program_hlo(sim, run.cfg, clients, record))
    return tr, gauges


def scope_summary(tr: layers.LayerTrace, stages: int) -> dict:
    """``layers.layer_summary`` under this driver's ``SCOPES``, which it reads
    from its module when called (a scope list given as its argument is a
    ``benchmark`` change; ROADMAP)."""
    accepted, layers.SCOPES = layers.SCOPES, SCOPES
    try:
        return layers.layer_summary(tr, stages)
    finally:
        layers.SCOPES = accepted


def execute(run) -> Outcome:
    trace, run.trace = run.trace, False
    try:
        out = stage.execute(run)
    finally:
        run.trace = trace
    if not trace:
        return out
    gc.collect()
    clients, sim, first = stage.setup(run)
    load = out.layer_data["expert_tokens"] = expert_tokens(first)
    if load:
        run.log(f"set-up stage: tokens a held expert, mean {load['mean']!r} "
                f"max {load['max']!r}")
    stages = int(run.workload["trace_stages"])
    tr, gauges = traced(run, sim, clients, stages)
    out.trace = out.layer_data["trace"] = trace_summary(tr)
    out.layer_data["layers"] = scope_summary(tr, stages)
    run.log(f"traced part done; gauges {gauges}")
    return out
