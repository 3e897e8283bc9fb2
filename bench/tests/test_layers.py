"""CPU tests of the per-layer reduction (``bench/harness/layers.py``): idle
time split by the stage program's executions and the innermost span, device
time by scope, both summing to what ``trace.py`` reads; the reading of a made
``.xplane.pb``; scope paths from HLO text; and readers that return nothing,
never zero, where the trace has no device plane or the program no spans,
scopes or ``jit_stage_program``."""
from __future__ import annotations

import time

import pytest

import _tiny  # noqa: F401  (puts the repo on sys.path)
from bench.harness import layers as L
from bench.harness.trace import Trace

TRAIN = "jit(stage_program)/while/body/vmap(vmap(fl.local_train))/dot_general"
AGG = "jit(stage_program)/while/body/vmap(fl.aggregate)/div"
ENC = "jit(stage_program)/coding.encode/dot_general"


def made_trace():
    # ns; one device.  jit_other runs [0,5); jit_stage_program [10,60) and
    # [70,100).  Ops: copy.1 [0,5) (eager); fusion.1 [10,20) train; while.2
    # [20,40) (a loop) holding fusion.3 [22,30) train and fusion.4 [32,38)
    # aggregate; fusion.5 [45,55) encode; copy.6 [70,80) unscoped;
    # fusion.1 [85,95) train.  Host: bench.stage [0,120); repro.stage.plan
    # [0,8), repro.xla.stage_program [8,10), repro.stage.collect [100,120).
    dev = "/device:TPU:0"
    ops = [("copy.1", 0, 5), ("fusion.1", 10, 20), ("while.2", 20, 40),
           ("fusion.3", 22, 30), ("fusion.4", 32, 38), ("fusion.5", 45, 55),
           ("copy.6", 70, 80), ("fusion.1", 85, 95)]
    prog = L.PROGRAM
    where = ["jit_other"] + [prog] * 7
    paths = {(prog, "fusion.1"): TRAIN, (prog, "fusion.3"): TRAIN,
             (prog, "fusion.4"): AGG, (prog, "fusion.5"): ENC,
             (prog, "copy.6"): "jit(stage_program)/copy",
             (prog, "while.2"): "jit(stage_program)/while"}
    return L.LayerTrace(
        ops={dev: ops}, annotations=[("bench.stage", 0, 120)],
        spans=[("repro.stage.plan", 0, 8), ("repro.xla.stage_program", 8, 10),
               ("repro.stage.collect", 100, 120)],
        modules={dev: [("jit_other", 0, 5), (prog, 10, 60), (prog, 70, 100)]},
        op_modules={dev: where}, paths=paths)


def test_subtract_and_hlo_paths():
    assert L.subtract([(0, 10), (20, 30)], [(2, 4), (8, 22), (29, 40)]) == \
        [(0, 2), (4, 8), (22, 29)]
    assert L.subtract([(0, 10)], []) == [(0, 10)]
    text = "\n".join([
        "HloModule jit_stage_program, is_scheduled=true",
        "ENTRY %main.1 (x: f32[2]) -> f32[2] {",
        f'  %fusion.3 = f32[2]{{0}} fusion(%x), kind=kLoop, metadata={{op_name="{TRAIN}" source_file="a.py"}}',
        f'  ROOT copy.6 = f32[2]{{0}} copy(%fusion.3), metadata={{op_name="{AGG}"}}',
        "  %x = f32[2]{0} parameter(0)",
        "}"])
    assert L.hlo_paths(text) == {"fusion.3": TRAIN, "copy.6": AGG}
    # a fusion without op_name takes its (nested) fused computation's root's;
    # a copy inside a loop's body takes the loop's
    nested = "\n".join([
        "HloModule jit_stage_program",
        "%fused_inner (p: f32[2]) -> f32[2] {",
        "  %p = f32[2]{0} parameter(0)",
        f'  ROOT %mul.1 = f32[2]{{0}} multiply(%p, %p), metadata={{op_name="{AGG}"}}',
        "}",
        "%fused_outer (q: f32[2]) -> f32[2] {",
        "  %q = f32[2]{0} parameter(0)",
        "  ROOT %fusion.9 = f32[2]{0} fusion(%q), kind=kLoop, calls=%fused_inner",
        "}",
        "%body (t: f32[2]) -> f32[2] {",
        "  %t = f32[2]{0} parameter(0)",
        "  %copy.7 = f32[2]{0} copy(%t)",
        "  ROOT %fusion.8 = f32[2]{0} fusion(%copy.7), kind=kCustom, calls=%fused_outer",
        "}",
        "ENTRY %main.2 (x: f32[2]) -> f32[2] {",
        "  %x = f32[2]{0} parameter(0)",
        f'  ROOT %while.2 = f32[2]{{0}} while(%x), condition=%cond, body=%body, metadata={{op_name="{TRAIN}"}}',
        "}"])
    paths = L.hlo_paths(nested)
    assert paths["fusion.8"] == paths["fusion.9"] == AGG
    assert paths["copy.7"] == paths["while.2"] == TRAIN
    assert "x" not in paths
    tr = L.LayerTrace()
    tr.add_hlo(text)
    assert tr.paths == {(L.PROGRAM, "fusion.3"): TRAIN,
                        (L.PROGRAM, "copy.6"): AGG}


def test_module_intervals_and_idle_by_span():
    tr = made_trace()
    assert tr.module_intervals("/device:TPU:0") == [(10, 60), (70, 100)]
    idle = tr.idle_by_span(tr.window())
    # idle [5,10) under stage.plan, [60,70) under bench.stage only,
    # [100,120) under stage.collect; [40,45) [55,60) [80,85) [95,100)
    # inside the program's executions
    assert idle == {L.IN_PROGRAM: 20, "repro.stage.plan": 5,
                    "bench.stage": 10, "repro.stage.collect": 20}
    lo, hi = tr.window()
    assert sum(idle.values()) == (hi - lo) - tr.busy([tr.window()])


def test_scope_time_sums_to_busy():
    tr = made_trace()
    scopes = tr.scope_time(tr.window())
    # the round loop's own 6 ns, copy.1 and copy.6 are unscoped
    assert scopes == {"fl.local_train": 28, "fl.aggregate": 6,
                      "coding.encode": 10, L.UNSCOPED: 21}
    assert sum(scopes.values()) == tr.busy([tr.window()]) == 65
    assert tr.loop_time(tr.window()) == 6      # while.2's own time
    assert tr.scope_time((30, 60)) == {"fl.aggregate": 6, "coding.encode": 10,
                                       L.UNSCOPED: 4}
    # a loop inside a scope: its own time between its body's operations
    # counts in that scope
    tr.paths[(L.PROGRAM, "while.2")] = TRAIN
    assert tr.scope_time(tr.window()) == {"fl.local_train": 34,
                                          "fl.aggregate": 6,
                                          "coding.encode": 10, L.UNSCOPED: 15}


def test_summary_and_readers_of_made_trace():
    d = L.layer_summary(made_trace(), stages=2)
    got = {k: read(d) for k, read in L.METRICS.items()}
    assert got == pytest.approx({"idle_host.stage": 100 * 35 / 120,
                                 "idle_program.stage": 100 * 20 / 120,
                                 "train_ms.stage": 28e-9 * 1e3 / 2,
                                 "aggregate_ms.stage": 6e-9 * 1e3 / 2,
                                 "encode_ms.stage": 10e-9 * 1e3 / 2})
    idle_stage = 100 * (1 - d["busy_s"] / d["window_s"])
    assert got["idle_host.stage"] + got["idle_program.stage"] == \
        pytest.approx(idle_stage)


def test_readers_give_nothing_for_a_program_without_names():
    # the program before its spans, scopes and named stage program: the
    # module is jit_program and no op carries a path
    tr = made_trace()
    tr.modules = {k: [("jit_program", a, b) for _, a, b in v]
                  for k, v in tr.modules.items()}
    tr.paths = {}
    d = L.layer_summary(tr, stages=1)
    assert d["idle_by_span"] is None and set(d["scope_s"]) == {L.UNSCOPED}
    assert {k: read(d) for k, read in L.METRICS.items()} == \
        dict.fromkeys(L.METRICS)


XSPACE = '''
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000 }
    events { metadata_id: 2 offset_ps: 10000 duration_ps: 50000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 5000 }
    events { metadata_id: 4 offset_ps: 10000 duration_ps: 10000 }
    events { metadata_id: 3 offset_ps: 30000 duration_ps: 5000 }
    events { metadata_id: 4 offset_ps: 40000 duration_ps: 10000 }
    events { metadata_id: 3 offset_ps: 70000 duration_ps: 5000 } }
  event_metadata { key: 1 value { id: 1 name: "jit_other(3)" } }
  event_metadata { key: 2 value { id: 2 name: "jit_stage_program(7)" } }
  event_metadata { key: 3 value { id: 3 name: "%copy.1 = f32[2]{0} copy(x)" } }
  event_metadata { key: 4 value { id: 4 name: "%fusion.1 = f32[2]{0} fusion(x)" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 80000 }
    events { metadata_id: 2 offset_ps: 5000 duration_ps: 5000 }
    events { metadata_id: 3 offset_ps: 6000 duration_ps: 1000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.stage" } }
  event_metadata { key: 2 value { id: 2 name: "repro.stage.data" } }
  event_metadata { key: 3 value { id: 3 name: "other" } }
}
'''


def test_reads_a_made_xplane(tmp_path):
    from jax.profiler import ProfileData
    path = tmp_path / "made.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    tr = L.LayerTrace.from_file(str(path))
    base = Trace.from_file(str(path))
    assert tr.ops == base.ops and tr.annotations == base.annotations
    dev = "/device:TPU:0"
    assert tr.modules[dev] == [("jit_other", 1000, 1005),
                               (L.PROGRAM, 1010, 1060)]
    # copy.1 at 1030 runs inside the program, at 1070 outside every module
    assert tr.op_modules[dev] == ["jit_other", L.PROGRAM, L.PROGRAM,
                                  L.PROGRAM, None]
    assert tr.paths == {}
    tr.add_hlo("HloModule jit_stage_program, is_scheduled=true\n"
               "ENTRY %main (x: f32[2]) -> f32[2] {\n"
               f'  ROOT %fusion.1 = f32[2]{{0}} fusion(%x), metadata={{op_name="{TRAIN}"}}\n'
               "}")
    assert tr.paths == {(L.PROGRAM, "fusion.1"): TRAIN}
    assert tr.spans == [("repro.stage.data", 1005, 1010)]
    scopes = tr.scope_time(tr.window())
    assert scopes == {"fl.local_train": 20, L.UNSCOPED: 15}
    assert tr.idle_by_span(tr.window()) == {
        L.IN_PROGRAM: 25, "repro.stage.data": 5, "bench.stage": 15}


def test_stage_program_hlo_names_each_scope():
    from bench import layers as script
    from bench.harness import common, program
    cfg, wl = _tiny.cell("nanogpt.stage")
    model = common.config_module(wl["config"])
    clients = model.make_clients(1, cfg["federation"]["num_clients"],
                                 cfg["data"]["samples_per_client"])
    sim = program.simulator(cfg, 2, clients)
    record = program.run_stage(program.session(sim, cfg))
    tr = L.LayerTrace()
    tr.add_hlo(script.stage_program_hlo(sim, cfg, clients, record))
    assert {mod for mod, _ in tr.paths} == {L.PROGRAM}
    assert {L.scope_of(p) for p in tr.paths.values()} == set(L.SCOPES) | {L.UNSCOPED}


def test_recorded_cpu_run_reads_nothing_without_a_device_plane():
    import jax
    from bench import layers as script
    cfg, wl = _tiny.cell("nanogpt.stage")
    out = script.run_layers("nanogpt.stage", 2 ** 33 + 11, 0.2, 1,
                            jax.devices()[:1], time.perf_counter(),
                            cfg=cfg, workload=wl)
    assert set(out["spans"]) == {
        "repro.session.stage", "repro.stage.train", "repro.stage.plan",
        "repro.stage.data", "repro.xla.stage_program", "repro.store.put_stage",
        "repro.stage.collect"}
    assert out["metrics"] == dict.fromkeys(L.METRICS)
    assert out["idle.stage"] is None and out["unscoped_share"] is None
    assert out["timeline_s"][L.PROGRAM] == []
    assert len(out["timeline_s"]["repro.stage.collect"]) == 1
    assert len(out["stage_s"]["off"]) == len(out["stage_s"]["on"]) == 1
