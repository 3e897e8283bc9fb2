"""CPU tests of the ``moonlight.lora.stage`` cell at a small size: its
reference against the program (adapters, loss and gradients, at
``highest``), a whole run of a tiny copy through the ``stage_scoped`` driver
(correct when sound, caught with half of each batch left out), the stage
program's scopes in its HLO, the split of a made trace by those scopes, and
the operation count by hand at the configuration's own size."""
from __future__ import annotations

import dataclasses
import time
import types

import jax
import numpy as np
import pytest

import _tiny
from _faults import STAGE_FAULTS
from bench.harness import common
from bench.harness import layers as L
from bench.harness.data import char_clients
from bench.harness.drivers import stage_scoped

CELL = "moonlight.lora.stage"
CONFIG = "moonlight-16b-a3b-fedlora"
SEQ = 32
# the reference's sizes, read from the program's configuration
_KEYS = ("num_layers", "first_dense_layers", "d_model", "num_heads",
         "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
         "d_ff", "moe_d_ff", "moe_shared_d_ff", "num_experts",
         "experts_per_token", "experts_held", "moe_routed_scale",
         "vocab_size", "rope_theta", "norm_eps", "lora_rank", "lora_alpha")


def _tiny_program():
    """The family's CPU-size block with a 512-id slice (no head padding)
    and a bfloat16 base, as the cell holds it."""
    from repro.fl.families import get_model_family
    return dataclasses.replace(get_model_family("moonlight").build(None),
                               vocab_size=512, num_layers=3,
                               param_dtype="bfloat16")


def _reference(model: dict):
    ref = common.config_module(CONFIG).Reference(model, SEQ)
    mod = types.SimpleNamespace(init=ref.init, loss=ref.loss,
                                flops_per_example=ref.flops_per_example)
    return ref, mod


def _tiny_cell(monkeypatch):
    """(cfg, workload) of a tiny copy of the cell, with the program's
    configuration and the reference module at the tiny size."""
    import repro.configs
    tiny = _tiny_program()
    cfg, wl = _tiny.cell(CELL)
    model = {k: getattr(tiny, k) for k in _KEYS}
    model.update(layer_pattern=list(tiny.layer_pattern), padded_vocab=512)
    cfg["model"] = model
    cfg["data"].update(seq_len=SEQ, samples_per_client=4)
    real = repro.configs.get_config
    monkeypatch.setattr(repro.configs, "get_config",
                        lambda name: tiny if name == CONFIG else real(name))
    _, mod = _reference(model)
    mod.CONFIG = cfg
    mod.make_clients = lambda seed, n, samples: char_clients(
        seed, n, samples, SEQ, 512, n_words=300)
    real_module = common.config_module
    monkeypatch.setattr(common, "config_module",
                        lambda name: mod if name == CONFIG else real_module(name))
    return cfg, wl


def test_reference_is_the_program():
    """Same adapters from a seed, and the same loss and adapter gradients
    to float32 round-off, at ``highest``; the seed is the program's seed of
    a run, drawn from the configuration's ``checkpoint_seed``."""
    from repro.models import init_adapters, init_params, loss_fn, merge_adapters
    tiny = _tiny_program()
    model = {k: getattr(tiny, k) for k in _KEYS}
    model["padded_vocab"] = 512
    ref, _ = _reference(model)
    checkpoint = common.config_module(CONFIG).CONFIG["checkpoint_seed"]
    seed = common.subseeds(2 ** 31 + 11, checkpoint)["program"]
    ours, theirs = ref.init(seed), init_adapters(tiny, jax.random.key(seed))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    base = init_params(tiny, jax.random.key(seed))
    rng = np.random.default_rng(0)
    x, y = (rng.integers(0, 512, (2, SEQ)).astype(np.int32) for _ in range(2))
    lf = loss_fn(tiny)
    program = lambda w: lf(merge_adapters(base, w),  # noqa: E731
                           {"tokens": x, "labels": y})[0]
    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(program)(theirs)
        lr, gr = jax.value_and_grad(ref.loss)(ours, x, y)
    np.testing.assert_allclose(float(lp), float(lr), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        scale = float(np.max(np.abs(np.asarray(b))))
        assert scale > 0
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5 * scale, rtol=0)


@pytest.mark.parametrize("checkpoint", [True, False])
def test_sound_tiny_run_is_correct(monkeypatch, checkpoint):
    """Correct on the configuration's fixed checkpoint, and on a checkpoint
    drawn from ``--seed`` as a configuration without one draws it."""
    from bench import run as bench_run
    cfg, wl = _tiny_cell(monkeypatch)
    assert "checkpoint_seed" in cfg
    if not checkpoint:
        del cfg["checkpoint_seed"]
    result, checks = bench_run.run_cell(CELL, 2 ** 33 + 7, 0.5, False,
                                        jax.devices()[:1], time.perf_counter(),
                                        cfg=cfg, workload=wl)
    assert result["correct"], checks
    assert result["metrics"]["stage_s"]["value"] > 0


def test_checkpoint_seed_fixes_the_base_and_first_adapters(monkeypatch):
    """Two ``--seed`` values over a configuration with ``checkpoint_seed``:
    the same program seed (so the same stage plan), frozen base and first
    adapters, and different client data."""
    from bench.harness import program
    from bench.harness.cell import Run
    cfg, wl = _tiny_cell(monkeypatch)
    mod = common.config_module(CONFIG)
    runs = [Run(name=CELL, workload=wl, cfg=cfg, model=mod, seed=seed,
                seconds=0.5, trace=False, devices=jax.devices()[:1],
                started=time.perf_counter()) for seed in (2 ** 33 + 7, 12)]
    assert runs[0].seeds["program"] == runs[1].seeds["program"]
    assert runs[0].seeds["data"] != runs[1].seeds["data"]
    fed = cfg["federation"]
    clients = [mod.make_clients(r.seeds["data"], fed["num_clients"],
                                cfg["data"]["samples_per_client"]) for r in runs]
    assert any(not np.array_equal(clients[0][c][0], clients[1][c][0])
               for c in clients[0])
    sims = [program.simulator(cfg, r.seeds["program"], cs)
            for r, cs in zip(runs, clients)]
    firsts = [s.init_params(jax.random.key(s.seed)) for s in sims]
    for trees in ([s.base for s in sims], firsts):
        assert jax.tree.structure(trees[0]) == jax.tree.structure(trees[1])
        for x, y in zip(*map(jax.tree.leaves, trees)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_half_batch_is_caught(monkeypatch):
    from bench import run as bench_run
    cfg, wl = _tiny_cell(monkeypatch)
    STAGE_FAULTS["half_batch"](monkeypatch)
    result, checks = bench_run.run_cell(CELL, 2 ** 33 + 7, 0.5, False,
                                        jax.devices()[:1], time.perf_counter(),
                                        cfg=cfg, workload=wl)
    assert not result["correct"], checks


def _shared_experts_left_out(monkeypatch):
    """A fault in the MoE layer: the program's MoE layers run without their
    shared experts (the reference keeps them)."""
    from repro.models import transformer
    held = transformer.apply_moe_held

    def without_shared(p, x, cfg, first_expert=0):
        return held({k: v for k, v in p.items() if k != "shared"}, x, cfg,
                    first_expert)
    monkeypatch.setattr(transformer, "apply_moe_held", without_shared)


def test_shared_experts_left_out_is_caught(monkeypatch):
    from bench import run as bench_run
    cfg, wl = _tiny_cell(monkeypatch)
    _shared_experts_left_out(monkeypatch)
    result, checks = bench_run.run_cell(CELL, 2 ** 33 + 7, 0.5, False,
                                        jax.devices()[:1], time.perf_counter(),
                                        cfg=cfg, workload=wl)
    assert not result["correct"], checks
    assert "change" in {c.name for c in checks if not c.ok}


def test_traced_tiny_run_reads_nothing_on_the_cpu(monkeypatch):
    """The CPU trace has no device plane: the scoped readers give nothing,
    and never raise."""
    from bench import run as bench_run
    cfg, wl = _tiny_cell(monkeypatch)
    result, _ = bench_run.run_cell(CELL, 2 ** 33 + 9, 0.2, True,
                                   jax.devices()[:1], time.perf_counter(),
                                   cfg=cfg, workload=wl)
    for name in ("mla_ms.stage", "moe_ms.stage", "lora_ms.stage",
                 "idle.stage", "mfu.stage"):
        assert name not in result["metrics"]


def test_stage_program_hlo_names_each_model_scope(monkeypatch):
    from bench.harness import program
    cfg, _ = _tiny_cell(monkeypatch)
    mod = common.config_module(CONFIG)
    clients = mod.make_clients(1, cfg["federation"]["num_clients"],
                               cfg["data"]["samples_per_client"])
    sim = program.simulator(cfg, 2, clients)
    record = program.run_stage(program.session(sim, cfg))
    tr = L.LayerTrace()
    tr.add_hlo(stage_scoped.stage_program_hlo(sim, cfg, clients, record))
    found = {s for p in tr.paths.values() for s in stage_scoped.SCOPES
             if s in p}
    assert found == set(stage_scoped.SCOPES)
    load = stage_scoped.expert_tokens(record)
    assert 0 < load["mean"] <= load["max"]


def test_scoped_readers_of_made_trace():
    dev, prog = "/device:TPU:0", L.PROGRAM
    path = "jit(stage_program)/while/body/vmap(fl.local_train)/"
    ops = [("fusion.1", 0, 10), ("fusion.2", 10, 14), ("fusion.3", 14, 20),
           ("fusion.4", 20, 26), ("fusion.5", 26, 30), ("copy.6", 30, 32)]
    paths = {(prog, "fusion.1"): path + "mla.attention/dot_general",
             (prog, "fusion.2"): path + "mla.attention/lora.adapter/dot",
             (prog, "fusion.3"): path + "transpose(jvp(moe.experts))/dot",
             (prog, "fusion.4"): path + "moe.route/top_k",
             (prog, "fusion.5"): path + "moe.shared/dot",
             (prog, "copy.6"): path + "copy"}
    tr = L.LayerTrace(ops={dev: ops}, annotations=[("bench.stage", 0, 40)],
                      modules={dev: [(prog, 0, 32)]},
                      op_modules={dev: [prog] * len(ops)}, paths=paths)
    d = {"layers": stage_scoped.scope_summary(tr, 2)}
    ms = {n: common.metric_reader(n).read(d)
          for n in ("mla_ms.stage", "moe_ms.stage", "lora_ms.stage")}
    assert ms == pytest.approx({"mla_ms.stage": 5e-6, "moe_ms.stage": 8e-6,
                                "lora_ms.stage": 2e-6})
    assert L.SCOPES == ("fl.local_train", "fl.aggregate", "coding.encode")
    for n in ms:
        assert common.metric_reader(n).read({"layers": {"stages": 1}}) is None


def test_flops_by_hand():
    """1.185 GFLOP a token at the configuration's own size: forward
    578,819,072 a token, twice, plus the score and value products and the
    adapters' matmuls once more."""
    mod = common.config_module(CONFIG)
    forward = (5 * (27_525_120 + 5_242_880 + 263_168) + 138_412_032
               + 4 * (262_144 + 34_603_008 + 12_976_128) + 83_886_080)
    assert forward == 578_819_072
    per_token = 2 * forward + 5 * (5_242_880 + 263_168)
    assert mod.flops_per_example() == pytest.approx(512 * per_token, rel=1e-12)
    assert per_token == pytest.approx(1.185e9, rel=1e-3)
