"""CPU tests of the benchmark's yardstick: operation counts, the copied
traffic generator, the peaks table, the reference's weights, the metric
readers, BENCHMARK.json, and the refusal to run without a chip."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

from _tiny import ROOT, common  # noqa: F401  (puts the repo on sys.path)
from bench.harness import compare, peaks, traffic


# ------------------------------------------------------------ FLOP counts
def test_nanogpt_flops_by_hand():
    # per token and layer: q, k, v, o projections 4 x 2*16*16; gated MLP
    # 3 x 2*16*64; scores and values over all 64 positions 2 x 2*64*16;
    # output projection 2*16*109 once (the 109 real symbols, not the padding
    # to 512); forward + backward = 3 x forward
    per_layer = 4 * 2 * 16 * 16 + 3 * 2 * 16 * 64 + 2 * 2 * 64 * 16
    forward = 64 * (4 * per_layer + 2 * 16 * 109)
    assert forward == 3_368_960
    mod = common.config_module("nanogpt-paper")
    assert mod.flops_per_example() == 3 * forward == 10_106_880


def test_cnn_flops_by_hand():
    conv1 = 2 * 28 * 28 * 16 * (3 * 3 * 1)          # 225,792
    conv2 = 2 * 14 * 14 * 32 * (3 * 3 * 16)         # 1,806,336
    fc = 2 * 7 * 7 * 32 * 128 + 2 * 128 * 10        # 403,968
    assert conv1 + conv2 + fc == 2_436_096
    mod = common.config_module("cnn-paper")
    assert mod.flops_per_example() == 3 * 2_436_096


# ---------------------------------------------------------------- traffic
@pytest.mark.parametrize("skew", [0.0, 1.5])
def test_copied_generator_matches_program(skew):
    from repro.service.workload import iter_poisson_trace
    pool = list(range(3, 40, 2))
    theirs = list(iter_poisson_trace(pool, 50, 1.7, seed=1234, skew=skew))
    ours = list(traffic.iter_poisson_trace(pool, 50, 1.7, seed=1234,
                                           skew=skew))
    assert [(r.t, r.clients) for r in theirs] == ours


# ------------------------------------------------------------------ peaks
def test_peaks_known_and_unknown():
    v5e = peaks.peak("TPU v5 lite")
    assert v5e["flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(peaks.UnknownDevice, match="no published peaks"):
        peaks.peak("TPU v9 imaginary")


# ------------------------------------------------------------ reference
@pytest.mark.parametrize("name", ["nanogpt-paper", "cnn-paper"])
def test_reference_weights_are_the_programs(name):
    import jax
    from repro.configs import get_config
    from repro.models import init_params
    mod = common.config_module(name)
    seed = 2 ** 31 - 100
    theirs = init_params(get_config(name), jax.random.key(seed))
    ours = mod.init(seed)
    assert jax.tree.structure(theirs) == jax.tree.structure(ours)
    for a, b in zip(jax.tree.leaves(theirs), jax.tree.leaves(ours)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("name", ["nanogpt-paper", "cnn-paper"])
def test_reference_loss_is_the_programs(name):
    import jax
    from repro.configs import get_config
    from repro.fl.tasks import get_task
    from repro.models import loss_fn
    mod = common.config_module(name)
    w = mod.init(7)
    x, y = mod.make_clients(3, 2, 10)[1]
    task = get_task(mod.CONFIG["task"])
    theirs = loss_fn(get_config(name))(w, task.make_batch(x, y))[0]
    with jax.default_matmul_precision("highest"):
        ours = mod.loss(w, x, y)
    np.testing.assert_allclose(float(ours), float(theirs), rtol=2e-6)


def test_client_data_sizes_and_seeds():
    from bench.harness import data
    a = data.char_clients(5, 4, 3, 8, 109)
    b = data.char_clients(5, 4, 3, 8, 109)
    assert sorted(a) == [0, 1, 2, 3]
    assert a[2][0].shape == (3, 8) and a[2][0].dtype == np.int32
    np.testing.assert_array_equal(a[1][0][:, 1:], a[1][1][:, :-1])
    np.testing.assert_array_equal(a[3][0], b[3][0])
    assert a[0][0].max() < 109
    im = data.image_clients(5, 3, 4, 28, 1, 10)
    assert im[2][0].shape == (4, 28, 28, 1) and im[2][1].shape == (4,)
    assert 0.0 <= im[0][0].min() and im[0][0].max() <= 1.0


# ------------------------------------------------------- seeds and plan
@pytest.mark.parametrize("seed", [0, 2 ** 31 + 5, 2 ** 40 + 3])
def test_subseeds_are_31_bit_and_repeat(seed):
    a, b = common.subseeds(seed), common.subseeds(seed)
    assert a == b and set(a) == {"data", "program"}
    assert all(0 <= v < 2 ** 31 for v in a.values())
    assert a["data"] != a["program"] and a != common.subseeds(seed + 1)


@pytest.mark.parametrize("seed, want", [
    (0, {"data": 821328126, "program": 1529665575}),
    (2 ** 31 + 5, {"data": 181218269, "program": 869972020}),
    (2 ** 40 + 3, {"data": 642151732, "program": 1410910647})])
def test_subseeds_without_a_checkpoint_keep_their_values(seed, want):
    """A configuration with no ``checkpoint_seed`` draws both seeds from
    ``--seed``, to the values these cells have always had."""
    assert common.subseeds(seed) == common.subseeds(seed, None) == want


@pytest.mark.parametrize("seeds", [(0, 2 ** 31 + 5), (2 ** 40 + 3, 7)])
def test_checkpoint_seed_fixes_only_the_program_seed(seeds):
    a, b = (common.subseeds(s, 5) for s in seeds)
    assert a["program"] == b["program"] == common.subseeds(5)["program"]
    assert [a["data"], b["data"]] == [common.subseeds(s)["data"] for s in seeds]
    assert a["data"] != b["data"]
    assert all(0 <= v < 2 ** 31 for v in (*a.values(), *b.values()))


@pytest.mark.parametrize("name", ["nanogpt-paper", "cnn-paper",
                                  "moonlight-16b-a3b-fedlora"])
def test_run_draws_the_program_seed_from_the_configuration(name):
    """Only a configuration that names its checkpoint fixes the program's
    seed; the others follow ``--seed`` alone."""
    from bench.harness.cell import Run
    mod = common.config_module(name)
    cp = mod.CONFIG.get("checkpoint_seed")
    assert (cp is not None) == (name == "moonlight-16b-a3b-fedlora")
    seed = 2 ** 33 + 7
    run = Run(name=name, workload={}, cfg=mod.CONFIG, model=mod, seed=seed,
              seconds=1.0, trace=False, devices=[], started=0.0)
    assert run.seeds == common.subseeds(seed, cp)
    assert run.seeds["data"] == common.subseeds(seed)["data"]


def test_plan_splits_distinct_clients_into_equal_shards():
    from bench.harness.drivers import stage
    cfg = common.config_module("nanogpt-paper").CONFIG
    fed = cfg["federation"]
    first, second = stage.plan(cfg, 2 ** 33 + 1, stages=2)
    for shards in (first, second):
        clients = [c for s in sorted(shards) for c in shards[s]]
        assert sorted(shards) == list(range(fed["num_shards"]))
        assert len(clients) == len(set(clients)) == fed["clients_per_stage"]
        assert all(0 <= c < fed["num_clients"] for c in clients)
    assert first == stage.plan(cfg, 2 ** 33 + 1)[0] != second


# ----------------------------------------------------------- comparison
def test_norm_gap_uses_median_floor():
    ref = {"a": 1.0, "b": 2.0, "c": 1e-6}
    prog = {"a": 1.1, "b": 2.0, "c": 2e-6}
    # c's own norm is tiny: its gap is taken against the median leaf (1.0)
    assert compare.norm_gap(prog, ref, ["a", "b", "c"]) == pytest.approx(0.1)
    assert compare.scalar_gap([1.0, 2.2], [1.0, 2.0]) == pytest.approx(0.1)


def test_kept_leaves_drop_round_off_only_leaves():
    grads = {"w": np.ones(4), "v": np.full(4, 2.0), "bias": np.full(4, 1e-9)}
    assert compare.kept_leaves(grads) == ["['v']", "['w']"]


# --------------------------------------------------------- metric readers
def test_metric_readers():
    stage = {"stages": 4, "window_s": 10.0, "flops_per_stage": 197e12 * 0.5,
             "peak_flops": 197e12, "trace": {"window_s": 2.0, "busy_s": 1.5}}
    mfu = common.metric_reader("mfu.stage")
    assert mfu.read(stage) == pytest.approx(20.0)
    assert common.metric_reader("idle.stage").read(stage) == pytest.approx(25.0)
    # nothing to read: no value, never a zero
    for name in ("mfu.stage", "idle.stage"):
        assert common.metric_reader(name).read({"peak_flops": None}) is None


# ------------------------------------------------------- BENCHMARK.json
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_json_finds_every_file():
    bench = common.benchmark()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    configs = {c["name"]: c for c in bench["configs"]}
    for c in bench["configs"]:
        assert NAME.match(c["name"]) and (ROOT / c["file"]).is_file()
        assert common.config_module(c["name"]).CONFIG["name"] == c["name"]
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in configs
        wl = common.workload(w["name"])
        assert wl["config"] == w["config"]
        assert (ROOT / "bench" / "harness" / "drivers"
                / f"{wl['driver']}.py").is_file()
        assert set(wl["limits"])
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"] + bench["end_to_end"]:
        assert NAME.match(m["name"])
        assert set(m.get("workloads", cells)) <= cells
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()


# ------------------------------------------------- refusal without a chip
def _bench_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return env


def test_run_refuses_the_cpu():
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "nanogpt.stage",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=_bench_env(), capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert "no accelerator" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_run_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "nanogpt.stage",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=_bench_env(), capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert json.loads((tmp_path / "BENCHMARK.json").read_text())["paths"] == ["bench"]
