"""Forgetting-verification suite (repro.verify) — calibration, exactness,
and the paper's acceptance ordering on a tiny CNN scenario.

The heavy fixture trains ONE victim federation pushed into the memorization
regime (high lr, many local epochs, few samples per client — both probes
measure memorization residue) and verifies SE and FE against the retrain
oracle and the no-unlearn baseline.  The asserted ordering is the paper's
prediction:

* the no-unlearn model scores strictly higher than the oracle on BOTH
  forgetting probes (shadow-MIA F1 and canary accuracy) — the probes can
  detect remembered data;
* the sharded frameworks land within a seeded tolerance of the oracle —
  unlearning is indistinguishable from never-trained;
* the oracle itself calibrates at the no-information rate (MIA F1 ~ 0.5
  under the balanced decision rule) and chance canary accuracy.

Everything but wall time is bit-reproducible under a fixed seed.
"""
import dataclasses
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.fl import mia
from repro.fl.experiment.frameworks import UnlearnContext, run_unlearn
from repro.fl.experiment.scenario import ScenarioConfig, build_simulator
from repro.fl.experiment.stage import train_stage
from repro.fl.tasks import resolve_task
from repro.verify import (VERIFIERS, CanaryVerifier, ForgettingVerifier,
                          ShadowMIAVerifier, UtilityVerifier, get_verifier,
                          plant_canaries, predict_stage_victim,
                          resolve_verifiers, run_verification)
from repro.verify.report import CandidateScore, VerifyReport

# the tiny CNN victim scenario: memorization regime at CI scale
CFG = ScenarioConfig(task="classification", num_clients=8, clients_per_round=8,
                     num_shards=2, samples_per_client=32, image_size=10,
                     local_epochs=8, global_rounds=6, test_n=160, seed=3,
                     lr=0.3, noise=0.35, store="coded")
N_SHADOWS = 2
N_CANARIES = 12

# seeded tolerances: the run is deterministic, these bound the candidate-vs-
# oracle gap with headroom over the measured values (SE: mia .07 / canary
# .08; FE: mia .19 / canary .08)
TOL_MIA = 0.25
TOL_CANARY = 0.15
MARGIN_MIA = 0.05      # none must beat oracle by at least this much
MARGIN_CANARY = 0.10


@pytest.fixture(scope="module")
def report():
    return run_verification(CFG, frameworks=("SE", "FE"),
                            n_shadows=N_SHADOWS, n_canaries=N_CANARIES)


@pytest.fixture(scope="module")
def repeat_report():
    """Second independent run (SE only) for bit-reproducibility."""
    return run_verification(CFG, frameworks=("SE",),
                            n_shadows=N_SHADOWS, n_canaries=N_CANARIES)


# ---------------------------------------------------------------------------
# acceptance: the suite separates frameworks as the paper predicts
# ---------------------------------------------------------------------------

def test_probes_detect_remembered_data(report):
    none, oracle = report.candidate("none"), report.candidate("oracle")
    assert none.metrics["mia_f1"] > oracle.metrics["mia_f1"] + MARGIN_MIA
    assert (none.metrics["canary_acc"]
            > oracle.metrics["canary_acc"] + MARGIN_CANARY)


@pytest.mark.parametrize("fw", ["SE", "FE"])
def test_unlearned_indistinguishable_from_oracle(report, fw):
    assert report.gap(fw, "mia_f1") <= TOL_MIA
    assert report.gap(fw, "canary_acc") <= TOL_CANARY


def test_oracle_calibrates_at_no_information(report):
    oracle = report.candidate("oracle")
    # balanced decision rule -> no-information F1 ~ 0.5
    assert 0.3 <= oracle.metrics["mia_f1"] <= 0.65
    chance = oracle.metrics["canary_chance"]
    assert chance == pytest.approx(1 / 10)
    assert oracle.metrics["canary_acc"] <= chance + 0.15


def test_unlearning_preserves_retained_utility(report):
    none = report.candidate("none")
    for fw in ("SE", "FE", "oracle"):
        c = report.candidate(fw)
        assert c.metrics["retain_acc"] >= none.metrics["retain_acc"] - 0.25


def test_oracle_pays_the_full_retraining_bill(report):
    se, oracle = report.candidate("SE"), report.candidate("oracle")
    assert oracle.cost_units > se.cost_units
    assert report.candidate("none").cost_units == 0.0


def test_report_export_shape(report):
    d = report.to_dict()
    assert d["task"] == "classification" and d["seed"] == CFG.seed
    assert {c["name"] for c in d["candidates"]} == {"none", "SE", "FE",
                                                    "oracle"}
    assert set(d["gaps_to_oracle"]) == {"none", "SE", "FE"}
    assert "none" in d["pareto_front"]      # best forgetting-free utility
    assert report.to_json().startswith("{")


def test_bit_reproducible_under_fixed_seed(report, repeat_report):
    a, b = report.metrics_dict(), repeat_report.metrics_dict()
    for name in b:                           # repeat ran a candidate subset
        assert a[name] == b[name], f"candidate {name} not reproducible"


# ---------------------------------------------------------------------------
# oracle exactness: the framework output IS the manual retrain counterfactual
# ---------------------------------------------------------------------------

def test_oracle_matches_manual_retrain_loop():
    cfg = dataclasses.replace(CFG, local_epochs=3, global_rounds=3, test_n=80)
    sim, _ = build_simulator(cfg)
    record = train_stage(sim, store_kind=cfg.store)
    victim = record.plan.clients[0]
    res = run_unlearn(sim, "oracle", record, [victim])

    ctx = UnlearnContext(sim, record, [victim], sim.fl.global_rounds,
                         None, None)
    w0 = ctx.stage_init_model()
    for s in record.shard_models:
        if s not in res.impacted_shards:
            for a, b in zip(jax.tree.leaves(record.shard_models[s]),
                            jax.tree.leaves(res.models[s])):
                np.testing.assert_array_equal(a, b)
            continue
        retained = ctx.retained(s)
        assert victim not in retained
        g = len(record.round_globals[s]) - 1
        xs, ys = ctx.stack_client_data(retained)
        w = w0
        for _ in range(g):
            w = ctx.stacked_mean(ctx.local_train(w, xs, ys,
                                                 sim.fl.local_epochs))
        for a, b in zip(jax.tree.leaves(w), jax.tree.leaves(res.models[s])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-6)


def test_oracle_registered_as_framework_alias():
    from repro.fl.experiment import FRAMEWORKS
    assert FRAMEWORKS["oracle"] is FRAMEWORKS["retrain-oracle"]
    assert FRAMEWORKS["oracle"].exact


# ---------------------------------------------------------------------------
# canary planting
# ---------------------------------------------------------------------------

def _client_data(task, n_clients=4, n=10, seed=0):
    rng = np.random.default_rng(seed)
    if task == "classification":
        mk = lambda: (rng.normal(size=(n, 6, 6, 1)).astype(np.float32),
                      rng.integers(0, 10, n).astype(np.int64))
    else:
        mk = lambda: (rng.integers(0, 30, (n, 12)).astype(np.int32),
                      rng.integers(0, 30, (n, 12)).astype(np.int32))
    return {c: mk() for c in range(n_clients)}


@pytest.mark.parametrize("task,cfg,chance", [
    ("classification", SimpleNamespace(num_classes=10), 0.1),
    ("generation", SimpleNamespace(vocab_size=30), 1 / 30),
])
def test_plant_canaries_replaces_first_k(task, cfg, chance):
    data = _client_data(task)
    before = {c: (x.copy(), y.copy()) for c, (x, y) in data.items()}
    spec = resolve_task(task)
    cx, cy, got_chance = plant_canaries(data, [1, 3], spec, cfg, n=4, seed=7)
    assert got_chance == pytest.approx(chance)
    assert cx.shape == (8,) + before[1][0].shape[1:]
    for v in (1, 3):
        x, y = data[v]
        bx, by = before[v]
        # replacement, not append: counts/shapes/dtypes unchanged
        assert x.shape == bx.shape and x.dtype == bx.dtype
        assert y.shape == by.shape and y.dtype == by.dtype
        assert not np.array_equal(x[:4], bx[:4])
        np.testing.assert_array_equal(x[4:], bx[4:])
    for c in (0, 2):                         # non-victims untouched
        np.testing.assert_array_equal(data[c][0], before[c][0])
        np.testing.assert_array_equal(data[c][1], before[c][1])


def test_plant_canaries_deterministic_and_per_victim_distinct():
    spec = resolve_task("classification")
    cfg = SimpleNamespace(num_classes=10)
    a = plant_canaries(_client_data("classification"), [1, 3], spec, cfg,
                       n=4, seed=7)
    b = plant_canaries(_client_data("classification"), [1, 3], spec, cfg,
                       n=4, seed=7)
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[1], b[1])
    # different victims get different canaries (per-victim seed offset)
    assert not np.array_equal(a[0][:4], a[0][4:])


def test_plant_canaries_rejects_zero():
    with pytest.raises(ValueError, match="at least 1 canary"):
        plant_canaries(_client_data("classification"), [1],
                       resolve_task("classification"),
                       SimpleNamespace(num_classes=10), n=0, seed=0)


# ---------------------------------------------------------------------------
# task-routed MIA features (satellite: no raw task-string branching)
# ---------------------------------------------------------------------------

def test_classification_mia_features_formula():
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(16, 10)).astype(np.float32))
    y = jnp.asarray(rng.integers(0, 10, 16))
    f = np.asarray(resolve_task("classification").mia_features(logits, y))
    ll = np.asarray(jax.nn.log_softmax(logits, -1))
    p = np.exp(ll)
    np.testing.assert_allclose(f[:, 0], -ll[np.arange(16), np.asarray(y)],
                               rtol=1e-5)
    np.testing.assert_allclose(f[:, 1], p.max(-1), rtol=1e-5)
    np.testing.assert_allclose(f[:, 2], -(p * ll).sum(-1), rtol=1e-5)


def test_generation_mia_features_sequence_mean():
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(6, 12, 30)).astype(np.float32))
    y = jnp.asarray(rng.integers(0, 30, (6, 12)))
    f = np.asarray(resolve_task("generation").mia_features(logits, y))
    assert f.shape == (6, 3)
    ll = np.asarray(jax.nn.log_softmax(logits, -1))
    gold = np.take_along_axis(ll, np.asarray(y)[..., None], -1)[..., 0]
    np.testing.assert_allclose(f[:, 0], -gold.mean(-1), rtol=1e-5)


def test_mia_features_accept_spec_and_aliases():
    rng = np.random.default_rng(1)
    x = rng.normal(size=(20, 8)).astype(np.float32)
    y = rng.integers(0, 4, 20).astype(np.int64)
    models = {0: None}
    predict = lambda _m, b: jnp.asarray(b["x"][:, :4])
    make_batch = lambda x, y: {"x": x, "y": y}
    outs = [mia._features(predict, models, make_batch, x, y, task)
            for task in ("classification", "image",
                         resolve_task("classification"))]
    np.testing.assert_array_equal(outs[0], outs[1])
    np.testing.assert_array_equal(outs[0], outs[2])


# ---------------------------------------------------------------------------
# public predict surface (satellite: no private simulator attrs)
# ---------------------------------------------------------------------------

def test_predict_interface_public_surface():
    cfg = dataclasses.replace(CFG, local_epochs=1, global_rounds=1, test_n=40)
    sim, test = build_simulator(cfg)
    record = train_stage(sim, store_kind=cfg.store)
    iface = sim.predict_interface()
    assert iface.task is sim.task_spec
    x, y = test[0][:8], test[1][:8]
    lg = iface.ensemble_logits(record.shard_models, x, y)
    assert lg.shape[0] == 8 and lg.dtype == jnp.float32
    manual = sum(np.asarray(iface.predict(m, iface.make_batch(
        jnp.asarray(x), jnp.asarray(y)))) for m in
        record.shard_models.values()) / len(record.shard_models)
    np.testing.assert_allclose(np.asarray(lg), manual, rtol=1e-5, atol=1e-6)


def test_predict_stage_victim_matches_trained_plan():
    cfg = dataclasses.replace(CFG, local_epochs=1, global_rounds=1, test_n=40)
    victim = predict_stage_victim(cfg)
    sim, _ = build_simulator(cfg)
    record = train_stage(sim, store_kind=cfg.store)
    assert victim in record.plan.clients


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_verifier_registry():
    assert {"shadow-mia", "canary", "utility"} <= set(VERIFIERS)
    assert isinstance(get_verifier("canary"), CanaryVerifier)
    with pytest.raises(ValueError, match="unknown verifier"):
        get_verifier("nope")
    got = resolve_verifiers(["shadow-mia", UtilityVerifier,
                             CanaryVerifier(n_canaries=3)])
    assert isinstance(got[0], ShadowMIAVerifier)
    assert isinstance(got[1], UtilityVerifier)
    assert got[2].n_canaries == 3
    assert all(isinstance(v, ForgettingVerifier) for v in got)


def test_canary_score_before_plant_raises():
    with pytest.raises(RuntimeError, match="before plant"):
        CanaryVerifier().score(None, {})


# ---------------------------------------------------------------------------
# report mechanics (pure python)
# ---------------------------------------------------------------------------

def _mk_report():
    mk = lambda name, fw, cost, mia_f1, can, ret: CandidateScore(
        name, fw, 0.0, cost, {"mia_f1": mia_f1, "canary_acc": can,
                              "retain_acc": ret})
    return VerifyReport(
        task="classification", store="coded", seed=0, victims=[2],
        n_shadows=2, n_canaries=8, verifiers=["shadow-mia"],
        candidates=[mk("none", None, 0.0, 0.8, 0.6, 0.7),
                    mk("SE", "SE", 10.0, 0.5, 0.1, 0.68),
                    mk("slow", "FR", 99.0, 0.5, 0.1, 0.68),
                    mk("oracle", "oracle", 50.0, 0.5, 0.1, 0.7)])


def test_pareto_front_drops_dominated():
    front = _mk_report().pareto_front()
    # "slow" matches SE on every metric at 10x the cost -> dominated
    assert "slow" not in front
    assert {"SE", "oracle"} <= set(front)


def test_gap_and_candidate_lookup():
    rep = _mk_report()
    assert rep.gap("SE", "mia_f1") == pytest.approx(0.0)
    assert rep.gap("none", "canary_acc") == pytest.approx(0.5)
    with pytest.raises(KeyError, match="no candidate"):
        rep.candidate("missing")


def test_metrics_dict_excludes_walls():
    md = _mk_report().metrics_dict()
    assert "wall_s" not in md["SE"] and md["SE"]["cost_units"] == 10.0
