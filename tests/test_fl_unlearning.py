"""End-to-end federated learning + unlearning tests (paper Sec 5 protocol at
reduced scale): SE/FE/FR/RR all produce finite working models, SE touches only
the impacted shard, the coded store round-trips through training, and the
theory formulas match Monte-Carlo."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import FLConfig, OptimizerConfig, get_config, reduce_for_smoke
from repro.core import theory, unlearning
from repro.core.sharding import ShardManager, adaptive_requests, even_requests
from repro.data import client_datasets_images, make_image_data
from repro.fl import FLSimulator
from repro.fl.experiment import train_stage
from repro.fl.mia import mia_f1

FL_SMALL = FLConfig(num_clients=12, clients_per_round=8, num_shards=2,
                    local_epochs=4, global_rounds=4, retrain_ratio=2.0)


@pytest.fixture(scope="module")
def sim():
    cfg = dataclasses.replace(get_config("cnn-paper"), image_size=12,
                              d_model=32, cnn_channels=(4, 8))
    data = make_image_data(12 * 40, image_size=12, seed=0)
    clients = client_datasets_images(data, FL_SMALL.num_clients, iid=True)
    # lr=0.1: the smoke config cuts the paper's training budget (L=10, G=30
    # -> 4, 4), and sgdm at the paper's lr=0.05 is still far from converged
    # at that budget (acc 0.27 vs the 0.3 learning bar). Doubling the lr
    # compensates for the reduced epoch count and trains stably (loss 1.98
    # -> 1.79, acc 0.37); 0.15+ starts to diverge on this config.
    s = FLSimulator(cfg, FL_SMALL, clients, task="image",
                    opt_cfg=OptimizerConfig(name="sgdm", lr=0.1, grad_clip=0.0),
                    local_batch=10)
    return s


@pytest.fixture(scope="module")
def record(sim):
    return train_stage(sim, store_kind="coded")


def test_training_learns(sim, record):
    test = make_image_data(400, image_size=12, seed=99)
    m = sim.evaluate(record.shard_models, test.images, test.labels)
    assert m["acc"] > 0.3, f"shard-ensemble failed to learn: {m}"


@pytest.mark.parametrize("fw", ["SE", "FE", "FR", "RR"])
def test_unlearning_frameworks_run(sim, record, fw):
    victim = record.plan.shard_clients[0][0]
    res = sim.unlearn(fw, record, [victim], rounds=2)
    leaves = jax.tree.leaves(list(res.models.values())[0])
    assert all(np.all(np.isfinite(np.asarray(l, np.float32))) for l in leaves)
    assert res.cost_units > 0
    if fw == "SE":
        assert res.impacted_shards == [0]
        # untouched shard model must be bit-identical (isolation!)
        for a, b in zip(jax.tree.leaves(record.shard_models[1]),
                        jax.tree.leaves(res.models[1])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_se_cost_below_fr(sim, record):
    victim = record.plan.shard_clients[0][0]
    se = sim.unlearn("SE", record, [victim], rounds=2)
    fr = sim.unlearn("FR", record, [victim], rounds=2)
    assert se.cost_units < fr.cost_units, (se.cost_units, fr.cost_units)


def test_coded_store_erasure_during_unlearning(sim, record):
    """Unlearning still works when only a subset of slices is reachable."""
    victim = record.plan.shard_clients[0][0]
    avail = list(range(FL_SMALL.clients_per_round))[:FL_SMALL.num_shards + 1]
    res = sim.unlearn("SE", record, [victim], rounds=1, available=avail)
    leaves = jax.tree.leaves(res.models[0])
    assert all(np.all(np.isfinite(np.asarray(l, np.float32))) for l in leaves)


def test_mia_f1_in_range(sim, record):
    test = make_image_data(300, image_size=12, seed=123)
    victim = record.plan.shard_clients[0][0]
    res = sim.unlearn("SE", record, [victim], rounds=2)
    member_ids = [c for c in record.plan.clients if c != victim][:4]
    mx = np.concatenate([sim.client_data[c][0][:40] for c in member_ids])
    my = np.concatenate([sim.client_data[c][1][:40] for c in member_ids])
    f1 = mia_f1(sim._pf, res.models, sim._make_batch, "image",
                (mx, my), (test.images, test.labels),
                sim.client_data[victim])
    assert 0.0 <= f1 <= 1.0


def test_request_patterns():
    mgr = ShardManager(100, 4, 20, seed=0)
    plan = mgr.new_stage()
    ev = even_requests(plan, 4)
    assert len({plan.shard_of(c) for c in ev}) == 4   # spread over all shards
    ad = adaptive_requests(plan, 3)
    assert len({plan.shard_of(c) for c in ad}) == 1   # concentrated
    assert mgr.impacted_shards(plan, ad) == {plan.shard_of(ad[0])}


def test_theory_matches_montecarlo():
    s, k, ct = 4, 6, 2.5
    assert abs(theory.sequential_time(s, k, ct)
               - theory.mc_sequential_time(s, k, ct)) < 1e-6
    analytic = theory.concurrent_time(s, k, ct)
    mc = theory.mc_concurrent_time(s, k, ct)
    assert abs(analytic - mc) / analytic < 0.02
    lo, hi = theory.storage_efficiency_bounds(100, 4, 0.1)
    assert lo == 4 and abs(hi - 80.0) < 1e-9
    assert theory.coded_throughput(100, 8) > theory.coded_throughput(100, 4)


def test_calibration_eq3_algebra():
    """eq (3): the calibrated update has the historical norm, new direction."""
    w = {"a": np.zeros(4, np.float32)}
    new_delta = {"a": np.asarray([0.0, 3.0, 0.0, 4.0], np.float32)}  # norm 5
    old_delta = {"a": np.asarray([10.0, 0.0, 0.0, 0.0], np.float32)}  # norm 10
    out = unlearning.calibrate(w, [new_delta], [old_delta])
    got = np.asarray(out["a"])
    np.testing.assert_allclose(np.linalg.norm(got), 10.0, rtol=1e-5)
    np.testing.assert_allclose(got / np.linalg.norm(got),
                               np.asarray(new_delta["a"]) / 5.0, rtol=1e-5)
