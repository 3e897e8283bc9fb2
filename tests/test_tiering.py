"""Tiered-store tests: int8 quantization round-trip bounds (property-based),
the hot/warm/cold ladder's bit-stability, budget-driven eviction, the
acceptance anchors — an unlimited-budget ``TieredStore`` is bit-identical to
``CodedStore`` (models *and* shared ``StoreStats`` fields), and a fully
demoted session serves SE unlearning entirely from warm+cold within the
quantization bound — plus cold-tier corruption recovery through the robust
decoder and snapshot round-trips that carry cold-file pointers."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_fallback import given, settings, st
from repro.configs import FLConfig, OptimizerConfig, get_config
from repro.data import client_datasets_images, make_image_data
from repro.durability import load_snapshot, save_snapshot
from repro.durability.session_state import _capture_store, _restore_store
from repro.faults import FaultPlan
from repro.fl import FLSimulator
from repro.fl.experiment import (FederatedSession, RequestSchedule,
                                 UnlearnRequest)
from repro.stores.store import STORES, RoundPayload, StoreStats, make_store
from repro.tiering import (EVICTION, TIER_ORDER, TIERS, TierEntry,
                           TieredStore, dequantize_int8, make_eviction,
                           quant_error_bound, quantize_int8)
from repro.tiering.tiers import cold_file_crc

FAULT_SEED = 20240

FL_TINY = FLConfig(num_clients=8, clients_per_round=8, num_shards=2,
                   local_epochs=1, global_rounds=3, retrain_ratio=2.0)


def _tiny_sim(seed=3):
    cfg = dataclasses.replace(get_config("cnn-paper"), image_size=8,
                              d_model=16, cnn_channels=(4, 4))
    data = make_image_data(FL_TINY.num_clients * 12, image_size=8, seed=0)
    clients = client_datasets_images(data, FL_TINY.num_clients, iid=True)
    return FLSimulator(cfg, FL_TINY, clients, task="image",
                       opt_cfg=OptimizerConfig(name="sgdm", lr=0.05,
                                               grad_clip=0.0),
                       local_batch=10, seed=seed)


def _trees_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _unit_store(kind="tiered", c=12, s=4, rounds=1, seed=1, **opts):
    """Registry-built store over ``c`` clients / ``s`` shards with ``rounds``
    seeded rounds already flushed in (mirrors the fault-suite helper)."""
    per = c // s
    shard_clients = {i: list(range(i * per, (i + 1) * per))
                     for i in range(s)}
    store = make_store(kind, shard_clients, num_shards=s, num_clients=c,
                       **opts)
    rng = np.random.default_rng(seed)
    for rnd in range(rounds):
        params = {cl: {"w": jnp.asarray(rng.standard_normal(5), jnp.float32)}
                  for cl in range(c)}
        store.put_round(RoundPayload.from_clients(rnd, shard_clients, params))
    store.flush()
    return store


# ------------------------------------------------------------- quantization
class TestQuantization:
    @settings(max_examples=20, deadline=None)
    @given(c=st.integers(2, 24), p=st.integers(1, 64),
           log_mag=st.floats(-3.0, 3.0), seed=st.integers(0, 10_000))
    def test_round_trip_error_within_bound(self, c, p, log_mag, seed):
        rng = np.random.default_rng(seed)
        arr = jnp.asarray(rng.standard_normal((c, p)) * 10.0 ** log_mag,
                          jnp.float32)
        q, scales = quantize_int8(arr)
        back = np.asarray(dequantize_int8(q, scales), np.float64)
        err = np.abs(np.asarray(arr, np.float64) - back)
        # per-slice bound, and the global helper dominates every row
        assert (err.max(axis=1) <= scales * (0.5 + 127 * 1.2e-7) + 1e-12).all()
        assert err.max() <= quant_error_bound(scales) + 1e-12

    def test_zero_rows_are_exact(self):
        arr = jnp.zeros((3, 7), jnp.float32)
        q, scales = quantize_int8(arr)
        assert (np.asarray(q) == 0).all()
        assert (scales == 1.0).all()            # guarded against 0-division
        np.testing.assert_array_equal(np.asarray(dequantize_int8(q, scales)),
                                      np.zeros((3, 7), np.float32))

    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 10_000), bf16=st.booleans())
    def test_requantization_with_stored_scales_is_bit_exact(self, seed, bf16):
        """The lossy entry's invariant: dequantize → requantize with the SAME
        stored scales recovers q (and hence the dequantized value) exactly —
        repeated promote/demote cycles cannot drift."""
        rng = np.random.default_rng(seed)
        dt = jnp.bfloat16 if bf16 else jnp.float32
        arr = jnp.asarray(rng.standard_normal((6, 33)), dt)
        q1, scales = quantize_int8(arr)
        back = dequantize_int8(q1, scales, dtype=dt)
        q2, scales2 = quantize_int8(back, scales=scales)
        np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
        np.testing.assert_array_equal(scales, scales2)


# -------------------------------------------------------------- tier ladder
class TestTierLadder:
    def test_tiered_registered_in_stores(self):
        assert "tiered" in STORES
        assert isinstance(_unit_store(), TieredStore)

    def test_unlimited_budget_stays_hot(self):
        store = _unit_store()
        assert store.tier_of(0) == "hot"
        assert store.stats.tier_bytes["hot"] > 0
        assert store.stats.tier_bytes.get("warm", 0) == 0
        store.get_shard(0, 0)
        assert store.stats.tier_hits == {"hot": 1}
        assert store.stats.tier_misses == {}

    def test_zero_hot_budget_lands_warm_and_stays(self):
        store = _unit_store(hot_bytes=0)
        assert store.tier_of(0) == "warm"
        assert store.stats.tier_bytes["hot"] == 0
        assert store.stats.tier_evictions["hot"] >= 1
        store.get_shard(0, 0)
        # undersized hot budget must not promote (would churn forever)
        assert store.tier_of(0) == "warm"
        assert store.stats.tier_hits == {"warm": 1}
        assert store.stats.tier_misses == {"hot": 1}
        assert store.stats.tier_promotions == {}

    @pytest.mark.parametrize("slice_dtype", [None, "bfloat16"])
    def test_promote_demote_read_is_bit_stable(self, slice_dtype):
        """Once lossy, every read reconstructs the same bits — through warm,
        through cold, and through promote-back-to-hot cycles."""
        store = _unit_store(slice_dtype=slice_dtype)
        store.demote_all("warm")
        first = store.get_shard(0, 0)          # decodes warm, promotes hot
        assert store.tier_of(0) == "hot"
        store.demote_all("warm")
        _trees_equal(first, store.get_shard(0, 0))
        store.demote_all("cold")
        assert store.tier_of(0) == "cold"
        _trees_equal(first, store.get_shard(0, 0))
        assert store.stats.tier_promotions["hot"] == 3

    def test_cold_file_is_atomic_and_canonical(self):
        store = _unit_store()
        store.demote_all("cold")
        e = store._slices.entry(0)
        assert e.path is not None and os.path.exists(e.path)
        assert not any(f.endswith(".tmp") for f in os.listdir(store.cold_dir))
        assert cold_file_crc(e.path) == e.file_crc
        before = os.path.getmtime(e.path)
        store.get_shard(0, 0)                  # promote…
        store.demote_all("cold")               # …and demote again
        assert os.path.getmtime(e.path) == before   # file written exactly once

    def test_demote_all_rejects_unknown_tier(self):
        with pytest.raises(ValueError, match="unknown tier"):
            _unit_store().demote_all("lukewarm")

    def test_registries(self):
        assert tuple(TIER_ORDER) == ("hot", "warm", "cold")
        assert set(TIER_ORDER) <= set(TIERS)
        assert {"lru", "stage_age", "heat"} <= set(EVICTION)
        with pytest.raises(KeyError):
            make_eviction("nope")

    def test_eviction_policy_victim_choice(self):
        def entry(key, hits, last, stage):
            return TierEntry(key=key, shape=(2, 2), dtype=jnp.float32,
                             hits=hits, last_access=last, stage=stage)
        cands = [entry(0, hits=9, last=5, stage=2),
                 entry(1, hits=1, last=9, stage=0),
                 entry(2, hits=1, last=2, stage=1)]
        assert make_eviction("lru")(cands).key == 2          # oldest access
        assert make_eviction("stage_age")(cands).key == 1    # oldest birth
        # heat: fewest hits first, LRU tiebreak among the cold ones
        assert make_eviction("heat")(cands).key == 2

    def test_store_stats_tier_fields_merge_and_snapshot(self):
        a = StoreStats(tier_bytes={"hot": 10}, tier_hits={"hot": 2})
        b = StoreStats(tier_bytes={"hot": 5, "warm": 7},
                       tier_evictions={"hot": 1})
        tot = a + b
        assert tot.tier_bytes == {"hot": 15, "warm": 7}
        assert tot.tier_hits == {"hot": 2}
        assert tot.tier_evictions == {"hot": 1}
        snap = a.snapshot()
        snap.tier_bytes["hot"] = 999
        assert a.tier_bytes["hot"] == 10               # dicts are isolated


# ------------------------------------------------- session-level acceptance
def _schedule(rounds=1):
    return RequestSchedule([
        UnlearnRequest(lambda p: [p.shard_clients[0][0]], framework="SE",
                       after_stage=0, rounds=rounds)])


def _run_session(store, store_options=None, seed=3):
    session = FederatedSession(_tiny_sim(seed), store_kind=store,
                               store_options=store_options or {})
    report = session.run(1, schedule=_schedule())
    return session, report


@pytest.fixture(scope="module")
def coded_run():
    return _run_session("coded")


class TestUnlimitedBitIdentity:
    @pytest.fixture(scope="class")
    def tiered_run(self):
        return _run_session("tiered")

    def test_models_and_coded_slices_bit_identical(self, coded_run,
                                                   tiered_run):
        sess_c, _ = coded_run
        sess_t, _ = tiered_run
        for s in sess_c.records[0].shard_models:
            _trees_equal(sess_c.records[0].shard_models[s],
                         sess_t.records[0].shard_models[s])
        store_c, store_t = sess_c.records[0].store, sess_t.records[0].store
        store_c.flush(), store_t.flush()
        assert sorted(store_c._slices) == sorted(store_t._slices)
        for rnd in store_c._slices:
            np.testing.assert_array_equal(
                np.asarray(store_c._slices[rnd]),
                np.asarray(store_t._slices[rnd]))

    def test_unlearn_bit_identical(self, coded_run, tiered_run):
        (res_c,) = coded_run[1].stages[0].unlearn
        (res_t,) = tiered_run[1].stages[0].unlearn
        assert res_c.impacted_shards == res_t.impacted_shards
        assert res_c.cost_units == res_t.cost_units
        for s in res_c.models:
            _trees_equal(res_c.models[s], res_t.models[s])

    def test_shared_store_stats_byte_parity(self, coded_run, tiered_run):
        got_c = coded_run[1].store_stats.to_dict()
        got_t = tiered_run[1].store_stats.to_dict()
        tier_keys = {k for k in got_t if k.startswith("tier_")}
        for k in set(got_c) - tier_keys:
            assert got_c[k] == got_t[k], k
        assert got_t["tier_hits"].get("hot", 0) > 0
        assert got_t["tier_misses"] == {}

    def test_tier_metrics_surface_in_report(self, tiered_run):
        d = tiered_run[1].to_dict()
        assert d["store_stats"]["tier_bytes"]["hot"] > 0

    def test_tier_stats_fan_out_into_per_tier_gauges(self):
        from repro.telemetry.metrics import MetricsRegistry
        reg = MetricsRegistry()
        reg.absorb_store_stats(StoreStats(reads=3,
                                          tier_bytes={"hot": 8, "warm": 2},
                                          tier_hits={"cold": 1}), stage=0)
        gauges = reg.snapshot()["gauges"]
        tiered = {k: v for k, v in gauges.items() if "tier=" in k}
        assert any("store.tier_bytes" in k and "tier=hot" in k and v == 8
                   for k, v in tiered.items())
        assert any("store.tier_hits" in k and "tier=cold" in k and v == 1
                   for k, v in tiered.items())


class TestConstrainedServing:
    def test_se_unlearn_served_from_cold_within_quant_bound(self, coded_run,
                                                            tmp_path):
        """hot=warm=0: every stored round lives on disk, every decode is an
        int8 reconstruction — SE unlearning still lands within the
        quantization error envelope of the exact-store result."""
        sess, report = _run_session(
            "tiered", store_options=dict(hot_bytes=0, warm_bytes=0,
                                         offload_dir=str(tmp_path)))
        stats = report.store_stats
        assert set(stats.tier_hits) == {"cold"}
        assert stats.tier_bytes.get("hot", 0) == 0
        assert stats.tier_bytes.get("warm", 0) == 0
        assert stats.tier_hits["cold"] == stats.tier_misses["hot"] \
            == stats.tier_misses["warm"]
        # training never reads the store: shard models stay bit-identical
        sess_c, report_c = coded_run
        for s in sess_c.records[0].shard_models:
            _trees_equal(sess_c.records[0].shard_models[s],
                         sess.records[0].shard_models[s])
        (res_c,) = report_c.stages[0].unlearn
        (res_t,) = report.stages[0].unlearn
        assert res_c.impacted_shards == res_t.impacted_shards
        for s in res_c.models:
            diff = np.concatenate(
                [(np.asarray(x, np.float64) - np.asarray(y, np.float64)).ravel()
                 for x, y in zip(jax.tree.leaves(res_c.models[s]),
                                 jax.tree.leaves(res_t.models[s]))])
            ref = np.concatenate([np.asarray(x, np.float64).ravel()
                                  for x in jax.tree.leaves(res_c.models[s])])
            rel = np.linalg.norm(diff) / (np.linalg.norm(ref) + 1e-12)
            assert rel < 2e-2, rel                 # ~0.5% measured; bf16-order
            assert np.abs(diff).max() < 2e-2


# ------------------------------------------------------ cold-tier corruption
class TestColdCorruption:
    def test_cold_corrupt_recovers_and_is_accounted(self):
        clean = _unit_store(seed=7)
        clean.demote_all("cold")
        base = clean.get_shard(0, 0)
        store = _unit_store(seed=7)
        store.demote_all("cold")
        plan = FaultPlan(seed=FAULT_SEED).add("cold_corrupt", count=2,
                                              scale=10.0)
        store.attach_faults(plan)
        got = store.get_shard(0, 0)
        for cl in base:
            np.testing.assert_allclose(np.asarray(got[cl]["w"]),
                                       np.asarray(base[cl]["w"]), atol=1e-4)
        assert store.stats.corrupted_slices == 2
        assert store.stats.recovered_reads == 1
        assert plan.ledger.count("cold_corrupt") == 1
        assert plan.ledger.count("quorum_read") == 1

    def test_cold_corrupt_is_inert_for_hot_reads(self):
        store = _unit_store(seed=7)          # unlimited: stays hot
        plan = FaultPlan(seed=FAULT_SEED).add("cold_corrupt", count=2,
                                              scale=10.0)
        store.attach_faults(plan)
        store.get_shard(0, 0)
        assert store.stats.corrupted_slices == 0
        assert store.stats.recovered_reads == 0

    def test_quant_residue_is_not_flagged_as_corruption(self):
        """The widened lossy-read tolerance: an honest warm/cold round must
        decode clean — zero corrupted slices, zero recovery events."""
        store = _unit_store(seed=7)
        store.demote_all("cold")
        store.attach_faults(FaultPlan(seed=FAULT_SEED))   # empty plan
        store.get_shard(0, 0)
        assert store.stats.corrupted_slices == 0
        assert store.stats.recovered_reads == 0


# ------------------------------------------------------- snapshot round-trip
class TestTieredDurability:
    def _mixed_store(self, tmp_path):
        store = _unit_store(rounds=2, offload_dir=str(tmp_path))
        store.demote_all("cold")
        store.get_shard(1, 0)          # promote round 1 back to hot
        assert store.tier_of(0) == "cold" and store.tier_of(1) == "hot"
        return store

    def test_snapshot_round_trip_is_bit_identical(self, tmp_path):
        store = self._mixed_store(tmp_path)
        path = str(tmp_path / "store.ckpt")
        save_snapshot(path, _capture_store(store))
        back = _restore_store(load_snapshot(path))
        assert isinstance(back, TieredStore)
        assert back.budget == store.budget
        assert back.eviction == store.eviction
        for rnd in (0, 1):
            assert back.tier_of(rnd) == store.tier_of(rnd)
        assert back.stats.to_dict() == store.stats.to_dict()
        # round 0 reads come through the restored cold pointer on both sides
        for rnd in (0, 1):
            for s in range(4):
                want = store.get_shard(rnd, s)
                got = back.get_shard(rnd, s)
                for cl in want:
                    _trees_equal(want[cl], got[cl])

    def test_restore_rejects_corrupted_cold_file(self, tmp_path):
        store = self._mixed_store(tmp_path)
        state = _capture_store(store)
        cold = store._slices.entry(0).path
        with open(cold, "r+b") as f:
            f.seek(3)
            f.write(b"\xff\xff")
        with pytest.raises(IOError, match="crc"):
            _restore_store(state)

    def test_restore_rejects_missing_cold_file(self, tmp_path):
        store = self._mixed_store(tmp_path)
        state = _capture_store(store)
        os.remove(store._slices.entry(0).path)
        with pytest.raises(FileNotFoundError):
            _restore_store(state)
