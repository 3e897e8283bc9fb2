"""Experiment-layer tests: store/framework registries, RoundPayload
validation, StoreStats aggregation, and the multi-stage ``FederatedSession``
acceptance path — >=3 stages with interleaved SE requests asserting
(a) only impacted shards retrain per request, (b) per-stage coded-store bytes
match the single-stage (shim) path, and (c) every registered framework is
bit-identical to the deprecated ``FLSimulator.unlearn`` shim."""
import dataclasses
import json

import jax
import numpy as np
import pytest

from repro.stores.store import (RoundPayload, STORES, StoreStats,
                                    make_store)
from repro.configs import FLConfig, OptimizerConfig, get_config
from repro.data import client_datasets_images, make_image_data
from repro.fl import FLSimulator
from repro.fl.experiment import (FRAMEWORKS, FederatedSession, RequestSchedule,
                                 ScenarioConfig, UnlearnContext,
                                 UnlearnFramework, UnlearnRequest,
                                 build_session, get_framework,
                                 register_framework, run_scenario, run_unlearn,
                                 train_stage)

FL_TINY = FLConfig(num_clients=10, clients_per_round=8, num_shards=2,
                   local_epochs=2, global_rounds=3, retrain_ratio=2.0)


def _tiny_sim(seed=0):
    cfg = dataclasses.replace(get_config("cnn-paper"), image_size=8,
                              d_model=16, cnn_channels=(4, 4))
    data = make_image_data(FL_TINY.num_clients * 30, image_size=8, seed=0)
    clients = client_datasets_images(data, FL_TINY.num_clients, iid=True)
    return FLSimulator(cfg, FL_TINY, clients, task="image",
                       opt_cfg=OptimizerConfig(name="sgdm", lr=0.05,
                                               grad_clip=0.0),
                       local_batch=10, seed=seed)


def _trees_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------- registries
class TestRegistries:
    def test_builtin_stores_registered(self):
        assert {"full", "uncoded", "coded"} <= set(STORES)

    def test_make_store_unknown_raises(self):
        with pytest.raises(KeyError, match="unknown store"):
            make_store("nope", {0: [0]})

    def test_builtin_frameworks_registered(self):
        assert {"SE", "SE-uncoded", "FE", "FR", "RR"} <= set(FRAMEWORKS)
        assert get_framework("SE").name == "SE"

    def test_get_framework_unknown_raises(self):
        with pytest.raises(ValueError, match="unknown unlearning framework"):
            get_framework("nope")

    def test_third_party_framework_is_a_plugin(self):
        """The registry makes a new strategy drop-in: register, dispatch by
        name through the same entry point the built-ins use."""
        @register_framework("NOOP-test")
        class NoopEraser(UnlearnFramework):
            def run(self, ctx: UnlearnContext):
                return dict(ctx.record.shard_models), 0.0
        try:
            sim = _tiny_sim()
            rec = train_stage(sim, store_kind="uncoded", rounds=1)
            victim = rec.plan.shard_clients[0][0]
            res = run_unlearn(sim, "NOOP-test", rec, [victim])
            assert res.framework == "NOOP-test"
            assert res.cost_units == 0.0
            assert res.impacted_shards == [0]
            _trees_equal(res.models[0], rec.shard_models[0])
        finally:
            FRAMEWORKS.pop("NOOP-test")


# ------------------------------------------------------------- round payload
class TestRoundPayload:
    def test_exactly_one_form(self):
        with pytest.raises(ValueError, match="exactly one"):
            RoundPayload(0, {0: [0]})
        with pytest.raises(ValueError, match="exactly one"):
            RoundPayload(0, {0: [0]}, client_params={0: {}},
                         stacked={0: {}})

    def test_flat_requires_row_spec(self):
        import jax.numpy as jnp
        with pytest.raises(ValueError, match="row_spec"):
            RoundPayload(0, {0: [0]}, flat={0: jnp.zeros((1, 4))})

    def test_flat_payload_has_no_client_trees(self):
        import jax.numpy as jnp
        p = RoundPayload.from_flat(0, {0: [0]}, {0: jnp.zeros((1, 4))},
                                   row_spec=object())
        with pytest.raises(ValueError, match="no per-client trees"):
            list(p.iter_client_trees())


# ---------------------------------------------------------------- StoreStats
class TestStoreStats:
    def test_merge_and_iadd(self):
        a = StoreStats(server_bytes=1, client_bytes=2, encode_flops=3,
                       decode_flops=4, comm_bytes_store=5,
                       comm_bytes_retrieve=6)
        b = StoreStats(server_bytes=10, client_bytes=20, encode_flops=30,
                       decode_flops=40, comm_bytes_store=50,
                       comm_bytes_retrieve=60)
        c = a + b                      # non-mutating
        assert (a.server_bytes, b.server_bytes) == (1, 10)
        assert c == StoreStats(11, 22, 33, 44, 55, 66)
        a += b                         # mutating accumulate
        assert a == c
        assert a.to_dict()["comm_bytes_retrieve"] == 66

    def test_snapshot_is_independent(self):
        a = StoreStats(server_bytes=7)
        s = a.snapshot()
        a.server_bytes = 99
        assert s.server_bytes == 7


# ----------------------------------------------------- multi-stage sessions
class TestMultiStageSession:
    N_STAGES = 3

    @pytest.fixture(scope="class")
    def scheduled(self):
        """Per-stage train_stage + the unlearn shim vs FederatedSession on
        identically-seeded sims, with an SE request interleaved after every
        stage."""
        sim_a, sim_b = _tiny_sim(), _tiny_sim()

        # --- reference: the single-stage API, stage by stage --------------
        records_a, unlearns_a, victims = [], [], []
        for k in range(self.N_STAGES):
            rec = train_stage(sim_a, store_kind="coded")
            records_a.append(rec)
            victim = rec.plan.shard_clients[k % rec.plan.num_shards][0]
            victims.append(victim)
            stage_results = {}
            for i, r in enumerate(records_a):
                if victim in set(r.plan.clients):
                    with pytest.warns(DeprecationWarning):
                        stage_results[i] = sim_a.unlearn("SE", r, [victim],
                                                         rounds=2)
            unlearns_a.append(stage_results)

        # --- session: same schedule, driven end-to-end --------------------
        schedule = RequestSchedule()
        for k, victim in enumerate(victims):
            schedule.add(UnlearnRequest([victim], framework="SE",
                                        after_stage=k, rounds=2))
        session = FederatedSession(sim_b, store_kind="coded")
        report = session.run(self.N_STAGES, schedule=schedule)
        return records_a, unlearns_a, victims, session, report

    def test_runs_three_stages(self, scheduled):
        records_a, _, _, session, report = scheduled
        assert len(session.records) == self.N_STAGES
        assert len(report.stages) == self.N_STAGES
        for rec_a, rec_b in zip(records_a, session.records):
            assert rec_a.plan.shard_clients == rec_b.plan.shard_clients

    def test_stage_models_match_single_stage_path(self, scheduled):
        records_a, _, _, session, _ = scheduled
        for rec_a, rec_b in zip(records_a, session.records):
            for s in rec_a.shard_models:
                _trees_equal(rec_a.shard_models[s], rec_b.shard_models[s])

    def test_only_impacted_shards_retrain(self, scheduled):
        """(a) per served request: the impacted set is exactly one shard
        (single-victim requests), and every other shard's model is
        bit-identical to the trained stage model (isolation)."""
        _, _, _, session, report = scheduled
        served = [(st.stage, u) for st in report.stages for u in st.unlearn]
        assert served                         # schedule actually fired
        for stage, res in served:
            rec = session.records[stage]
            assert len(res.impacted_shards) == 1
            (shard,) = res.impacted_shards
            assert set(res.models) == set(rec.shard_models)
            for s, model in rec.shard_models.items():
                if s != shard:
                    _trees_equal(res.models[s], model)

    def test_cross_stage_isolation_targets_only_member_stages(self, scheduled):
        """Request k (served after stage k) dispatches to exactly the
        completed stages whose plan contains its victim — no other stage's
        report gains an entry."""
        _, _, victims, session, report = scheduled
        for i, st in enumerate(report.stages):
            expected = sum(
                1 for k in range(self.N_STAGES)
                if k >= i
                and victims[k] in set(session.records[i].plan.clients))
            assert len(st.unlearn) == expected

    def test_coded_bytes_match_single_stage_path(self, scheduled):
        """(b) per stage, the session's coded-store accounting equals the
        single-stage shim path."""
        records_a, _, _, session, report = scheduled
        for rec_a, st in zip(records_a, report.stages):
            assert rec_a.store.stats.client_bytes == st.store_stats.client_bytes
            assert rec_a.store.stats.encode_flops == st.store_stats.encode_flops
            assert rec_a.store.stats.server_bytes == st.store_stats.server_bytes

    def test_session_unlearn_matches_shim(self, scheduled):
        """(c on SE) the session-served models are bit-identical to the
        per-stage shim calls.  Requests are served in schedule order, so
        stage i's unlearn list is [request k for k >= i hitting stage i]."""
        _, unlearns_a, _, session, report = scheduled
        for i, st in enumerate(report.stages):
            expected = [unlearns_a[k][i] for k in range(self.N_STAGES)
                        if i in unlearns_a[k]]
            assert len(st.unlearn) == len(expected)
            for res_a, res_b in zip(expected, st.unlearn):
                assert res_a.impacted_shards == res_b.impacted_shards
                assert res_a.cost_units == res_b.cost_units
                for s in res_a.models:
                    _trees_equal(res_a.models[s], res_b.models[s])

    def test_report_json_roundtrip(self, scheduled):
        *_, report = scheduled
        d = json.loads(report.to_json())
        assert d["num_stages"] == self.N_STAGES
        assert len(d["stages"]) == self.N_STAGES
        assert d["total_cost_units"] == report.total_cost_units
        merged = report.store_stats
        assert merged.client_bytes == sum(
            s.store_stats.client_bytes for s in report.stages)
        assert d["store_stats"]["client_bytes"] == merged.client_bytes


# ------------------------------------------------------- session semantics
class TestSessionSemantics:
    @pytest.fixture(scope="class")
    def session(self):
        s = FederatedSession(_tiny_sim(), store_kind="uncoded", rounds=2)
        s.run_stage()
        return s

    def test_session_rounds_override_flows_to_unlearn(self, session):
        """Stages trained with rounds=2 must unlearn with 2 rounds too —
        the session default used to be dropped, making FE index history
        norms for rounds that never ran."""
        victim = session.records[0].plan.shard_clients[0][0]
        res = session.unlearn(UnlearnRequest([victim], framework="FE"))[0]
        retained = len(session.records[0].plan.clients) - 1
        retrain_ep = max(int(FL_TINY.local_epochs / FL_TINY.retrain_ratio), 1)
        assert res.cost_units == 2 * retained * retrain_ep

    def test_apply_replaces_shard_models(self, session):
        rec = session.records[0]
        victim = rec.plan.shard_clients[0][0]
        before = rec.shard_models[0]
        session.unlearn(UnlearnRequest([victim], framework="SE", apply=True))
        assert rec.shard_models[0] is not before
        leaves_a = jax.tree.leaves(before)
        leaves_b = jax.tree.leaves(rec.shard_models[0])
        assert any(not np.array_equal(np.asarray(a), np.asarray(b))
                   for a, b in zip(leaves_a, leaves_b))

    def test_apply_rejects_federation_level_frameworks(self, session):
        victim = session.records[0].plan.shard_clients[0][0]
        with pytest.raises(ValueError, match="shard-level"):
            session.unlearn(UnlearnRequest([victim], framework="FR",
                                           apply=True, rounds=1))

    def test_explicit_out_of_range_stage_raises(self, session):
        victim = session.records[0].plan.shard_clients[0][0]
        with pytest.raises(ValueError, match="stage"):
            session.unlearn(UnlearnRequest([victim], stages=[5]))

    @pytest.mark.parametrize("engine", ["stage", "fused", "legacy", "turbo"])
    def test_engine_accepts_only_stage(self, engine):
        """Stage training has one path; ``engine`` may name only it."""
        if engine == "stage":
            s = FederatedSession(_tiny_sim(), engine=engine)
            assert s.run_stage() is s.records[0]
        else:
            with pytest.raises(ValueError, match="engine='stage'"):
                FederatedSession(_tiny_sim(), engine=engine)

    def test_stage_report_uses_session_local_index(self):
        """A session on a simulator that already trained stages still
        reports/routes by session-local index."""
        sim = _tiny_sim()
        train_stage(sim, store_kind="uncoded", rounds=1)   # mgr counter -> 1
        s = FederatedSession(sim, store_kind="uncoded", rounds=1)
        s.run_stage()
        assert s.report.stages[0].stage == 0
        assert s.report.stages[0].plan_stage == 1
        assert s.records[s.report.stages[0].stage] is s.records[0]


# ----------------------------------------------------- batched request serving
class TestBatchedRequests:
    """batch_requests=True merges the requests due after a stage: each
    impacted shard retrains once per batch (union-of-clients semantics) and
    the merged result equals one run_unlearn over the union."""

    def _schedule(self):
        return RequestSchedule([
            UnlearnRequest(lambda p: [p.shard_clients[0][0]], framework="SE",
                           after_stage=0, rounds=2),
            UnlearnRequest(lambda p: [p.shard_clients[1][0]], framework="SE",
                           after_stage=0, rounds=2),
        ])

    def test_batch_merges_compatible_requests(self):
        session = FederatedSession(_tiny_sim(), store_kind="coded",
                                   batch_requests=True)
        report = session.run(1, schedule=self._schedule())
        (st,) = report.stages
        assert len(st.unlearn) == 1                 # merged: one serve
        assert st.unlearn[0].impacted_shards == [0, 1]

    def test_batched_equals_union_request(self):
        s_bat, s_ref = _tiny_sim(), _tiny_sim()
        session = FederatedSession(s_bat, store_kind="coded",
                                   batch_requests=True)
        report = session.run(1, schedule=self._schedule())
        res_bat = report.stages[0].unlearn[0]
        rec = train_stage(s_ref, store_kind="coded")
        victims = [rec.plan.shard_clients[0][0], rec.plan.shard_clients[1][0]]
        res_ref = run_unlearn(s_ref, "SE", rec, victims, rounds=2)
        assert res_bat.cost_units == res_ref.cost_units
        assert res_bat.impacted_shards == res_ref.impacted_shards
        for s in res_ref.models:
            for a, b in zip(jax.tree.leaves(res_ref.models[s]),
                            jax.tree.leaves(res_bat.models[s])):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-5, atol=1e-6)

    def test_sequential_default_unchanged(self):
        session = FederatedSession(_tiny_sim(), store_kind="coded")
        report = session.run(1, schedule=self._schedule())
        assert len(report.stages[0].unlearn) == 2   # one serve per request

    def test_incompatible_options_stay_separate(self):
        schedule = RequestSchedule([
            UnlearnRequest(lambda p: [p.shard_clients[0][0]], framework="SE",
                           after_stage=0, rounds=2),
            UnlearnRequest(lambda p: [p.shard_clients[1][0]], framework="SE",
                           after_stage=0, rounds=1),
        ])
        session = FederatedSession(_tiny_sim(), store_kind="coded",
                                   batch_requests=True)
        report = session.run(1, schedule=schedule)
        assert len(report.stages[0].unlearn) == 2   # rounds differ: no merge

    def test_unlearn_batch_requires_stage(self):
        session = FederatedSession(_tiny_sim(), batch_requests=True)
        with pytest.raises(RuntimeError, match="no completed stages"):
            session.unlearn_batch([UnlearnRequest([0])])

    def test_scenario_config_batches(self):
        cfg = ScenarioConfig(num_clients=8, clients_per_round=8, num_shards=2,
                             local_epochs=2, global_rounds=2,
                             samples_per_client=30, image_size=8, test_n=50,
                             batch_requests=True,
                             schedule=RequestSchedule([
                                 UnlearnRequest(
                                     lambda p: [p.shard_clients[0][0]],
                                     framework="SE", after_stage=0, rounds=1),
                                 UnlearnRequest(
                                     lambda p: [p.shard_clients[1][0]],
                                     framework="SE", after_stage=0, rounds=1),
                             ]))
        report = run_scenario(cfg)
        (st,) = report.stages
        assert len(st.unlearn) == 1
        assert st.unlearn[0].impacted_shards == [0, 1]


# ------------------------------------------------- request edge cases
class TestRequestEdgeCases:
    @pytest.fixture(scope="class")
    def session(self):
        s = FederatedSession(_tiny_sim(), store_kind="coded", rounds=2)
        s.run_stage()
        return s

    def test_duplicate_client_ids_dedupe(self, session):
        """Duplicate ids in one request are a retry, not a double-erasure:
        resolution dedupes (order-preserving) and the served models equal
        the unique request's bit-for-bit."""
        victim = session.records[0].plan.shard_clients[0][0]
        dup = UnlearnRequest([victim, victim, victim], framework="SE")
        assert dup.resolve_clients(session.records[0].plan) == [victim]
        res_dup = session.unlearn(dup)[0]
        res_one = session.unlearn(UnlearnRequest([victim], framework="SE"))[0]
        assert res_dup.cost_units == res_one.cost_units
        assert res_dup.impacted_shards == res_one.impacted_shards
        for s in res_one.models:
            _trees_equal(res_dup.models[s], res_one.models[s])

    def test_callable_resolving_empty_serves_nothing(self, session):
        before = sum(len(st.unlearn) for st in session.report.stages)
        results = session.unlearn(UnlearnRequest(lambda plan: [],
                                                 framework="SE"))
        assert results == []
        after = sum(len(st.unlearn) for st in session.report.stages)
        assert after == before                     # report untouched

    def test_apply_with_batched_serving(self):
        """apply=True survives the batch merge: the union-serve's models
        land in the stage record for every impacted shard."""
        session = FederatedSession(_tiny_sim(), store_kind="coded",
                                   batch_requests=True, rounds=2)
        rec = session.run_stage()
        before = {s: rec.shard_models[s] for s in rec.shard_models}
        schedule = RequestSchedule([
            UnlearnRequest(lambda p: [p.shard_clients[0][0]], framework="SE",
                           after_stage=0, apply=True),
            UnlearnRequest(lambda p: [p.shard_clients[1][0]], framework="SE",
                           after_stage=0, apply=True),
        ])
        due = schedule.due(0)
        (res,) = session.unlearn_batch(due)
        assert res.impacted_shards == [0, 1]
        for s in (0, 1):
            assert rec.shard_models[s] is not before[s]
            _trees_equal(rec.shard_models[s], res.models[s])


# ------------------------------------------------- unserved-request loss
class TestUnservedRequests:
    def _session(self, **kw):
        return FederatedSession(_tiny_sim(), store_kind="uncoded", rounds=1,
                                **kw)

    def test_unserveable_request_warns(self):
        schedule = RequestSchedule([UnlearnRequest([0], after_stage=5,
                                                   rounds=1)])
        with pytest.warns(UserWarning, match="never served"):
            self._session().run(1, schedule=schedule)

    def test_strict_schedule_raises(self):
        schedule = RequestSchedule([UnlearnRequest([0], after_stage=5,
                                                   rounds=1)])
        with pytest.raises(ValueError, match="never served"):
            self._session(strict_schedule=True).run(1, schedule=schedule)

    def test_served_schedule_does_not_warn(self, recwarn):
        session = self._session(strict_schedule=True)
        schedule = RequestSchedule([UnlearnRequest(
            lambda p: [p.shard_clients[0][0]], after_stage=0, rounds=1)])
        report = session.run(1, schedule=schedule)
        assert sum(len(st.unlearn) for st in report.stages) == 1
        assert not [w for w in recwarn.list
                    if "never served" in str(w.message)]


# ---------------------------------------------- all frameworks, shim parity
class TestFrameworkShimParity:
    @pytest.fixture(scope="class")
    def fixture(self):
        sim = _tiny_sim()
        rec = train_stage(sim, store_kind="coded")
        return sim, rec

    @pytest.mark.parametrize("fw", ["SE", "FE", "FR", "RR"])
    def test_registry_matches_deprecated_unlearn(self, fixture, fw):
        """(c) every registered framework produces models bit-identical to
        the FLSimulator.unlearn shim on a fixed seed."""
        sim, rec = fixture
        victim = rec.plan.shard_clients[0][0]
        res_new = run_unlearn(sim, fw, rec, [victim], rounds=2)
        with pytest.warns(DeprecationWarning):
            res_old = sim.unlearn(fw, rec, [victim], rounds=2)
        assert res_old.impacted_shards == res_new.impacted_shards
        assert res_old.cost_units == res_new.cost_units
        assert set(res_old.models) == set(res_new.models)
        for s in res_old.models:
            _trees_equal(res_old.models[s], res_new.models[s])


# ------------------------------------------------------------ scenario runner
class TestScenarioRunner:
    def test_run_scenario_end_to_end(self):
        cfg = ScenarioConfig(num_clients=8, clients_per_round=8, num_shards=2,
                             local_epochs=2, global_rounds=2,
                             samples_per_client=30, image_size=8, test_n=50,
                             num_stages=2,
                             schedule=RequestSchedule([UnlearnRequest(
                                 lambda plan: [plan.shard_clients[0][0]],
                                 framework="SE", after_stage=1, rounds=1)]))
        report = run_scenario(cfg)
        assert len(report.stages) == 2
        served = [u for st in report.stages for u in st.unlearn]
        assert served and all(u.framework == "SE" for u in served)
        assert report.total_cost_units > 0
        json.loads(report.to_json())

    def test_build_session_store_kind(self):
        cfg = ScenarioConfig(num_clients=8, clients_per_round=8, num_shards=2,
                             local_epochs=2, global_rounds=2,
                             samples_per_client=30, image_size=8, test_n=50,
                             store="uncoded")
        session, test = build_session(cfg)
        assert session.store_kind == "uncoded"
        assert test[0].shape[0] == 50
