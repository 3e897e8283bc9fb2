"""Chaos-harness tests: deterministic fault injection, coded quorum-read
recovery, service retry/backoff/abort, and degraded-mode training.

Property-style anchors (the PR's acceptance criteria):

* faults within the code's budget (<= ``max_errors`` corruptions, erasures
  leaving >= S slices) recover — *bit-identically* when they spare the
  canonical ``CodingScheme.quorum()`` read subset;
* faults beyond the budget fail loudly with the typed
  ``CodingBudgetExceeded`` (never a silent mis-decode);
* a chaotic serve completes with models bit-identical to the fault-free
  serve while ``ServiceReport``/``StoreStats`` record nonzero
  recoveries/retries, and replaying the same plan seed reproduces the
  identical fault ledger.

The fault seed is env-overridable (``REPRO_FAULT_SEED``) so the CI chaos
job can pin it explicitly.
"""
import dataclasses
import json
import os
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.stores.store import CodedStore, RoundPayload, StoreStats
from repro.configs import FLConfig, OptimizerConfig, get_config
from repro.core import coding
from repro.core.coding import CodingBudgetExceeded, CodingScheme
from repro.data import client_datasets_images, make_image_data
from repro.faults import (INJECTORS, DegradedModeEvent, FaultInjector,
                          FaultLedger, FaultPlan, RecoveryEvent,
                          TransientJobError, chaos_plan, make_injector,
                          register_injector)
from repro.fl import FLSimulator
from repro.fl.experiment import FederatedSession
from repro.service import (DevicePlacement, LedgerEntry, RetryPolicy,
                           ServiceReport, UnlearningService, sequenced_trace,
                           single_device_placement)

FAULT_SEED = int(os.environ.get("REPRO_FAULT_SEED", "7"))

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


def _scheme(c=12, s=4):
    return CodingScheme(num_shards=s, num_clients=c)


def _coded(c=12, s=4, p=33, seed=0):
    sch = _scheme(c, s)
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.standard_normal((s, p)), jnp.float32)
    return sch, w, coding.encode(sch, w)


# ---------------------------------------------------------------- coding
class TestQuorumRecovery:
    def test_quorum_is_the_decode_subset(self):
        sch = _scheme()
        q = sch.quorum()
        assert len(q) == sch.num_shards
        assert set(int(i) for i in q) <= set(range(sch.num_clients))
        _, ids = sch.decode_matrix(list(range(sch.num_clients)))
        assert list(q) == [int(i) for i in ids]

    def test_erasure_sparing_quorum_is_bit_identical(self):
        sch, w, slices = _coded()
        w0 = coding.decode_erasure(sch, slices, list(range(12)))
        spare = [i for i in range(12) if i not in set(sch.quorum())][:3]
        avail = [i for i in range(12) if i not in spare]
        w1, lost, bad = coding.decode_robust(sch, slices, available=avail)
        assert lost == spare and bad == []
        np.testing.assert_array_equal(np.asarray(w0), np.asarray(w1))

    def test_corruption_sparing_quorum_is_bit_identical(self):
        sch, w, slices = _coded()
        w0 = coding.decode_erasure(sch, slices, list(range(12)))
        hit = [i for i in range(12) if i not in set(sch.quorum())][:2]
        sl = slices.at[jnp.asarray(hit)].add(10.0)
        w1, lost, bad = coding.decode_robust(sch, sl)
        assert lost == [] and bad == hit
        np.testing.assert_array_equal(np.asarray(w0), np.asarray(w1))

    def test_corruption_hitting_quorum_still_recovers(self):
        sch, w, slices = _coded()
        hit = int(sch.quorum()[0])
        sl = slices.at[hit].add(10.0)
        w1, lost, bad = coding.decode_robust(sch, sl)
        assert bad == [hit]
        np.testing.assert_allclose(np.asarray(w1), np.asarray(w),
                                   atol=1e-3, rtol=1e-3)

    def test_combined_erasure_and_corruption_reduced_scheme(self):
        # 2 erased + 2 corrupted on C=12, S=4: the reduced (S=4, C=10) code
        # still has budget (10-4)//2 = 3 >= 2
        sch, w, slices = _coded()
        others = [i for i in range(12) if i not in set(sch.quorum())]
        lost_t, bad_t = others[:2], others[2:4]
        sl = slices.at[jnp.asarray(bad_t)].add(10.0)
        avail = [i for i in range(12) if i not in lost_t]
        w1, lost, bad = coding.decode_robust(sch, sl, available=avail)
        assert lost == lost_t and bad == bad_t
        w0 = coding.decode_erasure(sch, slices, list(range(12)))
        np.testing.assert_array_equal(np.asarray(w0), np.asarray(w1))

    def test_reduced_scheme_budget_tightens(self):
        sch = _scheme(12, 4)
        assert sch.max_errors == 4
        assert sch.reduced(range(8)).max_errors == 2
        assert sch.reduced(range(4)).max_errors == 0


class TestCodingBudgetExceeded:
    def test_locate_errors_names_budget_and_observed(self):
        sch, w, slices = _coded(10, 4)        # max_errors = 3
        sl = np.asarray(slices, np.float64)
        sl[:4] += 10.0
        with pytest.raises(CodingBudgetExceeded,
                           match=r"count 4 exceeds the correctable budget "
                                 r"max_errors=3") as ei:
            coding.locate_errors(sch, sl)
        assert ei.value.observed == 4 and ei.value.max_errors == 3

    def test_decode_with_errors_budget_raise(self):
        sch, w, slices = _coded(10, 4)
        sl = slices + jnp.where(jnp.arange(10)[:, None] < 4, 10.0, 0.0)
        with pytest.raises(CodingBudgetExceeded, match="max_errors=3"):
            coding.decode_with_errors(sch, sl)

    def test_too_few_available_raises_erasure_kind(self):
        sch, w, slices = _coded(12, 4)
        with pytest.raises(CodingBudgetExceeded,
                           match="erased slices count 9"):
            coding.decode_robust(sch, slices, available=[0, 1, 2])

    def test_zero_budget_scheme_detects_corruption(self):
        # C = S + 1: corruption is detectable (one redundant point) but
        # max_errors = 0 — the read must fail loudly, never mis-decode.
        # (At C == S every vector is a codeword; corruption is invisible.)
        sch, w, slices = _coded(5, 4)
        sl = slices.at[2].add(10.0)
        with pytest.raises(CodingBudgetExceeded, match="max_errors=0"):
            coding.decode_robust(sch, sl)

    def test_within_budget_does_not_raise(self):
        sch, w, slices = _coded(10, 4)
        sl = slices.at[jnp.asarray([1, 5, 8])].add(10.0)
        w1, bad = coding.decode_with_errors(sch, sl)
        assert list(bad) == [1, 5, 8]


# ------------------------------------------------------------- fault plans
class TestFaultPlanRegistry:
    def test_builtin_injectors_registered(self):
        for name in ("client_dropout", "straggler", "slice_erasure",
                     "slice_corruption", "device_failure", "device_hang",
                     "job_exception"):
            assert name in INJECTORS

    def test_unknown_injector_raises(self):
        with pytest.raises(ValueError, match="unknown fault injector"):
            make_injector("nope")

    def test_custom_injector_registers(self):
        @register_injector("_test_noop")
        class _Noop(FaultInjector):
            pass
        assert isinstance(make_injector("_test_noop"), _Noop)

    def test_chaos_plan_builder(self):
        plan = chaos_plan(seed=3, corrupt=1, erase=1, job_rate=0.5,
                          dead_device=0, dropout=0.1)
        names = [i.name for i in plan.injectors]
        assert names == ["slice_corruption", "slice_erasure",
                         "job_exception", "device_failure", "client_dropout"]
        assert plan.describe()["seed"] == 3


class TestFaultPlanDeterminism:
    def test_site_rng_is_pure_function_of_seed_and_site(self):
        a = FaultPlan(seed=FAULT_SEED).rng("x", 1, (2, 3)).random(4)
        b = FaultPlan(seed=FAULT_SEED).rng("x", 1, (2, 3)).random(4)
        c = FaultPlan(seed=FAULT_SEED).rng("x", 2, (2, 3)).random(4)
        np.testing.assert_array_equal(a, b)
        assert not np.array_equal(a, c)

    def test_slice_faults_replay_identically(self):
        sch = _scheme()
        p1 = FaultPlan(seed=FAULT_SEED).add("slice_corruption", count=2)
        p2 = FaultPlan(seed=FAULT_SEED).add("slice_corruption", count=2)
        l1, n1 = p1.slice_faults(3, sch, width=7)
        l2, n2 = p2.slice_faults(3, sch, width=7)
        assert l1 == l2 and sorted(n1) == sorted(n2)
        for r in n1:
            np.testing.assert_array_equal(n1[r], n2[r])
        # and a second read of the SAME round sees the SAME fault
        l3, n3 = p1.slice_faults(3, sch, width=7)
        assert l3 == l1 and sorted(n3) == sorted(n1)

    def test_spare_quorum_never_hits_the_read_set(self):
        sch = _scheme()
        q = set(int(i) for i in sch.quorum())
        plan = FaultPlan(seed=FAULT_SEED).add("slice_erasure", count=3)
        for rnd in range(20):
            lost, _ = plan.slice_faults(rnd, sch, width=5)
            assert not (set(lost) & q)

    def test_job_exception_is_job_keyed_not_attempt_keyed(self):
        plan = FaultPlan(seed=FAULT_SEED).add("job_exception", rate=1.0,
                                              fail_attempts=2)
        key = ("shard", 0, 1, (5,))
        _, e1 = plan.job_action(key, 1, device=0)
        _, e2 = plan.job_action(key, 2, device=3)   # other device, same job
        _, e3 = plan.job_action(key, 3, device=0)   # beyond fail_attempts
        assert isinstance(e1, TransientJobError)
        assert isinstance(e2, TransientJobError)
        assert e3 is None

    def test_straggler_delays_first_attempt_only(self):
        plan = FaultPlan(seed=FAULT_SEED).add("straggler", rate=1.0,
                                              delay_s=0.5)
        d1, e1 = plan.job_action(("j",), 1, device=0)
        d2, e2 = plan.job_action(("j",), 2, device=0)
        assert d1 == 0.5 and e1 is None
        assert d2 == 0.0 and e2 is None

    def test_ledger_signature_is_thread_order_independent(self):
        ev = [RecoveryEvent("retry", site=("j", i)) for i in range(5)]
        a, b = FaultLedger(), FaultLedger()
        for e in ev:
            a.record(e)
        for e in reversed(ev):
            b.record(e)
        assert a.signature() == b.signature()
        assert a.count("retry") == 5 and a.kinds() == {"retry": 5}

    def test_client_dropout_keeps_min_keep(self):
        plan = FaultPlan(seed=FAULT_SEED).add("client_dropout", rate=1.0,
                                              min_keep=1)
        shard_clients = {0: [1, 2, 3], 1: [4, 5]}
        dropped = plan.dropped_clients(0, shard_clients)
        assert len(dropped[0]) == 2 and len(dropped[1]) == 1


# ------------------------------------------------------------ coded store
class TestCodedStoreQuorumReads:
    def _store(self, plan=None, c=12, s=4):
        sch = _scheme(c, s)
        per = c // s
        shard_clients = {i: list(range(i * per, (i + 1) * per))
                         for i in range(s)}
        store = CodedStore(sch, shard_clients)
        rng = np.random.default_rng(1)
        params = {cl: {"w": jnp.asarray(rng.standard_normal(5), jnp.float32)}
                  for cl in range(c)}
        store.put_round(RoundPayload.from_clients(0, shard_clients, params))
        if plan is not None:
            store.attach_faults(plan)
        return store

    def test_faulted_read_is_bit_identical_and_accounted(self):
        base = self._store().get_shard(0, 1)
        plan = FaultPlan(seed=FAULT_SEED).add("slice_corruption", count=2)
        store = self._store(plan)
        got = store.get_shard(0, 1)
        for cl in base:
            _trees_equal(base[cl], got[cl])
        assert store.stats.reads == 1
        assert store.stats.recovered_reads == 1
        assert store.stats.corrupted_slices == 2
        assert plan.ledger.count("quorum_read") == 1

    def test_erasure_plan_recovers(self):
        base = self._store().get_shard(0, 0)
        plan = FaultPlan(seed=FAULT_SEED).add("slice_erasure", count=3)
        store = self._store(plan)
        got = store.get_shard(0, 0)
        for cl in base:
            _trees_equal(base[cl], got[cl])
        assert store.stats.erased_slices == 3

    def test_budget_exceeded_read_fails_typed_and_counted(self):
        # C=8, S=4: max_errors = 2 but 3 slices corrupted
        plan = FaultPlan(seed=FAULT_SEED).add("slice_corruption", count=3,
                                              spare_quorum=False)
        store = self._store(plan, c=8, s=4)
        with pytest.raises(CodingBudgetExceeded):
            store.get_shard(0, 0)
        assert store.stats.failed_reads == 1

    def test_legacy_available_and_corrupt_args_still_work(self):
        store = self._store()
        base = store.get_shard(0, 1)
        q = set(int(i) for i in store.scheme.quorum())
        avail = [i for i in range(12) if i in q or i % 2 == 0]
        got = store.get_shard(0, 1, available=avail)
        for cl in base:
            _trees_equal(base[cl], got[cl])
        noise = np.zeros((12, store._slices[0].shape[1]), np.float32)
        noise[1] = 25.0
        got2 = store.get_shard(0, 1, corrupt=noise)
        for cl in base:
            np.testing.assert_allclose(
                np.asarray(got2[cl]["w"]), np.asarray(base[cl]["w"]),
                atol=1e-3)

    def test_concurrent_reads_decode_identically(self):
        """Satellite: corrupt one slice while two threads read the same
        shard through the RLock path — both must decode identically."""
        plan = FaultPlan(seed=FAULT_SEED).add("slice_corruption", count=1)
        store = self._store(plan)
        base = self._store().get_shard(0, 2)
        barrier = threading.Barrier(2)
        results, errors = [None, None], []

        def read(i):
            try:
                barrier.wait(timeout=10)
                results[i] = store.get_shard(0, 2)
            except Exception as exc:          # pragma: no cover
                errors.append(exc)

        threads = [threading.Thread(target=read, args=(i,)) for i in (0, 1)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not errors
        for cl in base:
            _trees_equal(results[0][cl], results[1][cl])
            _trees_equal(base[cl], results[0][cl])
        assert store.stats.reads == 2
        assert store.stats.recovered_reads == 2   # same injected fault twice

    def test_stats_merge_includes_recovery_counters(self):
        a = StoreStats(reads=2, recovered_reads=1, erased_slices=3,
                       corrupted_slices=1, failed_reads=0)
        b = StoreStats(reads=1, failed_reads=2)
        c = a + b
        assert (c.reads, c.recovered_reads, c.failed_reads) == (3, 1, 2)


# -------------------------------------------------------------- placement
class TestPlacementSatellites:
    def test_context_manager_shuts_down_pool(self):
        with DevicePlacement(max_workers=1) as p:
            assert p.submit(lambda: 41 + 1).result() == 42
            assert p._pool is not None
        assert p._pool is None

    def test_shutdown_is_idempotent(self):
        p = DevicePlacement(max_workers=1)
        p.submit(lambda: None).result()
        p.shutdown()
        p.shutdown()                      # second call is a clean no-op
        assert p._pool is None

    def test_exit_shuts_down_even_when_body_raises(self):
        p = DevicePlacement(max_workers=1)
        with pytest.raises(RuntimeError, match="boom"):
            with p:
                p.submit(lambda: None).result()
                raise RuntimeError("boom")
        assert p._pool is None

    def test_reassign_skips_unhealthy_deterministically(self):
        p = DevicePlacement(devices=[object(), object(), object()])
        p.mark_unhealthy(1)
        assert p.reassign(0) == 2         # 1 is skipped
        assert p.reassign(1) == 2
        assert p.describe()["unhealthy"] == [1]
        # every device down: never raises, returns the avoided index
        p.mark_unhealthy(0)
        p.mark_unhealthy(2)
        assert p.reassign(0) == 0
        p.reset_health()
        assert p.reassign(0) == 1
        assert p.describe()["unhealthy"] == []

    def test_assign_stays_round_robin_under_faults(self):
        p = DevicePlacement(devices=[object(), object()])
        p.mark_unhealthy(0)
        assert [p.assign() for _ in range(4)] == [0, 1, 0, 1]


# ---------------------------------------------------------- report guards
class TestServiceReportGuards:
    def test_empty_report_never_raises(self):
        rep = ServiceReport()
        assert np.isnan(rep.percentile(50))
        assert np.isnan(rep.p95)
        assert np.isnan(rep.throughput)
        assert rep.sla_hit_rate is None
        assert rep.num_aborted == 0
        json.dumps(rep.to_dict())         # serializable end to end

    def test_all_aborted_ledger_guards(self):
        rep = ServiceReport(serve_wall=1.0)
        rep.entries = [LedgerEntry(rid=i, arrival=0.0, clients=(i,),
                                   framework="SE", batch_id=0, latency=1.0,
                                   aborted=True) for i in range(3)]
        assert rep.completed == []
        assert np.isnan(rep.p50)
        assert np.isnan(rep.throughput)
        assert rep.sla_hit_rate is None
        assert rep.num_aborted == 3
        assert rep.to_dict()["num_aborted"] == 3

    def test_completed_entries_keep_finite_aggregates(self):
        rep = ServiceReport(serve_wall=2.0)
        rep.entries = [
            LedgerEntry(rid=0, arrival=0.0, clients=(0,), framework="SE",
                        batch_id=0, latency=1.0, sla_met=True),
            LedgerEntry(rid=1, arrival=0.0, clients=(1,), framework="SE",
                        batch_id=0, latency=3.0, aborted=True),
        ]
        assert rep.percentile(50) == 1.0  # aborted entry excluded
        assert rep.throughput == 0.5
        assert rep.sla_hit_rate == 1.0

    def test_retry_policy_backoff_is_bounded(self):
        rp = RetryPolicy(backoff=0.1, backoff_factor=2.0, max_backoff=0.35)
        assert rp.backoff_for(1) == pytest.approx(0.1)
        assert rp.backoff_for(2) == pytest.approx(0.2)
        assert rp.backoff_for(3) == pytest.approx(0.35)
        assert rp.backoff_for(9) == pytest.approx(0.35)


# ----------------------------------------------------- degraded training
class TestDegradedTraining:
    def test_dropout_degrades_stage_engine_with_event(self):
        """Dropout makes the stage ragged: training falls back to the
        per-shard round and records it, instead of warning."""
        @register_injector("_test_drop_first_of_shard0")
        class _DropOne(FaultInjector):
            def stage_dropout(self, plan, stage, shard_clients):
                s = sorted(shard_clients)[0]
                return {s: [shard_clients[s][0]]}

        plan = FaultPlan(seed=FAULT_SEED).add("_test_drop_first_of_shard0")
        sess = FederatedSession(_tiny_sim(), store_kind="coded", faults=plan)
        record = sess.run_stage()
        sizes = sorted(len(cs) for cs in record.plan.shard_clients.values())
        assert sizes == [3, 4]            # one client gone -> ragged stage
        degraded = [e for e in plan.ledger.events
                    if isinstance(e, DegradedModeEvent)]
        assert len(degraded) == 1
        assert degraded[0].fallback == "per_shard"
        assert degraded[0].reason == "ragged_stage"
        assert len(degraded[0].dropped_clients) == 1
        assert plan.ledger.count("client_dropout") == 1
        # training still lands a full record: every shard has a model
        assert set(record.shard_models) == set(record.plan.shard_clients)

    def test_seeded_dropout_replays_identically(self):
        shard_clients = {0: [1, 2, 3, 4], 1: [5, 6, 7, 8]}
        d1 = FaultPlan(seed=FAULT_SEED).add(
            "client_dropout", rate=0.5).dropped_clients(2, shard_clients)
        d2 = FaultPlan(seed=FAULT_SEED).add(
            "client_dropout", rate=0.5).dropped_clients(2, shard_clients)
        assert d1 == d2


# --------------------------------------------------------- chaotic serves
@pytest.fixture(scope="module")
def trained_session():
    sess = FederatedSession(_tiny_sim(), store_kind="coded")
    sess.run_stage()
    return sess


def _serve(session, plan, trace=None, retry=None):
    svc = UnlearningService(session, policy="fifo",
                            placement=single_device_placement(),
                            faults=plan,
                            retry=retry or RetryPolicy(backoff=0.001))
    trace = trace or sequenced_trace([session.records[0].plan.clients[0]],
                                     spacing=0.1)
    try:
        report = svc.serve(trace)
    finally:
        svc.placement.shutdown()
        for rec in session.records:       # detach for the next scenario
            if hasattr(rec.store, "attach_faults"):
                rec.store.attach_faults(None)
    models = {s: jax.device_get(w) for s, w in
              session.report.stages[0].unlearn[-1].models.items()}
    return report, models


def _chaotic_plan():
    return (FaultPlan(seed=FAULT_SEED)
            .add("slice_corruption", count=2, scale=10.0)
            .add("job_exception", rate=1.0, fail_attempts=1))


class TestChaoticServe:
    def test_chaotic_serve_bit_identical_with_nonzero_recoveries(
            self, trained_session):
        """Acceptance anchor: <= max_errors corruptions + transient job
        failures -> the served trace completes with models bit-identical to
        the fault-free serve, and the report records the recovery work."""
        rep0, m0 = _serve(trained_session, None)
        rep1, m1 = _serve(trained_session, _chaotic_plan())
        assert set(m0) == set(m1)
        for s in m0:
            _trees_equal(m0[s], m1[s])
        assert rep1.faults["retries"] > 0
        assert rep1.faults["recoveries"] > 0
        assert rep1.faults["aborts"] == 0
        assert all(e.job_retries > 0 and not e.aborted for e in rep1.entries)
        assert rep0.faults["retries"] == 0 and rep0.faults["recoveries"] == 0

    def test_same_seed_replays_identical_ledger(self, trained_session):
        p1, p2 = _chaotic_plan(), _chaotic_plan()
        _serve(trained_session, p1)
        _serve(trained_session, p2)
        sig1, sig2 = p1.ledger.signature(), p2.ledger.signature()
        assert sig1 and sig1 == sig2
        other = (FaultPlan(seed=FAULT_SEED + 1)
                 .add("slice_corruption", count=2, scale=10.0)
                 .add("job_exception", rate=1.0, fail_attempts=1))
        _serve(trained_session, other)
        assert other.ledger.signature() != sig1

    def test_retry_budget_exhaustion_aborts_cleanly(self, trained_session):
        plan = FaultPlan(seed=FAULT_SEED).add("job_exception", rate=1.0,
                                              fail_attempts=99)
        rep, _m = _serve(trained_session, plan,
                         retry=RetryPolicy(max_retries=1, backoff=0.001))
        assert rep.faults["aborts"] > 0
        assert all(e.aborted for e in rep.entries)
        assert rep.num_aborted == len(rep.entries)
        assert np.isnan(rep.p50) and np.isnan(rep.throughput)
        assert plan.ledger.count("abort") > 0
        assert plan.ledger.count("retry") > 0

    def test_report_json_roundtrips_with_fault_summary(self, trained_session):
        rep, _m = _serve(trained_session, _chaotic_plan())
        d = json.loads(rep.to_json())
        assert d["faults"]["retries"] >= 1
        assert d["faults"]["recoveries"] >= 1
        assert d["requests"]["svc-0"]["job_attempts"] >= 2
        assert d["num_aborted"] == 0


# ------------------------------------------------- device-kill (4 devices)
class TestDeviceFailureMultiDevice:
    def test_device_kill_mid_serve_all_requests_complete(self):
        """Kill one of 4 virtual devices: every request still completes
        with models matching the healthy serve, the dead device is marked
        unhealthy, and retries re-dispatch deterministically.  Subprocess
        because XLA_FLAGS must be set before jax initializes."""
        env = dict(os.environ)
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=4")
        env["PYTHONPATH"] = os.pathsep.join(
            p for p in ["src", env.get("PYTHONPATH", "")] if p)
        env.setdefault("REPRO_FAULT_SEED", str(FAULT_SEED))
        child = os.path.join(os.path.dirname(__file__),
                             "_faults_chaos_child.py")
        proc = subprocess.run(
            [sys.executable, child], env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(child))),
            capture_output=True, text=True, timeout=560)
        assert proc.returncode == 0, proc.stderr[-2000:]
        out = json.loads(proc.stdout.strip().splitlines()[-1])
        assert out["num_devices"] == 4
        assert out["models_bit_identical"]
        assert out["aborts"] == 0
        assert out["retries"] > 0
        assert out["unhealthy"] == [0]
        assert out["ledger_replay_identical"]
