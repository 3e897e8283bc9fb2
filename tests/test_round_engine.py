"""Stage training's device-resident paths must be numerically equivalent to
the per-client references they replace — stacked flatten vs per-tree
flatten, batched vs per-round encode, the one-dispatch stage program vs the
per-shard round of a ragged stage, stacked vs sequential calibration, and
the bf16 / grouped-encode store options."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.stores.store import CodedStore, FullStore, RoundPayload
from repro.configs import FLConfig, OptimizerConfig, get_config
from repro.core import coding, unlearning
from repro.data import client_datasets_images, make_image_data
from repro.fl import FLSimulator
from repro.fl.experiment import (ScenarioConfig, build_simulator, run_unlearn,
                                 train_stage)
from repro.fl.experiment.stage import _run_per_shard


def _stacked_tree(m=5, seed=0):
    k = jax.random.key(seed)
    ks = jax.random.split(k, 3)
    return {
        "conv": {"w": jax.random.normal(ks[0], (m, 3, 3, 4), jnp.float32)},
        "dense": {"w": jax.random.normal(ks[1], (m, 7, 5), jnp.float32),
                  "b": jax.random.normal(ks[2], (m, 5), jnp.float32)},
    }


# ------------------------------------------------------------ flatten paths
class TestStackedFlatten:
    def test_rows_match_per_tree_flatten(self):
        stacked = _stacked_tree(m=5)
        flat, spec = coding.tree_to_flat_stacked(stacked)
        assert flat.shape[0] == 5
        for i in range(5):
            tree_i = jax.tree.map(lambda a, i=i: a[i], stacked)
            fi, spec_i = coding.tree_to_flat(tree_i)
            np.testing.assert_array_equal(np.asarray(flat[i]), np.asarray(fi))
            # per-row spec reassembles exactly like the per-tree spec
            back = coding.flat_to_tree(flat[i], spec)
            for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(tree_i)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_stacked_roundtrip(self):
        stacked = _stacked_tree(m=4, seed=1)
        flat, spec = coding.tree_to_flat_stacked(stacked)
        back = coding.flat_to_stacked_tree(flat, spec)
        for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(stacked)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_traceable_under_jit(self):
        stacked = _stacked_tree(m=3, seed=2)
        f_eager, _ = coding.tree_to_flat_stacked(stacked)
        f_jit = jax.jit(lambda t: coding.tree_to_flat_stacked(t)[0])(stacked)
        np.testing.assert_array_equal(np.asarray(f_eager), np.asarray(f_jit))


# ------------------------------------------------------------ batched encode
class TestBatchedEncode:
    def test_equals_per_round_encode(self):
        sch = coding.CodingScheme(num_shards=4, num_clients=16)
        rng = np.random.default_rng(0)
        mats = [jnp.asarray(rng.standard_normal((4, 257)), jnp.float32)
                for _ in range(5)]
        batched = coding.encode_batched(sch, mats)
        assert len(batched) == 5
        for m, b in zip(mats, batched):
            np.testing.assert_allclose(np.asarray(b),
                                       np.asarray(coding.encode(sch, m)),
                                       rtol=1e-6, atol=1e-6)

    def test_kernel_path(self):
        sch = coding.CodingScheme(num_shards=3, num_clients=12)
        rng = np.random.default_rng(1)
        mats = [jnp.asarray(rng.standard_normal((3, 100)), jnp.float32)
                for _ in range(3)]
        ref = coding.encode_batched(sch, mats)
        krn = coding.encode_batched(sch, mats, use_kernel=True)
        for a, b in zip(ref, krn):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-5)

    def test_bf16_storage(self):
        sch = coding.CodingScheme(num_shards=4, num_clients=16)
        rng = np.random.default_rng(2)
        w = jnp.asarray(rng.standard_normal((4, 512)), jnp.float32)
        sl = coding.encode(sch, w, out_dtype=jnp.bfloat16)
        assert sl.dtype == jnp.bfloat16
        out = coding.decode_erasure(sch, sl[jnp.asarray([0, 5, 10, 15])],
                                    [0, 5, 10, 15])
        assert out.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(out), np.asarray(w),
                                   rtol=5e-2, atol=5e-2)


# ------------------------------------------------------- fused encode-decode
class TestEncodeDecodeFused:
    def test_matches_two_pass_and_identity(self):
        sch = coding.CodingScheme(num_shards=4, num_clients=20)
        rng = np.random.default_rng(3)
        w = jnp.asarray(rng.standard_normal((4, 321)), jnp.float32)
        out_jnp = coding.encode_decode(sch, w)
        out_krn = coding.encode_decode(sch, w, use_kernel=True)
        np.testing.assert_allclose(np.asarray(out_jnp), np.asarray(out_krn),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(out_krn), np.asarray(w),
                                   rtol=1e-3, atol=1e-3)

    def test_subset_ids(self):
        sch = coding.CodingScheme(num_shards=3, num_clients=15)
        rng = np.random.default_rng(4)
        w = jnp.asarray(rng.standard_normal((3, 64)), jnp.float32)
        ids = [2, 6, 9, 14]
        out = coding.encode_decode(sch, w, client_ids=ids, use_kernel=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(w),
                                   rtol=2e-3, atol=2e-3)


# -------------------------------------------------------- stacked calibrate
class TestCalibrateStacked:
    def _setup(self, m=4, seed=0):
        k = jax.random.key(seed)
        ks = jax.random.split(k, 3)
        w = {"a": jax.random.normal(ks[0], (9, 4), jnp.float32),
             "b": jax.random.normal(ks[1], (11,), jnp.float32)}
        stacked = {"a": jax.random.normal(ks[2], (m, 9, 4), jnp.float32),
                   "b": jax.random.normal(jax.random.fold_in(k, 7), (m, 11),
                                          jnp.float32)}
        norms = jnp.asarray(np.random.default_rng(seed).uniform(0.5, 2.0, m),
                            jnp.float32)
        return w, stacked, norms

    def test_matches_sequential_calibrate(self):
        w, stacked, norms = self._setup()
        m = norms.shape[0]
        per_client = [jax.tree.map(lambda a, i=i: a[i], stacked)
                      for i in range(m)]
        # eq (3) reference: sequential per-client accumulation, with stored
        # deltas synthesized to have exactly the stored norms
        stored = [jax.tree.map(lambda a: a * 0, per_client[0]) for _ in range(m)]
        stored = [unlearning.tree_add(s, {"a": jnp.zeros((9, 4)).at[0, 0].set(n),
                                          "b": jnp.zeros(11)})
                  for s, n in zip(stored, np.asarray(norms))]
        ref = unlearning.calibrate(w, per_client, stored)
        out = unlearning.calibrate_stacked(w, stacked, norms)
        for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(out)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-5)

    def test_kernel_path_matches(self):
        w, stacked, norms = self._setup(seed=1)
        out = unlearning.calibrate_stacked(w, stacked, norms)
        krn = unlearning.calibrate_stacked(w, stacked, norms, use_kernel=True)
        for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(krn)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)

    def test_matches_simulator_host_loop(self):
        """calibrate_stacked == the seed _calibrate_with_norms host loop."""
        w, stacked, norms = self._setup(seed=2)
        m = norms.shape[0]
        per_client = [jax.tree.map(lambda a, i=i: a[i], stacked)
                      for i in range(m)]
        out = unlearning.calibrate_stacked(w, stacked, norms)
        ref = w
        for nd, sn in zip(per_client, np.asarray(norms)):
            ratio = float(sn) / max(float(unlearning.tree_norm(nd)), 1e-12)
            ref = unlearning.tree_add(ref, unlearning.tree_scale(nd, ratio / m))
        for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(out)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-5)


# ------------------------------------------------- end-to-end stage training
FL_TINY = FLConfig(num_clients=8, clients_per_round=8, num_shards=2,
                   local_epochs=2, global_rounds=3, retrain_ratio=2.0)


def _tiny_sim():
    cfg = dataclasses.replace(get_config("cnn-paper"), image_size=8,
                              d_model=16, cnn_channels=(4, 4))
    data = make_image_data(8 * 30, image_size=8, seed=0)
    clients = client_datasets_images(data, FL_TINY.num_clients, iid=True)
    return FLSimulator(cfg, FL_TINY, clients, task="image",
                       opt_cfg=OptimizerConfig(name="sgdm", lr=0.05,
                                               grad_clip=0.0),
                       local_batch=10)


def _per_shard_stage(sim, store_kind="coded"):
    """``train_stage``'s set-up, then the per-shard round directly — on a
    stage that would stack, the reference for the stage program."""
    plan = sim.mgr.new_stage()
    w0 = sim.init_params(jax.random.key(sim.seed + plan.stage))
    g_rounds = sim.fl.global_rounds
    store = sim._make_store(store_kind, plan, group_rounds=g_rounds)
    kind = "flat" if store.wants == "flat" else "stacked"
    data = {s: sim._stack_client_data(cs)
            for s, cs in plan.shard_clients.items()}
    return _run_per_shard(sim, plan, store, w0, data, g_rounds, kind)


class TestStageProgramVsPerShard:
    """The stage program (scan-over-rounds x vmap-over-shards + in-program
    Lagrange encode) must reproduce the per-shard round of a ragged stage on
    a stage that stacks: shard models, stored coded slices, history norms,
    store accounting, unlearning — and its lazy round-globals view must
    behave like the materialized lists."""

    @pytest.fixture(scope="class")
    def records(self):
        s_per, s_stg = _tiny_sim(), _tiny_sim()
        return (_per_shard_stage(s_per), train_stage(s_stg), s_stg, s_per)

    def test_shard_models_bit_for_bit(self, records):
        r_per, r_stg, *_ = records
        assert r_per.plan.shard_clients == r_stg.plan.shard_clients
        for s in r_per.shard_models:
            for a, b in zip(jax.tree.leaves(r_per.shard_models[s]),
                            jax.tree.leaves(r_stg.shard_models[s])):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_coded_slices_match(self, records):
        """In-program einsum encode vs the store's batched matmul encode —
        bit-identical on CPU; the acceptance bound for the in-program encode
        is <=1e-5 rel."""
        r_per, r_stg, *_ = records
        assert set(r_per.store._slices) == set(r_stg.store._slices)
        for g, sl in r_per.store._slices.items():
            np.testing.assert_allclose(np.asarray(sl),
                                       np.asarray(r_stg.store._slices[g]),
                                       rtol=1e-5, atol=1e-6)

    def test_history_norms_match(self, records):
        r_per, r_stg, *_ = records
        assert set(r_per.history_norms) == set(r_stg.history_norms)
        for k, v in r_per.history_norms.items():
            assert abs(v - r_stg.history_norms[k]) <= 1e-5 * max(abs(v), 1.0)

    def test_store_accounting_matches(self, records):
        r_per, r_stg, *_ = records
        assert r_per.store.stats == r_stg.store.stats

    def test_round_globals_lazy_view(self, records):
        r_per, r_stg, *_ = records
        for s in r_stg.plan.shard_clients:
            view = r_stg.round_globals[s]
            ref = r_per.round_globals[s]
            assert len(view) == len(ref) == FL_TINY.global_rounds + 1
            for g in (0, len(ref) - 1, -1):
                for a, b in zip(jax.tree.leaves(ref[g]),
                                jax.tree.leaves(view[g])):
                    np.testing.assert_array_equal(np.asarray(a),
                                                  np.asarray(b))

    def test_stored_round_reconstruction_matches(self, records):
        r_per, r_stg, *_ = records
        for s in r_per.plan.shard_clients:
            g_per = r_per.store.get_shard(0, s)
            g_stg = r_stg.store.get_shard(0, s)
            assert set(g_per) == set(g_stg)
            for c in g_per:
                for a, b in zip(jax.tree.leaves(g_per[c]),
                                jax.tree.leaves(g_stg[c])):
                    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                               rtol=1e-5, atol=1e-5)

    def test_unlearning_runs_on_stage_record(self, records):
        _, r_stg, sim, _ = records
        victim = r_stg.plan.shard_clients[0][0]
        for fw in ("SE", "FE", "FR", "RR"):
            res = run_unlearn(sim, fw, r_stg, [victim], rounds=2)
            leaves = jax.tree.leaves(list(res.models.values())[0])
            assert all(np.all(np.isfinite(np.asarray(l, np.float32)))
                       for l in leaves), fw

    @pytest.mark.parametrize("fw", ["SE", "FE", "FR", "RR"])
    def test_unlearning_cannot_tell_paths_apart(self, records, fw):
        """Each framework unlearns a stage-program record as it does the
        per-shard record of the same stage: same models, same cost."""
        r_per, r_stg, s_stg, s_per = records
        victim = r_stg.plan.shard_clients[0][0]
        res_stg = run_unlearn(s_stg, fw, r_stg, [victim], rounds=2)
        res_per = run_unlearn(s_per, fw, r_per, [victim], rounds=2)
        assert res_stg.impacted_shards == res_per.impacted_shards
        assert res_stg.cost_units == res_per.cost_units
        assert set(res_stg.models) == set(res_per.models)
        for s in res_per.models:
            for a, b in zip(jax.tree.leaves(res_per.models[s]),
                            jax.tree.leaves(res_stg.models[s])):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("store_kind",
                             ["full", "uncoded", "coded", "tiered"])
    def test_store_matches_per_shard(self, store_kind):
        s_stg, s_per = _tiny_sim(), _tiny_sim()
        r_stg = train_stage(s_stg, store_kind=store_kind)
        r_per = _per_shard_stage(s_per, store_kind)
        assert r_stg.store.stats == r_per.store.stats
        c = r_stg.plan.shard_clients[0][0]
        for a, b in zip(jax.tree.leaves(r_stg.store.get(0, c)),
                        jax.tree.leaves(r_per.store.get(0, c))):
            if store_kind in ("full", "uncoded"):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            else:       # decoded from coded slices
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-5, atol=1e-5)

    def test_ragged_stage_falls_back(self):
        """Unequal per-client sample counts across shards break the (S, M, n)
        stack — training must warn and fall back to the per-shard round,
        producing the same record as calling that round directly."""
        cfg = dataclasses.replace(get_config("cnn-paper"), image_size=8,
                                  d_model=16, cnn_channels=(4, 4))
        data = make_image_data(8 * 30, image_size=8, seed=0)
        clients = client_datasets_images(data, FL_TINY.num_clients, iid=True)
        # shrink one client's dataset: its shard's n_min now differs
        cid = sorted(clients)[0]
        clients[cid] = (clients[cid][0][:11], clients[cid][1][:11])

        def mk():
            return FLSimulator(cfg, FL_TINY, clients, task="image",
                               opt_cfg=OptimizerConfig(name="sgdm", lr=0.05,
                                                       grad_clip=0.0),
                               local_batch=10)

        s_stg, s_per = mk(), mk()
        with pytest.warns(UserWarning, match="ragged stage"):
            r_stg = train_stage(s_stg, store_kind="coded")
        r_per = _per_shard_stage(s_per)
        assert r_stg.plan.shard_clients == r_per.plan.shard_clients
        for s in r_per.shard_models:
            for a, b in zip(jax.tree.leaves(r_per.shard_models[s]),
                            jax.tree.leaves(r_stg.shard_models[s])):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestBatchedCalibration:
    """SE's multi-shard batched retraining (calib_stage: vmap over impacted
    shards, scan over rounds) must match the per-shard sequential loop."""

    def test_batched_matches_sequential(self):
        from repro.fl.experiment.frameworks import (ShardedEraser,
                                                    UnlearnContext)
        sim = _tiny_sim()
        rec = train_stage(sim, store_kind="coded")
        victims = [rec.plan.shard_clients[0][0], rec.plan.shard_clients[1][0]]
        fw = ShardedEraser()
        ctx = UnlearnContext(sim, rec, victims, FL_TINY.global_rounds)
        jobs = fw.prepare_jobs(ctx)
        assert len(jobs) == 2 and fw._batchable(jobs)
        m_bat, c_bat = fw._run_batched(ctx, jobs)
        m_seq, c_seq = fw._run_sequential(ctx, jobs)
        assert c_bat == c_seq
        assert set(m_bat) == set(m_seq)
        for s in m_seq:
            for a, b in zip(jax.tree.leaves(m_seq[s]),
                            jax.tree.leaves(m_bat[s])):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-5, atol=1e-6)

    def test_ragged_jobs_not_batchable(self):
        from repro.fl.experiment.frameworks import (ShardedEraser,
                                                    UnlearnContext)
        sim = _tiny_sim()
        rec = train_stage(sim, store_kind="coded")
        # two victims in shard 0, one in shard 1: retained counts differ
        victims = rec.plan.shard_clients[0][:2] + [rec.plan.shard_clients[1][0]]
        fw = ShardedEraser()
        ctx = UnlearnContext(sim, rec, list(victims), 2)
        jobs = fw.prepare_jobs(ctx)
        assert len(jobs) == 2 and not fw._batchable(jobs)
        res = run_unlearn(sim, "SE", rec, list(victims), rounds=2)
        assert res.impacted_shards == [0, 1]


class TestVmappedEvaluate:
    def test_matches_host_loop(self):
        sim = _tiny_sim()
        rec = train_stage(sim, store_kind="uncoded", rounds=1)
        data = make_image_data(110, image_size=8, seed=9)
        new = sim.evaluate(rec.shard_models, data.images, data.labels,
                           batch=32)
        ref = sim.evaluate_host(rec.shard_models, data.images, data.labels,
                                batch=32)
        assert new["acc"] == ref["acc"]
        assert abs(new["loss"] - ref["loss"]) < 1e-4

    def test_single_model_ensemble(self):
        sim = _tiny_sim()
        rec = train_stage(sim, store_kind="uncoded", rounds=1)
        data = make_image_data(60, image_size=8, seed=10)
        one = {0: rec.shard_models[0]}
        new = sim.evaluate(one, data.images, data.labels, batch=30)
        ref = sim.evaluate_host(one, data.images, data.labels, batch=30)
        assert new["acc"] == ref["acc"]
        assert abs(new["loss"] - ref["loss"]) < 1e-4


class TestDeprecatedShims:
    """``FLSimulator.unlearn`` stays callable as a thin wrapper over
    ``run_unlearn``: it warns, and its results are bit-identical to the new
    path on identically-seeded sims."""

    @pytest.fixture(scope="class")
    def pair(self):
        s_new, s_old = _tiny_sim(), _tiny_sim()
        return (s_new, train_stage(s_new, store_kind="coded"),
                s_old, train_stage(s_old, store_kind="coded"))

    @pytest.mark.parametrize("fw", ["SE", "FE", "FR", "RR"])
    def test_unlearn_shim_equivalent(self, pair, fw):
        s_new, r_new, s_old, r_old = pair
        victim = r_new.plan.shard_clients[0][0]
        res_new = run_unlearn(s_new, fw, r_new, [victim], rounds=2)
        with pytest.warns(DeprecationWarning, match="unlearn is deprecated"):
            res_old = s_old.unlearn(fw, r_old, [victim], rounds=2)
        assert res_old.impacted_shards == res_new.impacted_shards
        assert res_old.cost_units == res_new.cost_units
        for s in res_new.models:
            for a, b in zip(jax.tree.leaves(res_old.models[s]),
                            jax.tree.leaves(res_new.models[s])):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestDeprecatedScenarioShims:
    """``ScenarioConfig``'s pre-registry spellings — ``task="image"|"lm"``
    and ``iid=True/False`` — warn, map onto the task/family/partitioner
    registries, and build a bit-identical simulator + trained stage."""

    _TINY = dict(num_clients=6, clients_per_round=4, num_shards=2,
                 local_epochs=1, global_rounds=2, samples_per_client=10,
                 image_size=8, test_n=20)

    def test_image_noniid_spelling_bit_identical(self):
        with pytest.warns(DeprecationWarning, match="deprecated"):
            old = ScenarioConfig(task="image", iid=False, **self._TINY)
        new = ScenarioConfig(task="classification", model="cnn",
                             partitioner="primary-class", **self._TINY)
        assert (old.task, old.model, old.partitioner, old.iid) == \
            ("classification", "cnn", "primary-class", None)
        s_old, t_old = build_simulator(old)
        s_new, t_new = build_simulator(new)
        assert s_old.cfg == s_new.cfg
        assert s_old.opt == s_new.opt and s_old.local_batch == s_new.local_batch
        for c in s_old.client_data:
            np.testing.assert_array_equal(s_old.client_data[c][0],
                                          s_new.client_data[c][0])
            np.testing.assert_array_equal(s_old.client_data[c][1],
                                          s_new.client_data[c][1])
        np.testing.assert_array_equal(t_old[0], t_new[0])
        r_old = train_stage(s_old, store_kind="coded")
        r_new = train_stage(s_new, store_kind="coded")
        assert r_old.plan.shard_clients == r_new.plan.shard_clients
        for s in r_new.shard_models:
            for a, b in zip(jax.tree.leaves(r_old.shard_models[s]),
                            jax.tree.leaves(r_new.shard_models[s])):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_lm_spelling_maps_and_matches_data(self):
        tiny = dict(self._TINY, seq_len=12, samples_per_client=4)
        with pytest.warns(DeprecationWarning, match="task='lm'"):
            old = ScenarioConfig(task="lm", iid=True, **tiny)
        new = ScenarioConfig(task="generation", model="transformer",
                             partitioner="iid", **tiny)
        assert (old.task, old.model, old.partitioner) == \
            ("generation", "transformer", "iid")
        s_old, _ = build_simulator(old)
        s_new, _ = build_simulator(new)
        assert s_old.cfg == s_new.cfg
        for c in s_old.client_data:
            np.testing.assert_array_equal(s_old.client_data[c][0],
                                          s_new.client_data[c][0])

    def test_iid_false_lm_maps_to_buckets(self):
        with pytest.warns(DeprecationWarning, match="iid=.*deprecated"):
            cfg = ScenarioConfig(task="generation", iid=False, **self._TINY)
        assert cfg.partitioner == "buckets"


class TestStoreFastPaths:
    def test_grouped_encode_defers_then_matches(self):
        """group_rounds > 1 batches encodes; auto-flush on first read."""
        sch = coding.CodingScheme(num_shards=2, num_clients=6)
        shard_clients = {0: [0, 1], 1: [2, 3]}
        rng = np.random.default_rng(0)
        tmpl = {"w": np.zeros((3, 2), np.float32)}
        _, row_spec = coding.tree_to_flat(
            {"w": jnp.zeros((3, 2), jnp.float32)})

        def flats(seed):
            r = np.random.default_rng(seed)
            return {s: jnp.asarray(r.standard_normal((2, 6)), jnp.float32)
                    for s in (0, 1)}

        grouped = CodedStore(sch, shard_clients, group_rounds=4)
        eager = CodedStore(sch, shard_clients, group_rounds=1)
        per_round = [flats(i) for i in range(3)]
        for g, f in enumerate(per_round):
            grouped.put_round(RoundPayload.from_flat(g, shard_clients, f,
                                                     row_spec))
            eager.put_round(RoundPayload.from_flat(g, shard_clients, f,
                                                   row_spec))
        assert not grouped._slices          # group not full: still pending
        assert len(eager._slices) == 3      # eager store encodes per round
        got = grouped.get_shard(1, 0)       # triggers auto-flush
        want = eager.get_shard(1, 0)
        assert set(got) == set(want)
        for c in got:
            for a, b in zip(jax.tree.leaves(got[c]), jax.tree.leaves(want[c])):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-5, atol=1e-5)

    def test_bf16_slices_halve_storage(self):
        sch = coding.CodingScheme(num_shards=2, num_clients=6)
        shard_clients = {0: [0, 1], 1: [2, 3]}
        _, row_spec = coding.tree_to_flat({"w": jnp.zeros((8,), jnp.float32)})
        rng = np.random.default_rng(1)
        f = {s: jnp.asarray(rng.standard_normal((2, 8)), jnp.float32)
             for s in (0, 1)}
        st32 = CodedStore(sch, shard_clients)
        st16 = CodedStore(sch, shard_clients, slice_dtype=jnp.bfloat16)
        st32.put_round(RoundPayload.from_flat(0, shard_clients, f, row_spec))
        st16.put_round(RoundPayload.from_flat(0, shard_clients, f, row_spec))
        st32.flush(), st16.flush()
        assert st16.stats.client_bytes * 2 == st32.stats.client_bytes
        a = st32.get_shard(0, 0)
        b = st16.get_shard(0, 0)
        for c in a:
            np.testing.assert_allclose(np.asarray(a[c]["w"]),
                                       np.asarray(b[c]["w"]),
                                       rtol=5e-2, atol=5e-2)

    def test_full_store_stacked_rows_lazy(self):
        store = FullStore()
        stacked = _stacked_tree(m=3, seed=5)
        store.put_round(RoundPayload.from_stacked(0, {0: [10, 11, 12]},
                                                  {0: stacked}))
        got = store.get(0, 11)
        want = jax.tree.map(lambda a: a[1], stacked)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert store.clients_at(0) == [10, 11, 12]
        # the unified read path serves whole shards on uncoded stores too
        shard = store.get_shard(0, 0)
        assert sorted(shard) == [10, 11, 12]
