"""Durability tests: the checksummed snapshot format, the write-ahead
journal, checkpoint rotation with torn-write fallback, crash/resume
bit-identity for ``FederatedSession`` (in-process ``InjectedCrash`` and a
real ``os._exit`` subprocess kill), exactly-once unlearning replay through
the service journal, the ``repro.checkpoint`` -> ``repro.stores`` rename
shim, and the ``ScenarioConfig`` checkpoint-knob validation."""
import dataclasses
import json
import os
import subprocess
import sys
import tempfile

import jax
import numpy as np
import pytest

from _hypothesis_fallback import given, settings, st
from _durability_crash_child import session_signature

from repro.core.coding import CodingScheme, StackedRowSpec
from repro.configs import FLConfig, OptimizerConfig, get_config
from repro.data import client_datasets_images, make_image_data
from repro.durability import (CheckpointManager, Journal, SnapshotCorruption,
                              load_snapshot, replay, save_snapshot)
from repro.faults import FaultPlan, InjectedCrash
from repro.fl import FLSimulator
from repro.fl.experiment import (FederatedSession, RequestSchedule,
                                 ScenarioConfig, UnlearnRequest)
from repro.service import (LedgerEntry, ServiceRequest, UnlearningService,
                           sequenced_trace, service_request_id,
                           single_device_placement)
from repro.stores.store import StoreStats

FL_TINY = FLConfig(num_clients=10, clients_per_round=8, num_shards=2,
                   local_epochs=2, global_rounds=2, retrain_ratio=2.0)
NUM_STAGES = 2


def _tiny_sim(seed=0):
    cfg = dataclasses.replace(get_config("cnn-paper"), image_size=8,
                              d_model=16, cnn_channels=(4, 4))
    data = make_image_data(FL_TINY.num_clients * 30, image_size=8, seed=0)
    clients = client_datasets_images(data, FL_TINY.num_clients, iid=True)
    return FLSimulator(cfg, FL_TINY, clients, task="image",
                       opt_cfg=OptimizerConfig(name="sgdm", lr=0.05,
                                               grad_clip=0.0),
                       local_batch=10, seed=seed)


def _schedule():
    return RequestSchedule([
        UnlearnRequest(lambda p: [p.shard_clients[0][0]], framework="SE",
                       after_stage=0, rounds=1),
        UnlearnRequest(lambda p: [p.shard_clients[1][0]], framework="SE",
                       after_stage=1, rounds=1),
    ])


# -------------------------------------------------------------- snapshot fmt
class TestSnapshotFormat:
    def _graph(self):
        bf16 = np.dtype("bfloat16")
        rng = np.random.default_rng(0)
        tree = {"w": rng.standard_normal((3, 2)).astype(np.float32),
                "b": rng.standard_normal(2).astype(np.float32)}
        leaves, treedef = jax.tree.flatten(tree)
        return {
            "slices": {(0, 1): rng.standard_normal(7).astype(np.float32)
                       .astype(bf16)},
            "spec": StackedRowSpec((0, 1, 2), 8,
                                   (treedef, [(l.shape, l.dtype)
                                              for l in leaves])),
            "scheme": CodingScheme(num_shards=2, num_clients=5),
            "stats": StoreStats(server_bytes=12, reads=3),
            "served": {"req-s0-0", "req-s1-0"},
            "rng": {"state": 12345678901234567890, "pos": 17},
            "scalars": [None, True, 2.5, -0.0, "text"],
            "jaxarr": jax.numpy.arange(6, dtype=jax.numpy.int32),
        }

    def test_roundtrip_exact(self, tmp_path):
        path = str(tmp_path / "s.ckpt")
        obj = self._graph()
        n = save_snapshot(path, obj)
        assert n == os.path.getsize(path)
        back = load_snapshot(path)
        sl, sl2 = obj["slices"][(0, 1)], back["slices"][(0, 1)]
        assert sl2.dtype == np.dtype("bfloat16")          # never promoted
        assert sl2.tobytes() == sl.tobytes()
        td, shapes = back["spec"].row_spec
        td0, shapes0 = obj["spec"].row_spec
        assert td == td0 and shapes == shapes0
        assert back["spec"].client_ids == (0, 1, 2)
        assert back["scheme"].num_shards == 2
        assert back["scheme"].alpha.tobytes() == obj["scheme"].alpha.tobytes()
        assert back["stats"] == obj["stats"]
        assert back["served"] == obj["served"]
        assert back["rng"] == obj["rng"]                  # bigint exact
        assert back["scalars"] == obj["scalars"]
        got = np.asarray(back["jaxarr"])
        assert isinstance(back["jaxarr"], jax.Array)
        assert got.dtype == np.int32
        np.testing.assert_array_equal(got, np.arange(6, dtype=np.int32))

    def test_atomic_commit_leaves_no_tmp(self, tmp_path):
        path = str(tmp_path / "s.ckpt")
        save_snapshot(path, {"a": 1})
        assert os.listdir(tmp_path) == ["s.ckpt"]

    def test_truncation_detected(self, tmp_path):
        path = str(tmp_path / "s.ckpt")
        save_snapshot(path, self._graph())
        size = os.path.getsize(path)
        with open(path, "r+b") as f:
            f.truncate(size // 2)
        with pytest.raises(SnapshotCorruption, match="torn write"):
            load_snapshot(path)

    def test_bitflip_detected(self, tmp_path):
        path = str(tmp_path / "s.ckpt")
        save_snapshot(path, self._graph())
        size = os.path.getsize(path)
        with open(path, "r+b") as f:
            f.seek(size - 8)
            chunk = f.read(8)
            f.seek(size - 8)
            f.write(bytes(b ^ 0xFF for b in chunk))
        with pytest.raises(SnapshotCorruption, match="checksum mismatch"):
            load_snapshot(path)

    def test_bad_magic_detected(self, tmp_path):
        path = str(tmp_path / "s.ckpt")
        with open(path, "wb") as f:
            f.write(b"NOTASNAP" + b"\0" * 64)
        with pytest.raises(SnapshotCorruption, match="bad magic"):
            load_snapshot(path)

    def test_missing_file_is_corruption(self, tmp_path):
        with pytest.raises(SnapshotCorruption, match="unreadable"):
            load_snapshot(str(tmp_path / "nope.ckpt"))


# ------------------------------------------------------------------- journal
class TestJournal:
    def test_append_replay_roundtrip(self, tmp_path):
        path = str(tmp_path / "j.wal")
        j = Journal(path)
        events = [{"ev": "stage_begin", "stage": 0},
                  {"ev": "req_commit", "rids": ["req-s0-0"]},
                  {"ev": "snapshot", "step": 0, "path": "snap-000000.ckpt"}]
        assert [j.append(e) for e in events] == [0, 1, 2]
        j.close()
        assert Journal(path).events() == events

    def test_seq_continues_across_reopen(self, tmp_path):
        path = str(tmp_path / "j.wal")
        j1 = Journal(path)
        j1.append({"ev": "a"})
        j1.close()
        j2 = Journal(path)
        assert j2.append({"ev": "b"}) == 1
        assert [r["seq"] for r in j2.records()] == [0, 1]

    def test_torn_tail_tolerated(self, tmp_path):
        path = str(tmp_path / "j.wal")
        j = Journal(path)
        j.append({"ev": "a"})
        j.append({"ev": "b"})
        j.close()
        with open(path, "a") as f:
            f.write('deadbeef {"seq": 2, "ev"')     # crash mid-append
        assert [r["ev"]["ev"] for r in replay(path)] == ["a", "b"]
        # a reopened journal resumes numbering after the good prefix
        assert Journal(path).append({"ev": "c"}) == 2

    def test_corrupt_middle_stops_replay(self, tmp_path):
        path = str(tmp_path / "j.wal")
        j = Journal(path)
        j.append({"ev": "a"})
        j.append({"ev": "b"})
        j.close()
        lines = open(path).read().splitlines()
        lines[0] = "00000000 " + lines[0].split(" ", 1)[1]
        with open(path, "w") as f:
            f.write("\n".join(lines) + "\n")
        assert replay(path) == []                   # nothing after bad crc


# -------------------------------------------------------- checkpoint manager
class TestCheckpointManager:
    def test_save_load_and_rotation(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep=2)
        for step in range(4):
            mgr.save({"step": step}, step)
        assert mgr.steps() == [2, 3]                # pruned to keep=2
        state, step, path = mgr.load_latest()
        assert state == {"step": 3} and step == 3
        assert path.endswith("snap-000003.ckpt")

    def test_corrupt_newest_falls_back_to_previous(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep=3)
        for step in range(3):
            mgr.save({"step": step}, step)
        bad = mgr.snapshot_path(2)
        with open(bad, "r+b") as f:
            f.truncate(os.path.getsize(bad) // 3)
        state, step, _path = mgr.load_latest()
        assert state == {"step": 1} and step == 1
        assert mgr.skipped == [bad]

    def test_empty_dir_loads_none(self, tmp_path):
        assert CheckpointManager(str(tmp_path)).load_latest() is None


# ------------------------------------------------- stores rename (satellite)
class TestStoresRenameShim:
    def test_legacy_import_warns_and_is_identical(self):
        sys.modules.pop("repro.checkpoint", None)
        with pytest.warns(DeprecationWarning,
                          match="repro.checkpoint is deprecated"):
            import repro.checkpoint as legacy
        import repro.stores as stores
        for name in ("CodedStore", "FullStore", "UncodedShardStore",
                     "ParameterStore", "RoundPayload", "StoreStats",
                     "make_store", "register_store", "tree_bytes"):
            assert getattr(legacy, name) is getattr(stores, name), name
        assert legacy.STORES is stores.STORES

    def test_legacy_store_module_resolves_same_classes(self):
        from repro.checkpoint import store as legacy_store
        from repro.stores import store as new_store
        assert legacy_store.CodedStore is new_store.CodedStore
        assert legacy_store._StackedRow is new_store._StackedRow


# ---------------------------------------------- scenario knobs (satellite)
class TestScenarioCheckpointValidation:
    def test_negative_interval_fails_at_construction(self):
        with pytest.raises(ValueError, match="checkpoint_every=-1"):
            ScenarioConfig(checkpoint_every=-1)

    def test_interval_without_dir_fails(self):
        with pytest.raises(ValueError, match="needs a checkpoint_dir"):
            ScenarioConfig(checkpoint_every=2)

    def test_unwritable_dir_fails_at_construction(self):
        with pytest.raises(ValueError, match="not writable"):
            ScenarioConfig(checkpoint_dir="/proc/definitely/not/writable")

    def test_writable_dir_accepted(self, tmp_path):
        cfg = ScenarioConfig(checkpoint_every=2,
                             checkpoint_dir=str(tmp_path / "ck"))
        assert cfg.checkpoint_every == 2

    def test_session_rejects_interval_without_dir(self):
        with pytest.raises(ValueError, match="needs a"):
            FederatedSession(_tiny_sim(), checkpoint_every=1)


# --------------------------------------------- crash/resume (in-process)
@pytest.fixture(scope="module")
def baseline_sig():
    """Signature of the uninterrupted, checkpoint-free run — the oracle
    every crashed+resumed variant must match bit-for-bit."""
    session = FederatedSession(_tiny_sim(), store_kind="coded")
    session.run(NUM_STAGES, schedule=_schedule())
    return session_signature(session)


class TestSessionCrashResume:
    def test_crash_after_requests_resumes_bit_identical(self, tmp_path,
                                                        baseline_sig):
        ck = str(tmp_path / "ck")
        plan = FaultPlan(seed=7).add("process_kill", stage=1,
                                     phase="after_requests", mode="raise")
        crashed = FederatedSession(_tiny_sim(), store_kind="coded",
                                   faults=plan, checkpoint_every=1,
                                   checkpoint_dir=ck)
        with pytest.raises(InjectedCrash):
            crashed.run(NUM_STAGES, schedule=_schedule())
        assert plan.ledger.count("process_kill") == 1
        assert crashed.checkpointer.steps() == [0]   # died before snap-1

        resumed = FederatedSession(_tiny_sim(), store_kind="coded",
                                   checkpoint_every=1, checkpoint_dir=ck)
        resumed.run(NUM_STAGES, schedule=_schedule(), resume_from=ck)
        info = resumed.last_resume_info
        assert info["step"] == 0 and info["start_stage"] == 1
        assert session_signature(resumed) == baseline_sig
        # exactly-once: a request lands at most once per impacted stage
        # (a multi-stage victim legitimately yields one result per stage)
        pairs = [(i, u.request_id)
                 for i, st_ in enumerate(resumed.report.stages)
                 for u in st_.unlearn]
        assert len(pairs) == len(set(pairs))
        assert {rid for _, rid in pairs} == {"req-s0-0", "req-s1-0"}

    def test_torn_snapshot_falls_back_to_previous_good(self, tmp_path,
                                                       baseline_sig):
        ck = str(tmp_path / "ck")
        plan = (FaultPlan(seed=7)
                .add("torn_write", step=1, frac=0.4)
                .add("process_kill", stage=1, phase="after_snapshot",
                     mode="raise"))
        crashed = FederatedSession(_tiny_sim(), store_kind="coded",
                                   faults=plan, checkpoint_every=1,
                                   checkpoint_dir=ck)
        with pytest.raises(InjectedCrash):
            crashed.run(NUM_STAGES, schedule=_schedule())
        assert plan.ledger.count("torn_write") == 1

        resumed = FederatedSession(_tiny_sim(), store_kind="coded")
        resumed.run(NUM_STAGES, schedule=_schedule(), resume_from=ck)
        info = resumed.last_resume_info
        assert len(info["skipped_snapshots"]) == 1   # snap-1: checksum fail
        assert info["step"] == 0 and info["start_stage"] == 1
        assert session_signature(resumed) == baseline_sig

    def test_snapshot_naming_an_engine_resumes(self, tmp_path, baseline_sig):
        """A snapshot written while sessions still named a round engine
        carries ``"engine": "fused"``; it restores and resumes to the same
        models."""
        from repro.durability import session_state
        first = FederatedSession(_tiny_sim(), store_kind="coded")
        first.run(1, schedule=RequestSchedule(_schedule().requests[:1]))
        state = session_state.capture_session(first)
        assert "engine" not in state
        state["engine"] = "fused"
        ck = str(tmp_path / "ck")
        CheckpointManager(ck).save(state, step=0)

        resumed = FederatedSession(_tiny_sim(), store_kind="coded")
        resumed.run(NUM_STAGES, schedule=_schedule(), resume_from=ck)
        assert resumed.last_resume_info["start_stage"] == 1
        assert session_signature(resumed) == baseline_sig

    def test_resume_from_empty_dir_raises(self, tmp_path):
        session = FederatedSession(_tiny_sim(), store_kind="coded")
        with pytest.raises(FileNotFoundError, match="no usable snapshot"):
            session.run(NUM_STAGES, resume_from=str(tmp_path / "empty"))

    def test_resume_rejects_mismatched_config(self, tmp_path):
        ck = str(tmp_path / "ck")
        session = FederatedSession(_tiny_sim(), store_kind="coded",
                                   checkpoint_every=1, checkpoint_dir=ck)
        session.run(1, schedule=None)
        other = FederatedSession(_tiny_sim(), store_kind="full")
        with pytest.raises(ValueError, match="store_kind"):
            other.run(NUM_STAGES, resume_from=ck)


# ------------------------------------------ service exactly-once (satellite)
@pytest.fixture(scope="module")
def trained_for_service():
    session = FederatedSession(_tiny_sim(), store_kind="coded")
    record = session.run_stage()
    victims = [record.plan.shard_clients[0][0],
               record.plan.shard_clients[1][0]]
    return session, victims


class TestServiceExactlyOnce:
    def test_journal_replay_commits_exactly_once(self, tmp_path,
                                                 trained_for_service):
        session, victims = trained_for_service
        trace = sequenced_trace(victims, spacing=0.1, rounds=1)
        jpath = str(tmp_path / "svc.wal")
        j1 = Journal(jpath)
        svc1 = UnlearningService(session,
                                 placement=single_device_placement(),
                                 journal=j1)
        rep1 = svc1.serve(trace[:1])        # "crash" after first request
        j1.close()
        # the WAL interleaves the hash-chained audit records with the
        # dispatch/commit markers; exactly-once cares about the latter
        assert [e["ev"] for e in Journal(jpath).events()
                if e["ev"] != "audit"] == ["svc_dispatch", "svc_commit"]
        from repro.telemetry import verify_journal
        assert verify_journal(Journal(jpath)) == svc1.audit.head

        j2 = Journal(jpath)
        svc2 = UnlearningService(session,
                                 placement=single_device_placement(),
                                 journal=j2)
        rep2 = svc2.serve(trace, resume=True)
        j2.close()
        assert [e.request_id for e in rep2.entries] == ["svc-0", "svc-1"]
        # committed entry replayed bit-identically, never re-dispatched
        assert rep2.entries[0].to_dict() == rep1.entries[0].to_dict()
        events = Journal(jpath).events()
        commits = [e["request_id"] for e in events if e["ev"] == "svc_commit"]
        dispatches = [e["request_id"] for e in events
                      if e["ev"] == "svc_dispatch"]
        assert commits.count("svc-0") == 1          # exactly once, ever
        assert dispatches.count("svc-0") == 1
        assert commits.count("svc-1") == 1
        assert dispatches.count("svc-1") == 1

    def test_dispatched_uncommitted_redispatches_exactly_once(
            self, tmp_path, trained_for_service):
        session, victims = trained_for_service
        trace = sequenced_trace(victims[:1], rounds=1)
        jpath = str(tmp_path / "svc.wal")
        j = Journal(jpath)
        # crash between retrain and ledger-commit: dispatch journaled,
        # commit never was
        j.append({"ev": "svc_dispatch", "request_id": "svc-0",
                  "batch_id": 0})
        svc = UnlearningService(session,
                                placement=single_device_placement(),
                                journal=j)
        rep = svc.serve(trace, resume=True)
        j.close()
        assert [e.request_id for e in rep.entries] == ["svc-0"]
        events = Journal(jpath).events()
        assert sum(1 for e in events if e["ev"] == "svc_commit") == 1

    def test_report_keys_requests_on_ids(self, tmp_path,
                                         trained_for_service):
        session, victims = trained_for_service
        trace = sequenced_trace(victims, spacing=0.1, rounds=1)
        trace[0] = dataclasses.replace(trace[0], request_id="user-abc")
        svc = UnlearningService(session,
                                placement=single_device_placement())
        rep = svc.serve(trace)
        d = json.loads(rep.to_json())
        assert set(d["requests"]) == {"user-abc", "svc-1"}
        assert d["requests"]["user-abc"]["clients"] == [victims[0]]

    def test_ledger_entry_dict_roundtrip(self):
        entry = LedgerEntry(rid=4, arrival=0.25, clients=(7, 9),
                            framework="SE", batch_id=1, queue_wait=0.5,
                            batch_wait=0.01, retrain_wall=1.5, latency=2.01,
                            n_jobs=2, devices=[0, 1],
                            impacted=[(0, 0), (0, 1)], cost_units=3.5,
                            deadline=5.0, sla_met=True, job_attempts=3,
                            job_retries=1, request_id="user-x")
        assert LedgerEntry.from_dict(entry.to_dict()) == entry

    def test_service_request_id_fallback(self):
        assert service_request_id(ServiceRequest(t=0.0, clients=(1,),
                                                 rid=3)) == "svc-3"
        assert service_request_id(ServiceRequest(
            t=0.0, clients=(1,), rid=3, request_id="user-z")) == "user-z"


# ------------------------------------------- subprocess kill (acceptance)
class TestKillResumeSubprocess:
    def test_killed_session_resumes_bit_identical(self, tmp_path):
        """The durability acceptance anchor: a session killed mid-run with
        ``os._exit(137)`` (no atexit, no flushes) resumes from its snapshots
        and journal to a state bit-identical to the uninterrupted run.
        Subprocess because a real process kill cannot be simulated
        in-process."""
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(
            p for p in ["src", env.get("PYTHONPATH", "")] if p)
        child = os.path.join(os.path.dirname(__file__),
                             "_durability_crash_child.py")
        cwd = os.path.dirname(os.path.dirname(os.path.abspath(child)))

        def run(mode, ckpt):
            return subprocess.run([sys.executable, child, mode, ckpt],
                                  env=env, cwd=cwd, capture_output=True,
                                  text=True, timeout=560)

        ck = str(tmp_path / "ck")
        crash = run("crash", ck)
        assert crash.returncode == 137, crash.stderr[-2000:]
        assert os.path.exists(os.path.join(ck, "journal.wal"))
        assert os.path.exists(os.path.join(ck, "snap-000000.ckpt"))

        resume = run("resume", ck)
        assert resume.returncode == 0, resume.stderr[-2000:]
        got = json.loads(resume.stdout.strip().splitlines()[-1])
        assert got["start_stage"] == 1 and got["resumed_step"] == 0
        assert got["request_ids"] == ["req-s0-0", "req-s1-0", "req-s2-0"]
        assert got["once_per_stage"]

        base = run("baseline", str(tmp_path / "unused"))
        assert base.returncode == 0, base.stderr[-2000:]
        ref = json.loads(base.stdout.strip().splitlines()[-1])
        assert got["sig"] == ref["sig"]              # bit-identical


# ------------------------------------------------ property tests (satellite)
_DTYPES = ["float32", "float16", "bfloat16", "int32", "int8", "uint8"]


@settings(max_examples=12)
@given(dtype=st.sampled_from(_DTYPES), n=st.integers(1, 64),
       seed=st.integers(0, 2 ** 20), as_jax=st.booleans())
def test_snapshot_array_roundtrip_never_promotes(dtype, n, seed, as_jax):
    rng = np.random.default_rng(seed)
    dt = np.dtype(dtype)
    if dt.kind in "iu":
        a = rng.integers(np.iinfo(dt).min, np.iinfo(dt).max,
                         size=n).astype(dt)
    else:
        a = rng.standard_normal(n).astype(np.float32).astype(dt)
    obj = {("coded", 0): [jax.numpy.asarray(a) if as_jax else a, None],
           "dtype": dt}
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "s.ckpt")
        save_snapshot(path, obj)
        back = load_snapshot(path)
    got = back[("coded", 0)][0]
    assert isinstance(got, jax.Array) == as_jax
    arr = np.asarray(got)
    assert arr.dtype == dt                           # never silently promoted
    assert arr.tobytes() == a.tobytes()              # bit-for-bit
    assert back["dtype"] == dt


@settings(max_examples=12)
@given(seed=st.integers(0, 2 ** 20))
def test_snapshot_store_stats_roundtrip(seed):
    rng = np.random.default_rng(seed)
    stats = StoreStats(**{f.name: int(rng.integers(0, 2 ** 40))
                          for f in dataclasses.fields(StoreStats)})
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "s.ckpt")
        save_snapshot(path, {"stats": stats})
        back = load_snapshot(path)["stats"]
    assert isinstance(back, StoreStats) and back == stats


@settings(max_examples=10)
@given(shards=st.integers(1, 4), extra=st.integers(0, 5))
def test_snapshot_coding_scheme_roundtrip(shards, extra):
    scheme = CodingScheme(num_shards=shards, num_clients=shards + extra)
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "s.ckpt")
        save_snapshot(path, {"scheme": scheme})
        back = load_snapshot(path)["scheme"]
    assert back.num_shards == shards
    assert back.num_clients == shards + extra
    assert back.alpha.dtype == scheme.alpha.dtype
    assert back.alpha.tobytes() == scheme.alpha.tobytes()
    assert back.omega.tobytes() == scheme.omega.tobytes()


@settings(max_examples=10)
@given(n=st.integers(1, 12), cut=st.floats(min_value=0.05, max_value=0.95))
def test_journal_torn_tail_property(n, cut):
    events = [{"ev": "e", "i": i} for i in range(n)]
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "j.wal")
        j = Journal(path)
        for e in events:
            j.append(e)
        j.close()
        lines = open(path, "rb").read().splitlines(keepends=True)
        torn = lines[-1][: max(1, int(len(lines[-1]) * cut))]
        with open(path, "wb") as f:
            f.writelines(lines[:-1])
            f.write(torn)
        got = [r["ev"] for r in replay(path)]
    # a journal line is ~40+ bytes, so cut<=0.95 always tears the record
    assert got == events[:-1]
