"""Telemetry-layer tests: the no-op default and its overhead bound, span
nesting/threading/signatures, the metrics registry, the hash-chained audit
log (tamper detection + journal splice), Chrome-trace export validation,
and the acceptance anchors — two seeded service runs under the virtual
clock produce bit-identical span trees AND bit-identical audit-chain
heads, and a fault-injected read records injection + recovery telemetry;
and the device-trace side: recorded spans reach a ``jax.profiler`` trace as
``repro.*`` annotations (the null tracer's do not), and the stage program
is ``jit_stage_program`` with its named scopes in the ``op_name`` metadata.
"""
import dataclasses
import json
import os
import re
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import FLConfig, OptimizerConfig, get_config
from repro.core.coding import CodingScheme
from repro.data import client_datasets_images, make_image_data
from repro.durability import Journal
from repro.faults import FaultPlan
from repro.fl import FLSimulator
from repro.fl.experiment import (FederatedSession, RequestSchedule,
                                 UnlearnRequest, train_stage)
from repro.service import (ServiceRequest, UnlearningService, VirtualClock,
                           single_device_placement)
from repro.stores.store import CodedStore, RoundPayload
from repro.telemetry import (AuditChainError, AuditLog, GENESIS, NULL_TRACER,
                             MetricsRegistry, Tracer, chain_hash, configure,
                             get_tracer, render_tree, set_tracer,
                             to_chrome_trace, validate_chrome_trace,
                             verify_chain, verify_journal, write_chrome_trace,
                             write_jsonl)

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


def _req(rid, t, clients=(0,), deadline=None, framework="SE"):
    return ServiceRequest(t=t, clients=tuple(clients), framework=framework,
                          deadline=deadline, rid=rid)


@pytest.fixture(autouse=True)
def _restore_default_tracer():
    """Every test leaves the process-wide tracer in its no-op default —
    other test modules must keep seeing unchanged (untraced) behavior."""
    yield
    set_tracer(NULL_TRACER)


# -------------------------------------------------------------------- tracer
class TestTracer:
    def test_default_is_noop(self):
        tr = get_tracer()
        assert tr is NULL_TRACER and not tr.enabled
        with tr.span("anything", label=1) as sp:
            sp.annotate(more=2)
        tr.event("instant", x=3)
        tr.metrics.counter("c").inc()
        tr.metrics.histogram("h").observe(1.0)
        assert tr.all_spans() == [] and tr.signature() == ""
        assert tr.metrics.snapshot() == {}
        assert tr.describe() == {"enabled": False}

    def test_configure_installs_and_restores(self):
        tr = configure(enabled=True)
        assert get_tracer() is tr and tr.enabled
        assert configure(enabled=False) is NULL_TRACER
        assert get_tracer() is NULL_TRACER

    def test_nesting_and_tree(self):
        tr = Tracer()
        with tr.span("outer", stage=0):
            with tr.span("inner", shard=1):
                pass
            tr.event("mark", hit=True)
        tree = tr.tree()
        assert [n["name"] for n in tree] == ["outer"]
        kids = tree[0]["children"]
        assert [n["name"] for n in kids] == ["inner", "mark"]
        assert kids[1]["kind"] == "event"
        assert tree[0]["labels"] == {"stage": 0}

    def test_signature_ignores_wall_time_but_not_labels(self):
        def forest(extra=None, sleep=0.0):
            tr = Tracer()
            with tr.span("a", k=1):
                if sleep:
                    time.sleep(sleep)
                with tr.span("b", **(extra or {})):
                    pass
            return tr.signature()

        assert forest(sleep=0.0) == forest(sleep=0.01)
        assert forest({"x": 1}) != forest({"x": 2})
        assert forest() != forest({"x": 1})

    def test_worker_thread_spans_are_order_independent_roots(self):
        def run(order):
            tr = Tracer()
            barrier = threading.Barrier(len(order))

            def worker(i):
                barrier.wait()
                with tr.span("job", idx=i):
                    time.sleep(0.001 * (i + 1))

            threads = [threading.Thread(target=worker, args=(i,))
                       for i in order]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            return tr

        a, b = run([0, 1, 2]), run([2, 1, 0])
        assert len(a.sorted_roots()) == 3
        assert a.signature() == b.signature()
        assert [r.labels["idx"] for r in a.sorted_roots()] == [0, 1, 2]

    def test_virtual_clock_dual_times(self):
        tr = Tracer()
        clock = VirtualClock()
        tr.attach_clock(clock)
        clock.advance_to(3.5)
        with tr.span("planned") as sp:
            clock.advance_to(7.25)
        assert sp.v0 == 3.5 and sp.v1 == 7.25
        assert sp.t1 >= sp.t0
        tr.detach_clock()
        with tr.span("unplanned") as sp2:
            pass
        assert sp2.v0 is None and sp2.v1 is None
        node = tr.tree()[0]
        assert node["v0"] == 3.5 and node["v1"] == 7.25


# ------------------------------------------------------------------- metrics
class TestMetrics:
    def test_counter_gauge_histogram_and_snapshot(self):
        m = MetricsRegistry()
        m.counter("reads", store="coded").inc()
        m.counter("reads", store="coded").inc(2)
        m.gauge("depth").set(4)
        m.gauge("depth").set(7)                      # last write wins
        for v in range(1, 101):
            m.histogram("lat_s", client=3).observe(v / 100)
        snap = m.snapshot()
        assert snap["counters"]["reads{store=coded}"] == 3
        assert snap["gauges"]["depth"] == 7
        h = snap["histograms"]["lat_s{client=3}"]
        assert h["count"] == 100 and h["p50"] == pytest.approx(0.505)
        assert m.histogram("lat_s", client=3).percentile(99) == \
            pytest.approx(0.9901)

    def test_absorb_is_idempotent_and_per_client_p99(self):
        m = MetricsRegistry()
        faults = {"injected": 5, "recovered_reads": 2, "note": "x"}
        m.absorb_faults(faults)
        m.absorb_faults(faults)                      # absorb twice: no double
        snap = m.snapshot()
        assert snap["gauges"]["faults.injected"] == 5
        assert "faults.note" not in snap["gauges"]
        for c, lat in ((0, 1.0), (0, 3.0), (7, 0.5)):
            m.histogram("service.client_latency_s", client=c).observe(lat)
        p99 = m.per_client_p99()
        assert set(p99) == {0, 7}
        assert p99[0] == pytest.approx(2.98) and p99[7] == pytest.approx(0.5)


# --------------------------------------------------------------------- audit
class TestAudit:
    def test_chain_append_verify_and_lookup(self):
        log = AuditLog()
        h1 = log.record("received", request_id="svc-0", clients=[7])
        h2 = log.record("committed", request_id="svc-0", batch_id=0)
        assert h2 == log.head != h1 != GENESIS
        assert log.verify() == h2
        assert log.kinds() == ["received", "committed"]
        assert [e["kind"] for e in log.events_of("svc-0")] == \
            ["received", "committed"]
        assert chain_hash(h1, log.records[1]["event"]) == h2

    def test_tampering_breaks_the_chain(self):
        log = AuditLog()
        for i in range(3):
            log.record("received", request_id=f"svc-{i}")
        tampered = [dict(r, event=dict(r["event"])) for r in log.records]
        tampered[1]["event"]["request_id"] = "svc-999"
        with pytest.raises(AuditChainError):
            verify_chain(tampered)
        with pytest.raises(AuditChainError):          # dropped record
            verify_chain(log.records[:1] + log.records[2:])
        with pytest.raises(AuditChainError):          # reordered
            verify_chain(list(reversed(log.records)))
        assert verify_chain(log.records) == log.head

    def test_journal_splice_extends_one_chain(self, tmp_path):
        path = str(tmp_path / "audit.journal")
        first = AuditLog(journal=Journal(path))
        first.record("received", request_id="svc-0", clients=[1])
        first.record("retrained", request_id="svc-0", shards=[0])

        resumed = AuditLog(journal=Journal(path))     # the resume path
        assert resumed.head == first.head and len(resumed) == 2
        resumed.record("committed", request_id="svc-0", batch_id=0)
        assert resumed.verify() == resumed.head != first.head
        assert verify_journal(Journal(path)) == resumed.head
        assert verify_journal(Journal(str(tmp_path / "empty.journal"))) \
            is None


# -------------------------------------------------------------------- export
class TestExport:
    def _forest(self):
        tr = Tracer()
        clock = VirtualClock()
        tr.attach_clock(clock)
        with tr.span("service.dispatch", batch=0):
            clock.advance_to(1.0)
            with tr.span("service.job", device=1, shard=0):
                pass
            tr.event("fault.inject", kind="slice_corruption")
        return tr

    def test_chrome_trace_validates_with_lanes(self, tmp_path):
        tr = self._forest()
        obj = to_chrome_trace(tr)
        assert validate_chrome_trace(obj) == []
        names = {e["name"] for e in obj["traceEvents"]}
        assert {"service.dispatch", "service.job", "fault.inject"} <= names
        lanes = {e["args"]["name"] for e in obj["traceEvents"]
                 if e["name"] == "thread_name"}
        assert "device-1" in lanes                # device-labeled span lane
        inst = [e for e in obj["traceEvents"] if e.get("ph") == "i"]
        assert inst and all(e.get("s") == "t" for e in inst)
        path = str(tmp_path / "trace.json")
        write_chrome_trace(tr, path)
        assert validate_chrome_trace(json.loads(open(path).read())) == []
        assert tr.trace_path == path

    def test_validator_catches_malformed(self):
        assert validate_chrome_trace({"traceEvents": [{"ph": "X"}]})
        assert validate_chrome_trace(
            {"traceEvents": [{"ph": "??", "name": "x", "pid": 0, "tid": 0,
                              "ts": 0.0}]})

    def test_jsonl_and_tree_render(self, tmp_path):
        tr = self._forest()
        path = str(tmp_path / "spans.jsonl")
        write_jsonl(tr, path)
        lines = [json.loads(ln) for ln in open(path)]
        assert {ln["name"] for ln in lines} >= {"service.dispatch",
                                                "service.job"}
        text = render_tree(tr)
        assert "service.dispatch" in text and "service.job" in text


# --------------------------------------------------- integration (jit-heavy)
def _traced_service_run():
    """One seeded, fully traced workload: two stage-engine training stages,
    then a window-policy serve of three SE requests on one device."""
    tr = configure(enabled=True)
    sim = _tiny_sim(seed=0)
    session = FederatedSession(sim, store_kind="coded", engine="stage")
    session.run_stage()
    session.run_stage()
    svc = UnlearningService(session, policy="window",
                            policy_opts={"width": 0.5},
                            placement=single_device_placement())
    trace = [_req(0, 0.1, clients=(0,)), _req(1, 0.2, clients=(5,)),
             _req(2, 0.9, clients=(1,))]
    report = svc.serve(trace)
    return tr, svc, report


class TestIntegration:
    def test_seeded_runs_are_bit_identical(self):
        tr_a, svc_a, _ = _traced_service_run()
        sig_a, head_a, tree_a = tr_a.signature(), svc_a.audit.head, tr_a.tree()
        tr_b, svc_b, _ = _traced_service_run()
        assert tr_b.signature() == sig_a
        assert svc_b.audit.head == head_a
        assert tr_b.tree() == tree_a
        assert svc_b.audit.verify() == head_a
        kinds = svc_b.audit.kinds()
        assert kinds.count("received") == 3
        assert kinds.count("committed") == 3
        assert {"scheduled", "retrained"} <= set(kinds)

    def test_report_telemetry_section_gated_on_tracer(self):
        tr, svc, report = _traced_service_run()
        d = report.to_dict()
        assert d["telemetry"]["enabled"] is True
        assert d["telemetry"]["span_signature"] == tr.signature()
        assert d["telemetry"]["metrics"]["gauges"]["service.num_requests"] \
            == 3
        assert d["client_latency_p99_s"]
        required = {"session.stage", "stage.train", "xla.stage_program",
                    "store.put_stage", "store.read", "service.serve",
                    "service.plan", "service.dispatch", "service.job",
                    "unlearn.shard"}
        assert required <= set(tr.span_names())
        set_tracer(NULL_TRACER)
        assert "telemetry" not in report.to_dict()

    def test_session_audit_chain_spans_batched_unlearning(self, tmp_path):
        configure(enabled=True)
        session = FederatedSession(_tiny_sim(seed=0), store_kind="coded",
                                   engine="stage", batch_requests=True,
                                   checkpoint_every=1,
                                   checkpoint_dir=str(tmp_path))
        schedule = RequestSchedule([
            UnlearnRequest(lambda p, s=s: [p.shard_clients[s][0]],
                           framework="SE", after_stage=0)
            for s in (0, 1)])
        report = session.run(1, schedule=schedule)
        head = session.audit.verify()
        kinds = session.audit.kinds()
        assert kinds.count("received") == 2 and kinds.count("committed") == 2
        assert "retrained" in kinds
        assert verify_journal(session.checkpointer.journal) == head
        assert report.to_dict()["telemetry"]["enabled"] is True
        assert "durability.snapshot" in get_tracer().span_names()

    def test_chaos_read_records_injection_and_recovery(self):
        configure(enabled=True)
        c, s = 12, 4
        per = c // s
        shard_clients = {i: list(range(i * per, (i + 1) * per))
                         for i in range(s)}
        store = CodedStore(CodingScheme(num_shards=s, num_clients=c),
                           shard_clients)
        rng = np.random.default_rng(1)
        params = {cl: {"w": jnp.asarray(rng.standard_normal(5), jnp.float32)}
                  for cl in range(c)}
        store.put_round(RoundPayload.from_clients(0, shard_clients, params))
        store.attach_faults(
            FaultPlan(seed=7).add("slice_corruption", count=2))
        store.get_shard(0, 1)
        tr = get_tracer()
        reads = [sp for sp in tr.all_spans() if sp.name == "store.read"]
        assert reads and reads[-1].labels.get("recovered") is True
        assert reads[-1].labels.get("corrupted") == 2
        names = set(tr.span_names())
        assert names & {"fault.inject", "fault.recovery"}
        counters = tr.metrics.snapshot()["counters"]
        assert any(k.startswith("fault.") for k in counters)

    def test_null_tracer_overhead_bounded_below_2pct(self):
        # per-call cost of the disabled instrumentation path
        set_tracer(NULL_TRACER)
        tr = get_tracer()
        n = 100_000
        t0 = time.perf_counter()
        for _ in range(n):
            with tr.span("stage.train", engine="stage", shards=2) as sp:
                sp.annotate(stage=1)
        per_call = (time.perf_counter() - t0) / n

        # count the instrumentation sites one traced stage actually hits,
        # and the wall of the same stage untraced (warm jit)
        sim = _tiny_sim(seed=0)
        train_stage(sim, store_kind="coded", engine="stage")   # warm
        t0 = time.perf_counter()
        train_stage(sim, store_kind="coded", engine="stage")
        stage_wall = time.perf_counter() - t0
        configure(enabled=True)
        train_stage(sim, store_kind="coded", engine="stage")
        n_sites = len(get_tracer().all_spans())
        set_tracer(NULL_TRACER)

        # arithmetic bound: even charging 4 no-op calls per recorded span
        # (span + annotate + metrics + slack), disabled-tracer overhead
        # stays under 2% of the measured stage wall
        overhead = per_call * 4 * max(n_sites, 1)
        assert overhead < 0.02 * stage_wall, (
            f"null-tracer overhead {overhead * 1e6:.1f}us "
            f"({per_call * 1e9:.0f}ns/call x {n_sites} sites) exceeds 2% "
            f"of stage wall {stage_wall * 1e3:.1f}ms")


# ------------------------------------------- profiler trace and device names
STAGE_SPANS = {"repro.session.stage", "repro.stage.train", "repro.stage.plan",
               "repro.stage.data", "repro.xla.stage_program",
               "repro.store.put_stage", "repro.stage.collect"}


def _profiled_span_names(fn):
    """The ``repro.*`` host annotations of a profiler trace of ``fn()``."""
    import glob
    import tempfile

    import jax
    from jax.profiler import ProfileData
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            fn()
        finally:
            jax.profiler.stop_trace()
        data = ProfileData.from_file(
            glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0])
    return [e.name for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("repro.")]


def _compiled_text(fn, *args):
    import jax
    return jax.jit(fn).lower(*args).compile().as_text()


class TestProfilerTrace:
    @pytest.mark.parametrize("enabled", [True, False])
    def test_stage_spans_in_profiler_trace(self, enabled):
        sim = _tiny_sim(seed=0)
        session = FederatedSession(sim, store_kind="coded", engine="stage")
        session.run_stage()                                  # compile first
        configure(enabled=enabled)

        def two_stages():
            for _ in range(2):
                session.run_stage()
        names = _profiled_span_names(two_stages)
        if enabled:
            assert set(names) == STAGE_SPANS
            assert all(names.count(n) == 2 for n in STAGE_SPANS)
            assert len(get_tracer().all_spans()) == 2 * len(STAGE_SPANS)
        else:
            assert names == []

    def test_span_annotation_name_carries_no_labels(self):
        tr = configure(enabled=True)

        def spans():
            with tr.span("outer", stage=3):
                tr.event("mark", hit=True)
        assert sorted(_profiled_span_names(spans)) == ["repro.mark",
                                                       "repro.outer"]

    def test_stage_program_module_and_scopes(self):
        import jax
        from repro.models import init_params
        sim = _tiny_sim(seed=0)
        fl = sim.fl
        per = fl.clients_per_round // fl.num_shards
        xs, ys = sim._stack_client_data(list(range(per)))
        xs = jnp.stack([xs] * fl.num_shards)
        ys = jnp.stack([ys] * fl.num_shards)
        w0 = init_params(sim.cfg, jax.random.key(0))
        enc = jnp.asarray(CodingScheme(fl.num_shards, fl.clients_per_round)
                          .encode_matrix(), jnp.float32)
        prog = sim._get_stage_program(fl.local_epochs, "flat",
                                      fl.global_rounds, encode=True)
        text = prog.lower(w0, xs, ys, enc).compile().as_text()
        assert text.startswith("HloModule jit_stage_program")
        for scope in ("fl.local_train", "fl.aggregate", "coding.encode"):
            assert re.search(r'op_name="[^"]*' + re.escape(scope), text), scope

    @pytest.mark.parametrize("use_kernel", [False, True])
    def test_encode_rounds_scope_on_both_paths(self, use_kernel):
        from repro.core import coding
        enc = jnp.ones((6, 2), jnp.float32)
        hist = jnp.ones((3, 2, 256), jnp.float32)
        text = _compiled_text(
            lambda e, h: coding.encode_rounds(e, h, use_kernel=use_kernel),
            enc, hist)
        assert 'op_name="jit(<lambda>)/coding.encode/' in text
