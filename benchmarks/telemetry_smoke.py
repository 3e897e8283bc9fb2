"""CI telemetry smoke: trace a tiny session + service run end-to-end and
validate every exported artifact.

    PYTHONPATH=src python -m benchmarks.telemetry_smoke [out_dir]

Runs two checkpointed training stages and a FIFO-served unlearning trace
under the span tracer, then asserts:

* span coverage — stage training, the fused XLA dispatch, coded-store
  writes/reads, snapshot + journal I/O, service planning/dispatch, and the
  unlearning retrain programs all produced spans;
* the Chrome/Perfetto ``trace.json`` validates against the trace-event
  schema (and is written to ``out_dir`` for the CI artifact upload);
* the service's hash-chained audit log verifies end-to-end AND re-deriving
  the chain from the write-ahead journal alone yields the same head — the
  resume/splice invariant;
* the ``ServiceReport`` carries its telemetry section.

Exits non-zero on the first failed check.
"""
from __future__ import annotations

import json
import os
import sys
import tempfile

REQUIRED_SPANS = {
    "session.stage", "stage.train", "xla.stage_program", "store.put_stage",
    "store.read", "durability.snapshot", "durability.journal_append",
    "service.plan", "service.serve", "service.dispatch", "service.job",
    "unlearn.dispatch", "unlearn.shard",
}


def main(argv=None) -> int:
    sys.path.insert(0, "src")
    args = list(sys.argv[1:] if argv is None else argv)
    out_dir = args[0] if args else "."

    from benchmarks.common import Scale, build_image_sim
    from repro.core.sharding import even_requests
    from repro.durability import Journal
    from repro.fl.experiment import (FederatedSession, RequestSchedule,
                                     UnlearnRequest)
    from repro.service import (UnlearningService, sequenced_trace,
                               single_device_placement)
    from repro.telemetry import (NULL_TRACER, configure, get_tracer,
                                 render_tree, set_tracer,
                                 validate_chrome_trace, verify_journal,
                                 write_chrome_trace)

    failures = []

    def check(ok: bool, what: str):
        print(f"[telemetry-smoke] {'ok  ' if ok else 'FAIL'} {what}",
              flush=True)
        if not ok:
            failures.append(what)

    sc = Scale(num_clients=8, clients_per_round=8, num_shards=2,
               local_epochs=2, global_rounds=2, samples_per_client=40,
               image_size=12, seq_len=32, test_n=120)
    configure(enabled=True)
    with tempfile.TemporaryDirectory() as tmp:
        sim, _ = build_image_sim(sc, iid=True)
        session = FederatedSession(sim, store_kind="coded",
                                   checkpoint_every=1, checkpoint_dir=tmp)
        # two checkpointed stages with one scheduled SE request after stage 0
        # — covers snapshot I/O, the session unlearning dispatch, and the
        # session's own audit chain alongside the service's
        schedule = RequestSchedule([
            UnlearnRequest(lambda p: [p.shard_clients[0][0]],
                           framework="SE", after_stage=0)])
        session.run(2, schedule=schedule)
        sess_head = session.audit.verify()
        check(bool(sess_head) and len(session.audit) >= 3,
              f"session audit chain verifies ({len(session.audit)} events)")
        check(verify_journal(session.checkpointer.journal) == sess_head,
              "session journal replay re-derives the same audit head")

        plan = session.records[0].plan
        victims = even_requests(plan, plan.num_shards)
        trace = sequenced_trace(victims, spacing=0.0, rounds=sc.global_rounds)
        journal = Journal(os.path.join(tmp, "svc.journal"))
        svc = UnlearningService(session, policy="fifo",
                                placement=single_device_placement(),
                                journal=journal)
        report = svc.serve(trace)

        tr = get_tracer()
        missing = REQUIRED_SPANS - set(tr.span_names())
        check(not missing, f"span coverage (missing: {sorted(missing)})")

        os.makedirs(out_dir, exist_ok=True)
        trace_path = os.path.join(out_dir, "trace.json")
        write_chrome_trace(tr, trace_path)
        with open(trace_path) as f:
            obj = json.load(f)
        errors = validate_chrome_trace(obj)
        check(not errors, f"perfetto schema ({len(errors)} errors: "
                          f"{errors[:3]})")
        check(len(obj["traceEvents"]) > len(REQUIRED_SPANS),
              f"trace.json has {len(obj['traceEvents'])} events "
              f"({os.path.getsize(trace_path)} bytes) -> {trace_path}")

        head = svc.audit.verify()
        check(bool(head), f"service audit chain verifies (head {head[:12]}, "
                          f"{len(svc.audit)} events)")
        kinds = svc.audit.kinds()
        check({"received", "scheduled", "retrained",
               "committed"} <= set(kinds),
              f"audit lifecycle kinds {sorted(set(kinds))}")
        replayed = verify_journal(journal)
        check(replayed == head,
              "journal replay re-derives the same audit head")

        d = report.to_dict()
        check("telemetry" in d, "ServiceReport.to_dict has telemetry section")
        check(bool(d.get("client_latency_p99_s")),
              "per-client p99 latency populated")

        print(render_tree(tr), flush=True)

    set_tracer(NULL_TRACER)
    if failures:
        print(f"[telemetry-smoke] {len(failures)} check(s) failed",
              flush=True)
        return 1
    print("[telemetry-smoke] all checks passed", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
