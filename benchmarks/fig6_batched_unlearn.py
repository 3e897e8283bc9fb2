"""Batched-vs-sequential session unlearning on one stage.

Four SE requests overlapping on two shards of one stage, served
sequentially (each request retrains its whole shard — four calibrated
retrains) vs merged (``batch_requests=True``: each shard retrains once, both
shards in one vmapped ``calib_stage`` dispatch).  Emits each mode's median
unlearning wall and the speed-up.  Timings are of whatever backend runs it;
on the CPU they are not device metrics.
"""
from __future__ import annotations

import statistics

from benchmarks.common import Scale, build_image_sim, collect_report, emit

ITERS = 3


def _batched_unlearn(sc: Scale, tag: str):
    """N=4 overlapping SE requests (two per shard on two shards of one
    stage): served sequentially (each request triggers a full calibrated
    retraining of its shard — overlapping shards retrain once PER REQUEST)
    vs merged into one batch (each impacted shard retrains ONCE with the
    union of its requested clients removed, the two shards vmapped into a
    single calib_stage dispatch)."""
    from repro.fl.experiment import (FederatedSession, RequestSchedule,
                                     UnlearnRequest)

    def schedule():
        return RequestSchedule([
            UnlearnRequest(lambda p, s=s, i=i: [p.shard_clients[s][i]],
                           framework="SE", after_stage=0)
            for s in (0, 1) for i in (0, 1)
        ])

    walls = {}
    for mode, batch in (("sequential", False), ("batched", True)):
        sim, _ = build_image_sim(sc, iid=True)    # one sim: jits stay warm
        per_iter = []
        report = None
        for it in range(ITERS + 1):            # iter 0 warms the jit caches
            session = FederatedSession(sim, store_kind="coded",
                                       batch_requests=batch)
            report = session.run(1, schedule=schedule())
            if it > 0:
                per_iter.append(report.total_unlearn_wall * 1e6)
        walls[mode] = statistics.median(per_iter)
        served = sum(len(st.unlearn) for st in report.stages)
        emit(f"fig6_unlearn_4req_{mode}{tag}", walls[mode],
             f"SE;4 requests;{served} serve(s);median_of={ITERS}")
        collect_report(f"fig6_4req_{mode}{tag}", report)
    emit(f"fig6_batched_unlearn_speedup{tag}", 0.0,
         f"batched_vs_sequential="
         f"{walls['sequential'] / walls['batched']:.2f}x")


def run(sc: Scale):
    _batched_unlearn(sc, "")


if __name__ == "__main__":                 # PYTHONPATH=src python -m benchmarks.fig6_batched_unlearn
    run(Scale())
