"""Paper Fig. 4: performance with CONCURRENT unlearning requests, in the
'Even' (spread across shards) and 'Adapt' (all in one shard) patterns.

SE's claim: the retraining cost follows eq. (10) — only distinct impacted
shards retrain — so Adapt is much cheaper than Even, and both beat FR/FE/RR
which always retrain the full federation.
"""
from __future__ import annotations

from benchmarks.common import Scale, build_image_sim, build_lm_sim, emit
from repro.fl.experiment import train_stage
from repro.core.sharding import adaptive_requests, even_requests

FRAMEWORKS = ("FR", "FE", "RR", "SE")


def run(sc: Scale, k: int = 4, tasks=("image", "lm")):
    for task in tasks:
        sim, test = (build_image_sim if task == "image" else build_lm_sim)(
            sc, iid=True)
        record = train_stage(sim, store_kind="coded")
        for pattern, reqfn in (("even", even_requests),
                               ("adapt", adaptive_requests)):
            requests = reqfn(record.plan, k)
            tag = f"fig4_{task}_{pattern}"
            for fw in FRAMEWORKS:
                res = sim.unlearn(fw, record, requests)
                m = sim.evaluate(res.models, *test)
                emit(f"{tag}_{fw}", res.wall_time * 1e6,
                     f"acc={m['acc']:.4f};loss={m['loss']:.4f};"
                     f"cost_units={res.cost_units:.0f};"
                     f"impacted={len(res.impacted_shards)}")


if __name__ == "__main__":
    run(Scale())
