"""Paper Fig. 3: performance with a SINGLE unlearning request.

For each framework (FR / FE / RR / SE) x task (image, lm) x distribution
(IID, non-IID): unlearned-model quality (accuracy / loss) and retraining time.
SE's claim: comparable accuracy to FR at a fraction of the retraining time.
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import Scale, build_image_sim, build_lm_sim, emit
from repro.fl.experiment import train_stage

FRAMEWORKS = ("FR", "FE", "RR", "SE")


def run(sc: Scale, tasks=("image", "lm"), iids=(True, False)):
    for task in tasks:
        for iid in iids:
            tag = f"fig3_{task}_{'iid' if iid else 'noniid'}"
            sim, test = (build_image_sim if task == "image" else build_lm_sim)(
                sc, iid=iid)
            record = train_stage(sim, store_kind="coded")
            base = sim.evaluate(record.shard_models, *test)
            emit(f"{tag}_trained", 0.0,
                 f"acc={base['acc']:.4f};loss={base['loss']:.4f}")
            victim = record.plan.shard_clients[0][0]
            for fw in FRAMEWORKS:
                res = sim.unlearn(fw, record, [victim])
                m = sim.evaluate(res.models, *test)
                emit(f"{tag}_{fw}", res.wall_time * 1e6,
                     f"acc={m['acc']:.4f};loss={m['loss']:.4f};"
                     f"cost_units={res.cost_units:.0f};"
                     f"retrain_s={res.wall_time:.2f}")
            fr = sim.unlearn("FR", record, [victim])
            se = sim.unlearn("SE", record, [victim])
            gain = 1.0 - se.cost_units / max(fr.cost_units, 1e-9)
            emit(f"{tag}_SE_vs_FR_cost_reduction", 0.0, f"gain={gain:.2%}")


if __name__ == "__main__":
    run(Scale())
