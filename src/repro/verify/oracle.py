"""Retrain-from-scratch oracle — the ground truth that defines EXACT
unlearning (Halimi et al., arXiv 2207.05521): the model the federation would
have produced had the requested clients never participated.

Under the paper's isolation a shard's model is a pure function of its own
clients' data, so the exact counterfactual is computable per shard: restart
from the stage's actual initial model (same ``plan.stage``-derived seed), run
the stage's G rounds at the FULL L local epochs, with the requested clients'
data simply absent.  The pass reuses the stage program's ``shard_round``
body — impacted shards with matching geometry are vmapped together and the
rounds scanned, one XLA dispatch for the whole oracle
(``FLSimulator._get_retrain_program``).

Registered as an unlearning framework (``"oracle"``), so every driver —
``run_unlearn``, ``FederatedSession``, the online service — can dispatch to
it by name, and the verification suite scores approximate frameworks
(SE/FE/RR) against it with the same ``UnlearnResult`` wall/cost accounting.
It is NOT a practical serving framework: its cost is the full retraining
bill the paper's SE exists to avoid.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.fl.experiment.frameworks import (UnlearnContext, UnlearnFramework,
                                            register_framework)


@register_framework("oracle", "retrain-oracle")
class RetrainOracle(UnlearnFramework):
    """Exact per-shard retraining on retained data only — the reference
    every approximate framework's forgetting is measured against."""

    shard_level = True
    exact = True     # marks the ground-truth framework for reports/tests

    def run(self, ctx: UnlearnContext):
        models = dict(ctx.record.shard_models)
        w0 = ctx.stage_init_model()
        jobs = []
        for s in ctx.impacted:
            retained = ctx.retained(s)
            # the stage's ACTUAL round count, not the request's G' budget:
            # the oracle replays history, it doesn't serve a reduced retrain
            g = len(ctx.record.round_globals[s]) - 1
            if not retained:
                # every client of the shard was erased: the counterfactual
                # shard never trained, its model is the from-scratch init
                models[s] = w0
                continue
            xs, ys = ctx.stack_client_data(retained)
            jobs.append((s, retained, xs, ys, g))

        cost = 0.0
        groups: dict = {}
        for job in jobs:
            groups.setdefault((job[2].shape, job[4]), []).append(job)
        for (_shape, g), group in groups.items():
            xs = jnp.stack([j[2] for j in group])      # (K, M', n, ...)
            ys = jnp.stack([j[3] for j in group])
            final = ctx.retrain_shards(w0, xs, ys, g)
            for i, (s, retained, *_rest) in enumerate(group):
                models[s] = jax.tree.map(lambda a, i=i: a[i], final)
                cost += g * len(retained) * ctx.fl.local_epochs
        return models, cost

    @classmethod
    def impacted_shards(cls, plan, clients):
        hit = set(clients)
        return sorted(s for s, cs in plan.shard_clients.items()
                      if hit & set(cs))
