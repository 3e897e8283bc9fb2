"""Unlearning-framework registry — strategy classes replacing the simulator's
if/elif chain.

Each framework is a class registered under one or more names
(``@register_framework("SE", "SE-uncoded")``).  ``run`` receives an
``UnlearnContext`` — the stage record plus every capability the seed
``FLSimulator.unlearn`` body used (stacked client data, jitted
calibrated-retraining / local-training steps, historical update norms moved
to device once, shard-impact analysis, stored-round reconstruction through
the parameter store) — and returns ``(models, cost_units)``.  A third-party
framework (e.g. Halimi et al.'s PGD client erasure) is therefore one file:
subclass ``UnlearnFramework``, decorate, and every driver (``FLSimulator``
shim, ``FederatedSession``, ``run_scenario``) can dispatch to it by name.

``run_unlearn`` is the dispatch entry point: it times the framework, blocks
on the result, and packages an ``UnlearnResult``.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Type

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import unlearning
from repro.telemetry import get_tracer


@dataclass
class UnlearnContext:
    """Everything a framework needs to serve one unlearning request against
    one stage record."""
    sim: object                       # FLSimulator (jitted steps, data, cfg)
    record: object                    # StageRecord
    requests: List[int]               # client ids to erase
    rounds: int                       # unlearning rounds G'
    available: Optional[Sequence[int]] = None   # reachable coded slices
    corrupt: Optional[np.ndarray] = None        # modelled slice corruption

    # ------------------------------------------------------------ accessors
    @property
    def plan(self):
        return self.record.plan

    @property
    def fl(self):
        return self.sim.fl

    @property
    def mgr(self):
        return self.sim.mgr

    @property
    def retrain_epochs(self) -> int:
        """L/r — the reduced local-epoch budget of calibrated retraining."""
        return max(int(self.fl.local_epochs / self.fl.retrain_ratio), 1)

    @property
    def impacted(self) -> List[int]:
        """S' — shards containing at least one requested client."""
        return sorted(self.mgr.impacted_shards(self.plan, self.requests))

    def retained(self, shard: int) -> List[int]:
        return self.mgr.retained(self.plan, shard, self.requests)

    def retained_all(self) -> List[int]:
        gone = set(self.requests)
        return [c for c in self.plan.clients if c not in gone]

    # ------------------------------------------------------------- data/steps
    def stack_client_data(self, clients: Sequence[int]):
        return self.sim._stack_client_data(clients)

    def stored_round(self, shard: int, rnd: int) -> Dict[int, object]:
        """Reconstruct one shard's stored round from the parameter store
        (decoding through erasures/corruption for the coded store)."""
        return self.record.store.get_shard(rnd, shard,
                                           available=self.available,
                                           corrupt=self.corrupt)

    def all_stored_round(self, rnd: int) -> Dict[int, object]:
        out = {}
        for s in self.plan.shard_clients:
            out.update(self.stored_round(s, rnd))
        return out

    def stored_norms(self, shard_of: Callable[[int], int],
                     retained: Sequence[int], n_rounds: int) -> jnp.ndarray:
        """(G', M) historical update norms, moved to device once."""
        hn = self.record.history_norms
        return jnp.asarray(
            [[hn[(shard_of(c), g, c)] for c in retained]
             for g in range(n_rounds)], jnp.float32)

    def calib_round(self, w, xs, ys, round_norms):
        """One fused calibrated-retraining round (eq. 3) at L/r epochs."""
        return self.sim._calib_round[self.retrain_epochs](w, xs, ys,
                                                          round_norms)

    def calib_stage(self, ws, xs, ys, nmats):
        """The whole calibrated-retraining pass of K shards in ONE dispatch:
        ``calib_round`` vmapped over the stacked (K, ...) shard models and
        scanned over the G' rounds.  nmats: (G', K, M') stored norms."""
        return self.sim._calib_stage[self.retrain_epochs](ws, xs, ys, nmats)

    def local_train(self, w, xs, ys, epochs: int, fisher=None):
        """Vmapped local training -> stacked (M, ...) client params."""
        if fisher is not None:
            return self.sim._local_train[(epochs, "fisher")](w, xs, ys, fisher)
        return self.sim._local_train[epochs](w, xs, ys)

    def stacked_mean(self, stacked):
        return self.sim._stacked_mean(stacked)

    def init_model(self, salt: int = 777):
        return self.sim.init_params(jax.random.key(self.sim.seed + salt))

    def stage_init_model(self):
        """The stage's ACTUAL initial model w0 (seeded by ``plan.stage``,
        exactly as ``train_stage`` built it) — retraining from it with a
        client removed is the bit-exact counterfactual the retrain oracle
        (``repro.verify.oracle``) measures against."""
        return self.sim.init_params(
            jax.random.key(self.sim.seed + self.plan.stage))

    def retrain_shards(self, w0, xs, ys, g_rounds: int):
        """From-scratch FedAvg of a stacked ``(K, M, n, ...)`` batch of
        shards at the FULL L local epochs in one dispatch (vmap-over-shards
        × scan-over-rounds, reusing the stage engine's round body) — the
        exact-unlearning ground-truth pass.  Returns the ``(K, ...)`` final
        shard models."""
        prog = self.sim._get_retrain_program(self.fl.local_epochs, g_rounds)
        return prog(w0, xs, ys)

    def estimate_fisher(self, w, clients: Sequence[int]):
        return self.sim._estimate_fisher(w, clients)


class UnlearnFramework:
    """Base class for unlearning strategies.  Subclass, implement ``run``,
    and register with ``@register_framework(name, *aliases)``."""

    name: str = ""
    # shard-level strategies retrain only impacted shards and return one
    # model per shard; federation-level ones retrain everything ({0: w})
    shard_level: bool = False

    def run(self, ctx: UnlearnContext):
        """Return ``(models, cost_units)`` where ``models`` maps shard id to
        the unlearned model ({0: w} for federation-level frameworks) and
        ``cost_units`` counts client-epochs of retraining."""
        raise NotImplementedError

    @classmethod
    def impacted_shards(cls, plan, clients: Sequence[int]) -> List[int]:
        """The shards this strategy would retrain for ``clients`` on
        ``plan`` — what the strategy reports to the service scheduler so it
        can merge due requests per impacted shard and place shard programs
        on devices.  Federation-level strategies touch every shard; SE
        overrides with the membership-based impacted set."""
        return sorted(plan.shard_clients)


FRAMEWORKS: Dict[str, Type[UnlearnFramework]] = {}


def register_framework(*names: str):
    """Class decorator registering an ``UnlearnFramework`` under ``names``."""
    if not names:
        raise ValueError("register_framework needs at least one name")

    def deco(cls: Type[UnlearnFramework]) -> Type[UnlearnFramework]:
        cls.name = names[0]
        for n in names:
            FRAMEWORKS[n] = cls
        return cls
    return deco


def get_framework(name: str) -> UnlearnFramework:
    try:
        return FRAMEWORKS[name]()
    except KeyError:
        raise ValueError(f"unknown unlearning framework {name!r}; "
                         f"registered: {sorted(FRAMEWORKS)}") from None


def run_unlearn(sim, framework: str, record, requests: Sequence[int],
                rounds: Optional[int] = None,
                available: Optional[Sequence[int]] = None,
                corrupt: Optional[np.ndarray] = None):
    """Dispatch one unlearning request to the registered framework and
    package the timed ``UnlearnResult``."""
    from repro.fl.simulator import UnlearnResult

    fw = get_framework(framework)
    ctx = UnlearnContext(sim, record, list(requests),
                         rounds or sim.fl.global_rounds, available, corrupt)
    t0 = time.perf_counter()
    impacted = ctx.impacted
    with get_tracer().span("unlearn.dispatch", framework=fw.name,
                           clients=sorted(requests),
                           impacted=impacted) as sp:
        models, cost = fw.run(ctx)
        # block on EVERY returned model: blocking only the first dict entry
        # under-measures serves whose impacted shard is not the first key
        # (its retrain would still be in flight when the wall is recorded)
        jax.block_until_ready(list(models.values()))
        sp.annotate(cost_units=float(cost))
    wall = time.perf_counter() - t0
    stats = getattr(record.store, "stats", None)
    return UnlearnResult(framework, models, wall, cost, stats, impacted)


# ---------------------------------------------------------------------------
# The paper's four frameworks
# ---------------------------------------------------------------------------

@register_framework("SE", "SE-uncoded")
class ShardedEraser(UnlearnFramework):
    """SE (paper Sec 4): isolation means only impacted shards retrain —
    preparation from the stored round-0 locals (eq. 2), then calibrated
    retraining at L/r epochs (eq. 3).  "SE-uncoded" is the same algorithm
    reading from an uncoded shard store.

    When the request (or a batched group of requests) impacts SEVERAL shards
    with identical geometry (same retained count, sample count, and round
    budget), the whole retraining pass runs as one ``calib_stage`` program —
    the impacted shards vmapped together, the G' rounds scanned — instead of
    a Python loop of G' dispatches per shard.  Ragged shard batches fall back
    to the per-shard loop (identical math).

    The per-shard pieces are exposed for the online service
    (``repro.service``): ``prepare_shard_job`` builds one shard's job and
    ``run_prepared_job`` (module-level) retrains it — optionally on an
    explicit device — so independent shard programs can dispatch
    asynchronously across devices."""

    shard_level = True

    def run(self, ctx: UnlearnContext):
        models = dict(ctx.record.shard_models)
        jobs = self.prepare_jobs(ctx)
        if len(jobs) > 1 and self._batchable(jobs):
            out, cost = self._run_batched(ctx, jobs)
        else:
            out, cost = self._run_sequential(ctx, jobs)
        models.update(out)
        return models, cost

    @classmethod
    def impacted_shards(cls, plan, clients: Sequence[int]) -> List[int]:
        hit = set(clients)
        return sorted(s for s, cs in plan.shard_clients.items()
                      if hit & set(cs))

    # ------------------------------------------------------------- plumbing
    @staticmethod
    def prepare_shard_job(ctx: UnlearnContext, shard: int):
        """One impacted shard's retraining job: stacked retained data, the
        eq.-(2) prepared initial model (from the store's reconstructed
        round-0 locals), and the (G', M') stored-norm matrix.  ``None`` when
        every client of the shard was requested (nothing to retrain on)."""
        retained = ctx.retained(shard)
        if not retained:
            return None
        xs, ys = ctx.stack_client_data(retained)
        stored0 = ctx.stored_round(shard, 0)
        w0 = unlearning.prepare_initial_model(
            [stored0[c] for c in retained])
        n_r = min(ctx.rounds, len(ctx.record.round_globals[shard]) - 1)
        nmat = ctx.stored_norms(lambda c, s=shard: s, retained, n_r)
        return (shard, retained, xs, ys, w0, nmat, n_r)

    def prepare_jobs(self, ctx: UnlearnContext):
        jobs = (self.prepare_shard_job(ctx, s) for s in ctx.impacted)
        return [j for j in jobs if j is not None]

    @staticmethod
    def _batchable(jobs) -> bool:
        shapes = {(j[2].shape, j[6]) for j in jobs}
        return len(shapes) == 1

    def _run_sequential(self, ctx: UnlearnContext, jobs):
        models, cost = {}, 0.0
        for job in jobs:
            s, w, c = run_prepared_job(ctx, job)
            models[s] = w
            cost += c
        return models, cost

    def _run_batched(self, ctx: UnlearnContext, jobs):
        """All impacted shards retrain in ONE ``calib_stage`` dispatch."""
        ws = jax.tree.map(lambda *a: jnp.stack(a), *[j[4] for j in jobs])
        xs = jnp.stack([j[2] for j in jobs])
        ys = jnp.stack([j[3] for j in jobs])
        nmats = jnp.stack([j[5] for j in jobs], axis=1)      # (G', K, M')
        out = ctx.calib_stage(ws, xs, ys, nmats)
        models, cost = {}, 0.0
        for i, (s, retained, *_rest, n_r) in enumerate(jobs):
            models[s] = jax.tree.map(lambda a, i=i: a[i], out)
            cost += n_r * len(retained) * ctx.retrain_epochs
        return models, cost


def run_prepared_job(ctx: UnlearnContext, job, device=None):
    """Retrain ONE prepared shard job (eq. 3, fused stacked rounds) and
    return ``(shard, model, cost_units)``.

    With ``device`` set, the job's tensors are committed there first, so the
    G' jitted calibration rounds dispatch asynchronously *on that device* —
    the unit of work the service's ``DevicePlacement`` spreads across
    ``jax.devices()``.  ``device=None`` is bit-identical to the in-process
    sequential path (it IS the sequential path)."""
    s, retained, xs, ys, w, nmat, n_r = job
    with get_tracer().span("unlearn.shard", shard=s, rounds=n_r,
                           retained=len(retained)):
        if device is not None:
            xs, ys, w, nmat = jax.device_put((xs, ys, w, nmat), device)
        cost = 0.0
        for g in range(n_r):
            w = ctx.calib_round(w, xs, ys, nmat[g])
            cost += len(retained) * ctx.retrain_epochs
    return s, w, cost


@register_framework("FE")
class FedEraser(UnlearnFramework):
    """FedEraser without sharding: calibrated retraining over ALL retained
    clients from the full central store."""

    def run(self, ctx: UnlearnContext):
        retained = ctx.retained_all()
        xs, ys = ctx.stack_client_data(retained)
        stored0 = ctx.all_stored_round(0)
        w = unlearning.prepare_initial_model([stored0[c] for c in retained])
        nmat = ctx.stored_norms(ctx.plan.shard_of, retained, ctx.rounds)
        cost = 0.0
        for g in range(ctx.rounds):
            w = ctx.calib_round(w, xs, ys, nmat[g])
            cost += len(retained) * ctx.retrain_epochs
        return {0: w}, cost


class _FullRetrain(UnlearnFramework):
    """Federation-wide retraining from scratch (no stored parameters used)."""

    use_fisher = False

    def run(self, ctx: UnlearnContext):
        retained = ctx.retained_all()
        xs, ys = ctx.stack_client_data(retained)
        w = ctx.init_model(777)
        ep = ctx.retrain_epochs if self.use_fisher else ctx.fl.local_epochs
        # RR: estimate the diagonal Fisher on retained data once
        fisher = ctx.estimate_fisher(w, retained) if self.use_fisher else None
        cost = 0.0
        for g in range(ctx.rounds):
            locals_ = ctx.local_train(w, xs, ys, ep, fisher)
            w = ctx.stacked_mean(locals_)
            cost += len(retained) * ep
        return {0: w}, cost


@register_framework("FR")
class FedRetrain(_FullRetrain):
    """The gold standard: full retraining at the original L epochs."""
    use_fisher = False


@register_framework("RR")
class RapidRetrain(_FullRetrain):
    """Rapid retraining: reduced epochs with diagonal-Fisher preconditioned
    local steps."""
    use_fisher = True
