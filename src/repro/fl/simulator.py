"""CPU-scale federated learning + unlearning simulator (paper Sec 5).

Runs the paper's experimental protocol end-to-end on any registered task ×
model family (``repro.fl.tasks`` / ``repro.fl.families`` — the paper's CNN
classifier and NanoGPT, plus mamba / rwkv6 / moe): C clients, a sampled
subset per stage split into S isolated shards, FedAvg within shards,
intermediate-parameter storage (full / uncoded-shard / coded), and the four
unlearning frameworks (FR / FE / RR / SE).  Task-shaped behavior (batch
construction, per-example label counts, eval metrics) is delegated to the
``TaskSpec``.

The simulator is the *engine room*: it owns the client data, the jitted
training/calibration steps, and evaluation.  Orchestration lives in
``repro.fl.experiment``:

* ``experiment.train_stage(sim, ...)`` — one stage against a registered
  parameter store (``STORES``; ``full`` / ``uncoded`` / ``coded``).
* ``experiment.run_unlearn(sim, framework, ...)`` — dispatch to a registered
  unlearning framework (``FRAMEWORKS``; ``SE`` / ``FE`` / ``FR`` / ``RR``).
* ``experiment.FederatedSession`` — K stages with a scheduled stream of
  unlearning requests (the paper's cross-stage isolation).

``FLSimulator.unlearn`` remains as a deprecated thin shim over
``run_unlearn``.

Stage training
--------------
One path (``repro.fl.experiment.stage``): a stage whose shard data stacks to
``(S, M, n, ...)`` runs the stage program (``_get_stage_program``) — ONE
jitted dispatch giving the ``(G+1, S, ...)`` round globals, the ``(G, S, M)``
update norms and, for the coded store, the coded slices, encoded *inside the
same XLA program* (``coding.encode_rounds``).  A ragged stage (unequal
clients or sample counts per shard) falls back to one jitted ``shard_round``
per (shard, round): vmapped local training, FedAvg mean, the per-client
update norms as one (M,) reduction, and the stacked (M, P) flat parameter
matrix (``coding.tree_to_flat_stacked``) in one program.

Adapters over a frozen base
---------------------------
A model configured with LoRA adapters (``ModelConfig.lora_rank``) federates
its adapters: the trainable tree, the stored client models, the coded slices
and the SE calibration are the adapter tree, and the frozen base, drawn once
from the seed, is ``sim.base``.  Every jitted program takes the base as an
argument (``_jit``): broadcast to every client, never vmapped and never one
of the program's constants, so the clients' tokens fold into the rows of the
base's matmuls.  Without adapters ``sim.base`` is None and every program is
the one it always was.

SE/FE calibrated retraining (eq. 3) runs through ``calib_round`` — vmapped
retraining plus ``unlearning.calibrate_stacked`` fused in one jit — and, when
several shards retrain together (batched unlearning requests), through the
``calib_stage`` program: the impacted shards vmapped together and the G'
calibration rounds scanned, one dispatch for the whole retraining pass.
"""
from __future__ import annotations

import functools
import json
import warnings
from dataclasses import dataclass, field
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.stores.store import StoreStats, make_store
from repro.configs.base import FLConfig, ModelConfig, OptimizerConfig
from repro.core import coding, unlearning
from repro.core.sharding import ShardManager, StagePlan
from repro.fl.tasks import resolve_task
from repro.models import (init_adapters, init_params, loss_fn, merge_adapters,
                          predict_fn)
from repro.models.moe import pair_buffer_sizes
from repro.optim import make_optimizer
from repro.optim.fisher import diag_fisher, fisher_precondition


class StackedRoundGlobals:
    """List-like view of one shard's per-round global models, backed by the
    stage program's stacked ``(G, S, ...)`` output — length G+1 like the
    materialized per-shard lists, but each element is sliced out of the
    stacked buffers only on access (the stage program dispatches nothing for
    bookkeeping it never reads)."""

    def __init__(self, round_inputs, final, shard_index: int):
        self._inputs = round_inputs               # (G, S, ...) stacked tree
        self._final = final                       # (S, ...) stacked tree
        self._idx = shard_index
        self._len = int(jax.tree.leaves(round_inputs)[0].shape[0]) + 1

    def __len__(self) -> int:
        return self._len

    def __getitem__(self, g):
        if isinstance(g, slice):
            return [self[i] for i in range(*g.indices(self._len))]
        if g < 0:
            g += self._len
        if not 0 <= g < self._len:
            raise IndexError(g)
        if g == self._len - 1:
            return jax.tree.map(lambda a: a[self._idx], self._final)
        return jax.tree.map(lambda a, g=g: a[g, self._idx], self._inputs)

    def __iter__(self):
        return (self[i] for i in range(self._len))


@dataclass
class StageRecord:
    plan: StagePlan
    shard_models: Dict[int, object]               # final per-shard globals
    round_globals: Dict[int, object]              # shard -> [w^g inputs],
    # len G+1 (a lazy StackedRoundGlobals view of the stage program's output,
    # or a list from the per-shard round of a ragged stage)
    store: object                                 # parameter store
    history_norms: Dict[Tuple[int, int, int], float] = field(default_factory=dict)
    # (shard, round, client) -> ||delta|| of the stored update
    # tokens the stage routed to each held expert, (MoE layers, held), on
    # device; None for a model without held experts or for a ragged stage
    expert_load: Optional[object] = None


@dataclass
class UnlearnResult:
    framework: str
    models: Dict[int, object]        # shard -> unlearned model (single: {0: w})
    wall_time: float
    cost_units: float                # client-epochs of retraining
    store_stats: Optional[StoreStats]
    impacted_shards: Sequence[int]
    request_id: str = ""             # stable id of the request that produced it

    def to_dict(self) -> dict:
        """Machine-readable summary (models excluded — they are pytrees)."""
        return {
            "request_id": self.request_id,
            "framework": self.framework,
            "wall_time_s": self.wall_time,
            "cost_units": self.cost_units,
            "impacted_shards": [int(s) for s in self.impacted_shards],
            "num_models": len(self.models),
            "store_stats": (self.store_stats.to_dict()
                            if self.store_stats is not None else None),
        }

    def to_json(self, **kw) -> str:
        kw.setdefault("indent", 2)
        return json.dumps(self.to_dict(), **kw)


@dataclass(frozen=True)
class PredictInterface:
    """The simulator's public evaluation surface.

    Everything an external evaluator (the MIA attack, canary probes,
    benchmarks) needs to score models without reaching into ``FLSimulator``
    internals: the pure ``predict(model, batch) -> logits`` function, the
    task's batch constructor, and the ``TaskSpec`` itself (which owns metric
    and MIA-feature shapes).  Obtained via ``FLSimulator.predict_interface``.
    """
    predict: Callable
    make_batch: Callable
    task: object                       # the simulator's TaskSpec instance

    def ensemble_logits(self, models: Dict[int, object], x, y):
        """Mean float32 logits of a model ensemble on one batch."""
        batch = self.make_batch(jnp.asarray(x), jnp.asarray(y))
        logits = None
        for m in models.values():
            lg = self.predict(m, batch)
            logits = lg if logits is None else logits + lg
        return (logits / len(models)).astype(jnp.float32)


class FLSimulator:
    def __init__(self, model_cfg: ModelConfig, fl_cfg: FLConfig,
                 client_data: Dict[int, Tuple[np.ndarray, np.ndarray]],
                 task, opt_cfg: Optional[OptimizerConfig] = None,
                 local_batch: int = 20, seed: int = 0):
        self.cfg = model_cfg
        self.fl = fl_cfg
        # a registered TaskSpec (or its name; "image"/"lm" resolve as the
        # legacy aliases of classification/generation)
        self.task_spec = resolve_task(task)
        self.task = self.task_spec.name
        self.opt = opt_cfg or OptimizerConfig(name="sgdm", lr=0.05, grad_clip=0.0)
        self.client_data = client_data
        self.local_batch = local_batch
        self.seed = seed
        self.mgr = ShardManager(fl_cfg.num_clients, fl_cfg.num_shards,
                                fl_cfg.clients_per_round, seed)
        self.base = (init_params(model_cfg, jax.random.key(seed))
                     if model_cfg.lora_rank else None)
        self._lf = loss_fn(model_cfg)
        self._predict = predict_fn(model_cfg)
        self._pf = lambda p, b: self._predict(merge_adapters(self.base, p), b)
        self._build_steps()

    def init_params(self, rng):
        """A stage's initial trainable tree: the model's parameters, or the
        adapters where the model has them (the base stays ``self.base``)."""
        if self.base is None:
            return init_params(self.cfg, rng)
        return init_adapters(self.cfg, rng)

    def _jit(self, fn):
        """``fn(base, *args)`` jitted and called as ``prog(*args)``: the
        frozen base is the program's first argument.  Without a base the
        program is ``fn(None, *args)`` under ``fn``'s own name."""
        if self.base is None:
            prog = functools.partial(fn, None)
            prog.__name__ = fn.__name__
            return jax.jit(prog)
        return functools.partial(jax.jit(fn), self.base)

    # ------------------------------------------------------------------ jit
    def _build_steps(self):
        opt_init, opt_update = make_optimizer(self.opt)

        def lf(base, params, batch):
            return self._lf(merge_adapters(base, params), batch)

        def local_train(base, params, xs, ys, epochs, fisher=None):
            """Minibatch-SGD local training. xs: (n, ...), ys: (n, ...).
            Returns (params, load): ``load`` sums the steps' tokens routed
            to each held expert (MoE layers, held), None without them."""
            bs = self.local_batch
            n = xs.shape[0] // bs * bs
            xb = xs[:n].reshape(-1, bs, *xs.shape[1:])
            yb = ys[:n].reshape(-1, bs, *ys.shape[1:])
            state = opt_init(params)

            def epoch_body(carry, _):
                params, state = carry

                def batch_body(carry, xy):
                    params, state = carry
                    x, y = xy
                    batch = self._make_batch(x, y)
                    grads, metrics = jax.grad(lambda p: lf(base, p, batch),
                                              has_aux=True)(params)
                    if fisher is not None:
                        grads = fisher_precondition(grads, fisher)
                    params, state = opt_update(params, grads, state)
                    return (params, state), metrics.get("expert_load")

                (params, state), load = jax.lax.scan(
                    batch_body, (params, state), (xb, yb))
                return (params, state), load

            with jax.named_scope("fl.local_train"):
                (params, _), load = jax.lax.scan(epoch_body, (params, state),
                                                 None, length=epochs)
            return params, None if load is None else load.sum(axis=(0, 1))

        def vmapped_train(base, params, xs, ys, epochs, fisher=None):
            """Stacked data (M, n, ...), shared initial params -> (M, ...),
            and each client's expert load (M, ...) or None."""
            return jax.vmap(lambda x, y: local_train(base, params, x, y,
                                                     epochs, fisher))(xs, ys)

        def shard_round(base, params, xs, ys, epochs, payload):
            """One fused FedAvg round for one shard — everything on device:
            vmapped local training, stacked (M,) update norms, FedAvg mean,
            and (optionally) the stacked (M, P) flat parameter matrix for the
            coded store. Returns (new_global, payload, delta_norms, load)."""
            locals_, load = vmapped_train(base, params, xs, ys, epochs)
            with jax.named_scope("fl.aggregate"):
                deltas = unlearning.stacked_sub(locals_, params)
                norms = unlearning.stacked_norms(deltas)
                new_global = unlearning.stacked_mean(locals_)
                if payload == "flat":
                    out, _ = coding.tree_to_flat_stacked(locals_)
                else:
                    out = locals_
            return new_global, out, norms, load

        def calib_round(base, params, xs, ys, stored_norms, epochs):
            """One fused SE/FE calibrated-retraining round (eq. 3): vmapped
            retraining + stacked calibration, no per-client host loop."""
            locals_, _ = vmapped_train(base, params, xs, ys, epochs)
            deltas = unlearning.stacked_sub(locals_, params)
            return unlearning.calibrate_stacked(params, deltas, stored_norms)

        def calib_stage(base, ws, xs, ys, nmats, epochs):
            """The whole calibrated-retraining pass of a batch of impacted
            shards in ONE program: ``calib_round`` vmapped over the K shards,
            ``lax.scan``-ed over the G' rounds.  ws: stacked (K, ...) initial
            models; xs/ys: (K, M', n, ...); nmats: (G', K, M') stored norms."""
            def body(w, nrow):
                w2 = jax.vmap(lambda wi, x, y, n:
                              calib_round(base, wi, x, y, n, epochs)
                              )(w, xs, ys, nrow)
                return w2, None
            out, _ = jax.lax.scan(body, ws, nmats)
            return out

        # vmap over clients: stacked data (M, n, ...), shared initial params
        self._local_train = {}
        self._shard_round = {}
        self._calib_round = {}
        self._calib_stage = {}
        for ep in set([self.fl.local_epochs,
                       max(int(self.fl.local_epochs / self.fl.retrain_ratio), 1)]):
            self._local_train[ep] = self._jit(
                lambda b, p, x, y, e=ep: vmapped_train(b, p, x, y, e)[0])
            self._local_train[(ep, "fisher")] = self._jit(
                lambda b, p, x, y, f, e=ep: vmapped_train(b, p, x, y, e, f)[0])
            for payload in ("flat", "stacked"):
                self._shard_round[(ep, payload)] = self._jit(
                    lambda b, p, x, y, e=ep, pay=payload:
                    shard_round(b, p, x, y, e, pay)[:3])
            self._calib_round[ep] = self._jit(
                lambda b, p, x, y, n, e=ep: calib_round(b, p, x, y, n, e))
            self._calib_stage[ep] = self._jit(
                lambda b, w, x, y, n, e=ep: calib_stage(b, w, x, y, n, e))
        self._stacked_mean = jax.jit(unlearning.stacked_mean)
        self._grad_fn = self._jit(
            lambda b, p, batch: jax.grad(lambda q: lf(b, q, batch)[0])(p))
        self._shard_round_fn = shard_round      # unjitted: stage-program body
        self._stage_programs = {}               # (ep, kind, G, enc?, ...) -> jit
        self._eval_stats = self._jit(self._eval_stats_fn)

    def _get_stage_program(self, epochs: int, kind: str, g_rounds: int,
                           encode: bool, out_dtype=None,
                           use_kernel: bool = False):
        """Build (and cache) the whole-stage program of a stage that stacks:
        ``shard_round`` vmapped over the S shards and scanned over the G
        rounds, with the coded store's Lagrange encode fused into the same
        XLA program (``coding.encode_rounds``) when ``encode``.

        Returns a jitted ``program(w0, xs, ys[, enc])`` producing
        ``(final (S, ...), round_inputs (G, S, ...), history, norms (G, S, M))``
        where ``history`` is the coded ``(G, C, M*P)`` slices (``encode``),
        the flat ``(G, S, M, P)`` matrices (``kind == "flat"``), or the
        stacked per-round trees (``kind == "stacked"``); a model with held
        experts adds ``load (G, S, M, MoE layers, held)``, the tokens each
        client's training routed to each held expert.  With adapters the
        program is ``functools.partial(jitted, base)`` (see ``_jit``).
        """
        key = (epochs, kind, g_rounds, encode, out_dtype, use_kernel)
        prog = self._stage_programs.get(key)
        if prog is not None:
            return prog
        shard_round = self._shard_round_fn

        def stage_body(base, w0, xs, ys):
            s = xs.shape[0]
            ws0 = jax.tree.map(
                lambda a: jnp.broadcast_to(a.astype(jnp.float32),
                                           (s,) + a.shape), w0)

            def body(ws, _):
                new_ws, out, norms, load = jax.vmap(
                    lambda p, x, y: shard_round(base, p, x, y, epochs, kind)
                )(ws, xs, ys)
                return new_ws, (ws, out, norms, load)

            final, (round_in, hist, norms, load) = jax.lax.scan(
                body, ws0, None, length=g_rounds)
            return (final, round_in, hist, norms) + (
                () if load is None else (load,))

        # named so that its module is ``jit_stage_program`` in a device trace
        if encode:
            def stage_program(base, w0, xs, ys, enc):
                final, round_in, hist, *rest = stage_body(base, w0, xs, ys)
                g, s = hist.shape[:2]
                coded = coding.encode_rounds(enc, hist.reshape(g, s, -1),
                                             use_kernel=use_kernel,
                                             out_dtype=out_dtype)
                return (final, round_in, coded, *rest)
        else:
            def stage_program(base, w0, xs, ys):
                return stage_body(base, w0, xs, ys)
        prog = self._jit(stage_program)
        self._stage_programs[key] = prog
        return prog

    def stage_pair_rows(self, xs_shape, epochs: int, g_rounds: int) -> int:
        """The rows of held-expert pair buffers that a stage program over
        data ``(S, M, n, ...)`` runs in each MoE layer: one whole buffer a
        step (``moe.pair_buffer_sizes``), for every token of the step, since
        the program's vmaps over shards and clients pool their tokens into
        one call of the layer."""
        s, m, n = xs_shape[:3]
        bs = self.local_batch
        tokens = s * m * bs * int(np.prod(xs_shape[3:]))
        held = self.cfg.experts_held or self.cfg.num_experts
        rows = pair_buffer_sizes(tokens, self.cfg.experts_per_token, held)[-1]
        return rows * g_rounds * epochs * (n // bs)

    def _get_retrain_program(self, epochs: int, g_rounds: int):
        """Lean whole-stage program for from-scratch retraining (the
        exact-unlearning oracle, ``repro.verify.oracle``): the stage program's
        ``shard_round`` body vmapped over a stacked ``(K, M, n, ...)`` shard
        batch and scanned over the G rounds, returning ONLY the final
        ``(K, ...)`` models — round history, update norms, and the store
        encode are dead outputs XLA eliminates, so the oracle pays exactly
        one dispatch and no bookkeeping memory."""
        key = ("retrain", epochs, g_rounds)
        prog = self._stage_programs.get(key)
        if prog is not None:
            return prog
        shard_round = self._shard_round_fn

        def program(base, w0, xs, ys):
            k = xs.shape[0]
            ws0 = jax.tree.map(
                lambda a: jnp.broadcast_to(a.astype(jnp.float32),
                                           (k,) + a.shape), w0)

            def body(ws, _):
                new_ws, *_ = jax.vmap(
                    lambda p, x, y: shard_round(base, p, x, y, epochs, "stacked")
                )(ws, xs, ys)
                return new_ws, None

            final, _ = jax.lax.scan(body, ws0, None, length=g_rounds)
            return final

        prog = self._jit(program)
        self._stage_programs[key] = prog
        return prog

    def _make_batch(self, x, y):
        return self.task_spec.make_batch(x, y)

    def predict_interface(self) -> PredictInterface:
        """Public evaluation surface (see ``PredictInterface``) — the stable
        API benchmarks and the verification suite evaluate through, instead
        of the private ``_pf`` / ``_make_batch`` attributes."""
        return PredictInterface(self._pf, self.task_spec.make_batch,
                                self.task_spec)

    def _stack_client_data(self, clients: Sequence[int]):
        n_min = min(self.client_data[c][0].shape[0] for c in clients)
        xs = np.stack([self.client_data[c][0][:n_min] for c in clients])
        ys = np.stack([self.client_data[c][1][:n_min] for c in clients])
        return jnp.asarray(xs), jnp.asarray(ys)

    def _make_store(self, store_kind: str, plan: StagePlan,
                    group_rounds: int = 1, slice_dtype=None, **store_options):
        """Build a registered parameter store for one stage (``STORES``).
        ``store_options`` are factory-specific knobs passed through verbatim
        (e.g. the tiered store's ``hot_bytes``/``eviction``)."""
        return make_store(store_kind, plan.shard_clients,
                          num_shards=self.fl.num_shards,
                          num_clients=self.fl.clients_per_round,
                          group_rounds=group_rounds, slice_dtype=slice_dtype,
                          **store_options)

    # --------------------------------------------------- deprecated shims
    def unlearn(self, framework: str, record: StageRecord,
                requests: Sequence[int], rounds: Optional[int] = None,
                available: Optional[Sequence[int]] = None,
                corrupt: Optional[np.ndarray] = None) -> UnlearnResult:
        """Deprecated shim over ``repro.fl.experiment.run_unlearn``."""
        warnings.warn(
            "FLSimulator.unlearn is deprecated; use "
            "repro.fl.experiment.run_unlearn(sim, ...) or FederatedSession",
            DeprecationWarning, stacklevel=2)
        from repro.fl.experiment.frameworks import run_unlearn
        return run_unlearn(self, framework, record, requests, rounds=rounds,
                           available=available, corrupt=corrupt)

    # ------------------------------------------------------------- helpers
    def _calibrate_with_norms(self, w, new_deltas, stored_norms):
        """Seed per-client calibration loop (host-synced ratio per client) —
        retained as the reference implementation for equivalence tests; the
        live path is the fused ``calib_round`` / ``calibrate_stacked``."""
        m = len(new_deltas)
        out = w
        for nd, sn in zip(new_deltas, stored_norms):
            ratio = sn / max(float(unlearning.tree_norm(nd)), 1e-12)
            out = unlearning.tree_add(out, unlearning.tree_scale(nd, ratio / m))
        return out

    def _estimate_fisher(self, params, clients: Sequence[int], n_batches: int = 4):
        fisher = None
        for i, c in enumerate(clients[:n_batches]):
            x, y = self.client_data[c]
            batch = self._make_batch(jnp.asarray(x[: self.local_batch]),
                                     jnp.asarray(y[: self.local_batch]))
            g = self._grad_fn(params, batch)
            fisher = diag_fisher(fisher, g, i)
        return fisher

    # ------------------------------------------------------------- evaluate
    def _eval_stats_fn(self, base, stacked_models, xb, yb):
        """One jitted pass over all eval batches: ``predict_fn`` vmapped over
        the stacked (K, ...) ensemble, ``lax.scan`` over the (B, batch, ...)
        batches, correct/loss accumulated on device."""
        def body(carry, xy):
            x, y = xy
            b = self._make_batch(x, y)
            logits = jax.vmap(lambda m: self._predict(merge_adapters(base, m), b)
                              )(stacked_models)
            lg = logits.astype(jnp.float32).sum(0) / logits.shape[0]
            ll = jax.nn.log_softmax(lg, -1)
            correct = (lg.argmax(-1) == y).sum()
            loss = -jnp.take_along_axis(ll, y[..., None], axis=-1).sum()
            c, l = carry
            return (c + correct, l + loss), None
        init = (jnp.zeros((), jnp.int32), jnp.zeros((), jnp.float32))
        (correct, loss), _ = jax.lax.scan(body, init, (xb, yb))
        return correct, loss

    def evaluate(self, models: Dict[int, object], xs: np.ndarray,
                 ys: np.ndarray, batch: int = 200) -> Dict[str, float]:
        """Ensemble evaluation: mean logits across shard models (SISA-style).

        The shard models are stacked to one (K, ...) tree and ``predict_fn``
        is vmapped over the ensemble inside a single jitted eval step that
        scans all batches — one host pull per eval instead of one per batch
        per model (the seed loop is kept as ``evaluate_host`` for
        equivalence testing)."""
        batch = min(batch, len(xs))
        nb = len(xs) // batch
        if nb == 0:
            return {"acc": 0.0, "loss": 0.0}
        xb = jnp.asarray(xs[:nb * batch]).reshape(nb, batch, *xs.shape[1:])
        yb = jnp.asarray(ys[:nb * batch]).reshape(nb, batch, *ys.shape[1:])
        stacked = jax.tree.map(lambda *a: jnp.stack(a), *models.values())
        correct, loss = jax.device_get(self._eval_stats(stacked, xb, yb))
        total = nb * batch * self.task_spec.labels_per_example(ys.shape)
        return self.task_spec.eval_metrics(int(correct), float(loss),
                                           max(total, 1))

    def evaluate_host(self, models: Dict[int, object], xs: np.ndarray,
                      ys: np.ndarray, batch: int = 200) -> Dict[str, float]:
        """Seed per-batch-per-model eval loop — reference implementation for
        ``evaluate`` equivalence tests."""
        total, correct, loss_sum = 0, 0, 0.0
        batch = min(batch, len(xs))
        for i in range(0, len(xs) - batch + 1, batch):
            x = jnp.asarray(xs[i:i + batch])
            y = jnp.asarray(ys[i:i + batch])
            b = self._make_batch(x, y)
            logits = None
            for m in models.values():
                lg = self._pf(m, b)
                logits = lg if logits is None else logits + lg
            logits = logits / len(models)
            ll = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
            gold = jnp.take_along_axis(ll, y[..., None], axis=-1)[..., 0]
            loss_sum += float(-gold.sum())
            correct += int((logits.argmax(-1) == y).sum())
            total += y.shape[0] * self.task_spec.labels_per_example(y.shape)
        return self.task_spec.eval_metrics(correct, loss_sum, max(total, 1))
