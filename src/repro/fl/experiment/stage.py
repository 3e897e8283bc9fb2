"""Stage training — one isolated-sharding FedAvg stage against a registered
parameter store.

This is the training half of the experiment layer: ``train_stage(sim, ...)``
runs G FedAvg rounds for every shard of a freshly sampled stage and writes
each round's parameters into the store through the single
``ParameterStore.put_round(RoundPayload)`` entry point.  The store's
``wants`` attribute tells the engine which payload form to compute *inside*
the jitted round step ("flat" for the coded store, "stacked" for the uncoded
ones), so the store choice never forces a host round-trip.

Three engines (dispatch count per stage in parentheses):

* ``engine="stage"`` — the whole-stage superfusion (O(1)): shard data stacked
  to (S, M, n, ...), ``shard_round`` vmapped over shards, ``lax.scan`` over
  the G rounds, and the coded store's Lagrange encode fused into the same
  program — one dispatch produces final models, round globals, update norms,
  and the coded slices.  Ragged stages (unequal client or sample counts per
  shard) degrade gracefully to the fused per-shard path.
* ``engine="fused"`` (default) — one jitted ``shard_round`` per (shard,
  round) plus one deferred batched encode (G·S + 1).
* ``engine="legacy"`` — the seed per-client path (≫ G·S·M), kept for A/B
  benchmarking.

``FLSimulator.train_stage`` is a deprecated shim over this function.
"""
from __future__ import annotations

import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.stores.store import RoundPayload
from repro.core import coding, unlearning
from repro.telemetry import get_tracer

ENGINES = ("stage", "fused", "legacy")


def train_stage(sim, store_kind: str = "coded", rounds: Optional[int] = None,
                engine: str = "fused", encode_group: Optional[int] = None,
                slice_dtype=None, faults=None, store_options=None):
    """One stage: sample clients, split into shards, G FedAvg rounds per
    shard, storing intermediate params in the requested (registered) store.

    ``engine`` selects the round engine (see module docstring):
    ``"stage"`` (one dispatch per stage), ``"fused"`` (default, one per
    shard-round), or ``"legacy"`` (the seed per-client path, for A/B).
    ``encode_group`` batches that many rounds per coded encode on the fused
    engine (default: all G in one; the stage engine always encodes all G
    inside the program).  ``slice_dtype`` optionally stores coded slices in
    e.g. bf16.  ``store_options`` passes factory-specific knobs through to
    the registered store (e.g. ``store_kind="tiered"`` budgets/eviction).

    ``faults`` (a ``repro.faults.FaultPlan``) applies the plan's client
    dropout to the freshly sampled stage (clients vanish before training —
    shards may go ragged, which the stage engine tolerates by degrading to
    the per-shard fused path, recorded as a ``DegradedModeEvent`` instead of
    a warning) and attaches the plan's slice injectors to the stage's store.

    Returns a ``StageRecord``.
    """
    if engine not in ENGINES:
        raise ValueError(f"unknown engine {engine!r}; use one of {ENGINES}")
    if engine == "legacy":
        if encode_group is not None or slice_dtype is not None:
            raise ValueError("encode_group/slice_dtype need engine="
                             "'fused' or 'stage'")
        if faults is not None:
            raise ValueError("fault plans need engine='fused' or 'stage'")
        with get_tracer().span("stage.train", engine=engine,
                               store=store_kind) as sp:
            rec = _train_stage_legacy(sim, store_kind, rounds)
            sp.annotate(stage=rec.plan.stage)
            return rec
    if engine == "stage" and encode_group is not None:
        raise ValueError("encode_group is a fused-engine option; the stage "
                         "engine always encodes all rounds in-program")

    fl = sim.fl
    g_rounds = rounds or fl.global_rounds
    tr = get_tracer()
    with tr.span("stage.train", engine=engine, store=store_kind) as sp:
        with tr.span("stage.plan") as plan_span:
            plan = sim.mgr.new_stage()
            plan_span.annotate(stage=plan.stage)
            rng = jax.random.key(sim.seed + plan.stage)
            w0 = sim.init_params(rng)
            dropped = []
            if faults is not None:
                by_shard = faults.dropped_clients(plan.stage,
                                                  plan.shard_clients)
                for s, cs in by_shard.items():
                    gone = set(cs)
                    plan.shard_clients[s] = [c for c in plan.shard_clients[s]
                                             if c not in gone]
                    dropped.extend(cs)
                dropped.sort()
            store = sim._make_store(store_kind, plan,
                                    group_rounds=encode_group or g_rounds,
                                    slice_dtype=slice_dtype,
                                    **(store_options or {}))
            if faults is not None and hasattr(store, "attach_faults"):
                store.attach_faults(faults)
        sp.annotate(stage=plan.stage, shards=len(plan.shard_clients),
                    rounds=g_rounds, dropped=len(dropped))
        # the store's preferred payload form decides what the jitted round
        # step computes on device; anything unknown degrades to stacked trees.
        kind = ("flat" if getattr(store, "wants", "stacked") == "flat"
                else "stacked")
        with tr.span("stage.data", stage=plan.stage):
            data = {s: sim._stack_client_data(cs)
                    for s, cs in plan.shard_clients.items()}
            stacked = engine == "stage" and _stackable(plan, data)
            if stacked:
                shards = sorted(plan.shard_clients)
                xs = jnp.stack([data[s][0] for s in shards])  # (S, M, n, ...)
                ys = jnp.stack([data[s][1] for s in shards])

        if stacked:
            return _run_stage_program(sim, plan, store, w0, xs, ys,
                                      g_rounds, kind, slice_dtype)
        if engine == "stage":
            sp.annotate(degraded="ragged_stage")
            if faults is not None:
                from repro.faults.events import DegradedModeEvent
                faults.ledger.record(DegradedModeEvent(
                    stage=plan.stage,
                    reason="ragged_stage", fallback="fused",
                    dropped_clients=tuple(dropped)))
            else:
                warnings.warn(
                    "ragged stage (unequal client or sample counts per "
                    "shard); stage engine degrading to per-shard fused "
                    "dispatch",
                    stacklevel=2)
        return _run_fused(sim, plan, store, w0, data, g_rounds, kind)


def _stackable(plan, data) -> bool:
    """The stage program needs one (S, M, n, ...) stack: every shard must
    hold the same number of clients with the same per-client sample count."""
    shapes = {data[s][0].shape for s in plan.shard_clients}
    return len(shapes) == 1


def _flat_row_len(w0) -> int:
    """Per-client flat parameter length P (host-side, no device work)."""
    return sum(int(np.prod(l.shape)) if l.shape else 1
               for l in jax.tree.leaves(w0))


def _run_stage_program(sim, plan, store, w0, xs, ys, g_rounds, kind,
                       slice_dtype):
    """The whole-stage superfusion: ONE jitted dispatch runs all G rounds of
    all S shards and (for the coded store) the Lagrange encode.

    ``xs``/``ys`` are the stage's data stacked to (S, M, n, ...).  The
    ``xla.stage_program`` span is the host's dispatch of the program and
    closes at enqueue; the program's own execution is ``jit_stage_program``
    in a device trace.  ``stage.collect`` covers the host's reading of the
    results: the per-shard slices, the one norms transfer and its loop.  A
    model with held experts also leaves the stage's tokens routed to each
    held expert, summed over rounds and clients, in ``record.expert_load``
    (MoE layers, held), and its mean and max over experts in the gauges
    ``moe.expert_tokens{stat=mean|max}`` while a recording tracer is on;
    ``moe.expert_tokens{stat=fill}`` is the stage's held (token, expert)
    pairs over the rows of the largest pair buffers of its MoE layers.
    """
    from repro.fl.simulator import StackedRoundGlobals, StageRecord

    fl = sim.fl
    shards = sorted(plan.shard_clients)
    # in-program encode only when the store can register pre-encoded slices
    encode = kind == "flat" and hasattr(store, "put_stage_encoded")
    use_kernel = bool(getattr(store, "use_kernel", False))
    prog = sim._get_stage_program(fl.local_epochs, kind, g_rounds,
                                  encode=encode, out_dtype=slice_dtype,
                                  use_kernel=use_kernel)
    row_spec = coding.tree_to_flat(w0)[1] if kind == "flat" else None
    tr = get_tracer()
    if encode:
        enc = jnp.asarray(store.scheme.encode_matrix(), jnp.float32)
        args = (w0, xs, ys, enc)
    else:
        args = (w0, xs, ys)
    with tr.span("xla.stage_program", stage=plan.stage, shards=len(shards),
                 rounds=g_rounds, encode=encode):
        final, round_in, hist, norms_dev, *load = prog(*args)
    if encode:
        store.put_stage_encoded(hist, row_spec,
                                row_len=_flat_row_len(w0))
    else:
        for g in range(g_rounds):
            if kind == "flat":
                payload = RoundPayload.from_flat(
                    g, plan.shard_clients,
                    {s: hist[g, i] for i, s in enumerate(shards)}, row_spec)
            else:
                payload = RoundPayload.from_stacked(
                    g, plan.shard_clients,
                    {s: jax.tree.map(lambda a, g=g, i=i: a[g, i], hist)
                     for i, s in enumerate(shards)})
            store.put_round(payload)
    store.flush()
    with tr.span("stage.collect", stage=plan.stage):
        shard_models = {s: jax.tree.map(lambda a, i=i: a[i], final)
                        for i, s in enumerate(shards)}
        round_globals = {s: StackedRoundGlobals(round_in, final, i)
                         for i, s in enumerate(shards)}
        # ONE host sync for every stored-update norm of the stage
        arr = np.asarray(jax.device_get(norms_dev))        # (G, S, M)
        norms = {}
        for i, s in enumerate(shards):
            for g in range(g_rounds):
                for j, c in enumerate(plan.shard_clients[s]):
                    norms[(s, g, c)] = float(arr[g, i, j])
        expert_load = load[0].sum(axis=(0, 1, 2)) if load else None
        if expert_load is not None and tr.enabled:
            per_expert = np.asarray(jax.device_get(expert_load))
            tr.metrics.gauge("moe.expert_tokens", stat="mean").set(
                float(per_expert.mean()))
            tr.metrics.gauge("moe.expert_tokens", stat="max").set(
                float(per_expert.max()))
            rows = sim.stage_pair_rows(xs.shape, fl.local_epochs, g_rounds)
            tr.metrics.gauge("moe.expert_tokens", stat="fill").set(
                float(per_expert.sum()) / (rows * per_expert.shape[0]))
    return StageRecord(plan, shard_models, round_globals, store,
                       history_norms=norms, expert_load=expert_load)


def _run_fused(sim, plan, store, w0, data, g_rounds, kind):
    """Fused per-shard engine: one jitted ``shard_round`` per (shard, round),
    everything stacked/device-resident (see ``repro.fl.simulator``)."""
    from repro.fl.simulator import StageRecord

    fl = sim.fl
    step = sim._shard_round[(fl.local_epochs, kind)]
    row_spec = coding.tree_to_flat(w0)[1] if kind == "flat" else None

    # round-major loop: all shards advance one round, then the round's
    # parameters are stored together (the coded store encodes ACROSS the
    # S shards — eq. 5/6 mixes one round's shard vectors).
    shards = sorted(plan.shard_clients)
    ws = {s: w0 for s in shards}
    round_globals = {s: [] for s in shards}
    norms_dev = {s: [] for s in shards}
    for g in range(g_rounds):
        payload = {}
        for s in shards:
            round_globals[s].append(ws[s])
            xs, ys = data[s]
            ws[s], payload[s], nrm = step(ws[s], xs, ys)
            norms_dev[s].append(nrm)
        if kind == "flat":
            store.put_round(RoundPayload.from_flat(
                g, plan.shard_clients, payload, row_spec))
        else:
            store.put_round(RoundPayload.from_stacked(
                g, plan.shard_clients, payload))
    store.flush()
    for s in shards:
        round_globals[s].append(ws[s])
    # ONE host sync for every stored-update norm of the stage —
    # the legacy path pulled S*G*M scalars with float(...)
    norms_host = jax.device_get({s: jnp.stack(norms_dev[s]) for s in shards})
    norms = {}
    for s in shards:
        arr = np.asarray(norms_host[s])            # (G, M)
        for g in range(g_rounds):
            for i, c in enumerate(plan.shard_clients[s]):
                norms[(s, g, c)] = float(arr[g, i])
    return StageRecord(plan, dict(ws), round_globals, store,
                       history_norms=norms)


def _train_stage_legacy(sim, store_kind: str = "coded",
                        rounds: Optional[int] = None):
    """Seed per-client round loop (unstack + per-scalar norm pulls +
    per-round tree flatten/encode) — kept for A/B comparison."""
    from repro.fl.simulator import StageRecord

    fl = sim.fl
    g_rounds = rounds or fl.global_rounds
    plan = sim.mgr.new_stage()
    rng = jax.random.key(sim.seed + plan.stage)
    w0 = sim.init_params(rng)
    store = sim._make_store(store_kind, plan)
    ws = {s: w0 for s in plan.shard_clients}
    data = {s: sim._stack_client_data(cs)
            for s, cs in plan.shard_clients.items()}
    round_globals = {s: [] for s in plan.shard_clients}
    norms = {}
    for g in range(g_rounds):
        all_params = {}
        for s, clients in plan.shard_clients.items():
            round_globals[s].append(ws[s])
            xs, ys = data[s]
            locals_ = sim._local_train[fl.local_epochs](ws[s], xs, ys)
            per_client = [jax.tree.map(lambda a, i=i: a[i], locals_)
                          for i in range(len(clients))]
            all_params.update(dict(zip(clients, per_client)))
            for i, c in enumerate(clients):
                d = unlearning.tree_sub(per_client[i], ws[s])
                norms[(s, g, c)] = float(unlearning.tree_norm(d))
            ws[s] = unlearning.tree_mean(per_client)
        store.put_round(RoundPayload.from_clients(g, plan.shard_clients,
                                                  all_params))
    for s in plan.shard_clients:
        round_globals[s].append(ws[s])
    return StageRecord(plan, dict(ws), round_globals, store,
                       history_norms=norms)
