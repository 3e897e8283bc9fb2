"""Capture/restore a ``FederatedSession``'s durable state.

``capture_session`` turns the session's live state into a plain object
graph the snapshot format can serialize exactly: the shard manager's RNG
state and stage plans, every completed ``StageRecord`` (plan, shard
models, materialized round globals, history norms, and the parameter
store's contents — coded slices + specs + layouts, or per-client trees),
the ``SessionReport`` (including per-request ``UnlearnResult`` models),
and the set of served request ids.

``restore_session`` rebuilds that state onto a *freshly constructed*
session of the same configuration (same simulator seed and store kind; a
snapshot from before training had one path also carries an ``engine`` key,
which is ignored).  Because stage training is deterministic given the
restored RNG state, a resumed ``run`` re-trains post-snapshot stages
bit-identically — the durability acceptance test's whole premise.
"""
from __future__ import annotations

import os

import jax.numpy as jnp
import numpy as np

from repro.stores.store import (CodedStore, FullStore, StoreStats,
                                UncodedShardStore, _StackedRow)
from repro.tiering.budget import MemoryBudget
from repro.tiering.store import TieredStore
from repro.tiering.tiers import TierEntry, cold_file_crc

STATE_VERSION = 1


def _materialize(tree):
    return tree.materialize() if isinstance(tree, _StackedRow) else tree


# ---------------------------------------------------------------------------
# Stores
# ---------------------------------------------------------------------------

def _capture_store(store) -> dict:
    if isinstance(store, TieredStore):      # before CodedStore: a subclass
        store.flush()
        entries = {}
        for rnd, e in store._slices.entries().items():
            ent = {"tier": e.tier, "hits": int(e.hits),
                   "last_access": int(e.last_access), "stage": int(e.stage),
                   "lossy": bool(e.lossy),
                   "shape": (int(e.shape[0]), int(e.shape[1])),
                   "dtype": np.dtype(e.dtype).name,
                   "scales": e.scales,
                   # cold pointer: basename + crc — the file itself is NOT
                   # copied into the snapshot; resume revalidates it in place
                   "path": (os.path.basename(e.path) if e.path else None),
                   "file_crc": e.file_crc}
            if e.tier == "hot":
                ent["device"] = e.device
            if e.q is not None and e.path is None:
                ent["q"] = e.q              # RAM-only lossy payload
            entries[rnd] = ent
        return {"kind": "tiered",
                "scheme": store.scheme,
                "shard_clients": store.shard_clients,
                "use_kernel": bool(store.use_kernel),
                "slice_dtype": (np.dtype(store.slice_dtype).name
                                if store.slice_dtype is not None else None),
                "group_rounds": int(store.group_rounds),
                "budget": store.budget.to_dict(),
                "eviction": store.eviction,
                "promote_on_read": bool(store.promote_on_read),
                "offload_dir": store.offload_dir,
                "cold_dir": store._cold_dir,
                "seq": int(store._slices._seq),
                "births": int(store._slices._births),
                "entries": entries,
                "specs": dict(store._specs),
                "layouts": dict(store._layouts),
                "stats": store.stats}
    if isinstance(store, CodedStore):
        store.flush()                       # materialize deferred encodes
        return {"kind": "coded",
                "scheme": store.scheme,
                "shard_clients": store.shard_clients,
                "use_kernel": bool(store.use_kernel),
                "slice_dtype": (np.dtype(store.slice_dtype).name
                                if store.slice_dtype is not None else None),
                "group_rounds": int(store.group_rounds),
                "slices": dict(store._slices),
                "specs": dict(store._specs),
                "layouts": dict(store._layouts),
                "stats": store.stats}
    if isinstance(store, UncodedShardStore):
        return {"kind": "uncoded",
                "data": {k: _materialize(v) for k, v in store._data.items()},
                "shards": store._shards,
                "shard_of": store.shard_of,
                "per_shard": store._per_shard,
                "stats": store.stats}
    if isinstance(store, FullStore):
        return {"kind": "full",
                "data": {k: _materialize(v) for k, v in store._data.items()},
                "shards": store._shards,
                "stats": store.stats}
    raise TypeError(f"cannot capture store of type {type(store).__name__}; "
                    f"durable sessions support full/uncoded/coded/tiered")


def _restore_store(st: dict):
    kind = st["kind"]
    if kind == "tiered":
        dtype = np.dtype(st["slice_dtype"]) if st["slice_dtype"] else None
        store = TieredStore(st["scheme"], st["shard_clients"],
                            use_kernel=st["use_kernel"], slice_dtype=dtype,
                            group_rounds=st["group_rounds"],
                            budget=MemoryBudget(**st["budget"]),
                            eviction=st["eviction"],
                            offload_dir=st["offload_dir"],
                            promote_on_read=st["promote_on_read"])
        store._cold_dir = st["cold_dir"]
        table = store._slices
        for rnd, ent in st["entries"].items():
            path = None
            if ent["path"] is not None:
                path = os.path.join(st["cold_dir"], ent["path"])
                if not os.path.exists(path):
                    raise FileNotFoundError(
                        f"cold-tier file missing on resume: {path}")
                if ent["file_crc"] is not None \
                        and cold_file_crc(path) != ent["file_crc"]:
                    raise IOError(f"cold-tier file corrupted: {path} "
                                  f"(crc mismatch)")
            scales = ent["scales"]
            table._entries[rnd] = TierEntry(
                key=rnd, shape=tuple(ent["shape"]),
                dtype=jnp.dtype(ent["dtype"]), tier=ent["tier"],
                device=ent.get("device"),
                q=(np.asarray(ent["q"], np.int8).reshape(ent["shape"])
                   if "q" in ent else None),
                scales=(np.asarray(scales, np.float32)
                        if scales is not None else None),
                path=path, file_crc=ent["file_crc"], lossy=ent["lossy"],
                hits=ent["hits"], last_access=ent["last_access"],
                stage=ent["stage"])
        table._seq = st["seq"]
        table._births = st["births"]
        store._specs = dict(st["specs"])
        store._layouts = dict(st["layouts"])
        store.stats = st["stats"]
        return store
    if kind == "coded":
        dtype = np.dtype(st["slice_dtype"]) if st["slice_dtype"] else None
        store = CodedStore(st["scheme"], st["shard_clients"],
                           use_kernel=st["use_kernel"], slice_dtype=dtype,
                           group_rounds=st["group_rounds"])
        store._slices = dict(st["slices"])
        store._specs = dict(st["specs"])
        store._layouts = dict(st["layouts"])
        store.stats = st["stats"]
        return store
    if kind == "uncoded":
        store = UncodedShardStore(st["shard_of"])
        store._per_shard = dict(st["per_shard"])
    elif kind == "full":
        store = FullStore()
    else:
        raise ValueError(f"unknown store kind {kind!r} in snapshot")
    store._data = dict(st["data"])
    store._shards = dict(st["shards"])
    store.stats = st["stats"]
    return store


# ---------------------------------------------------------------------------
# Records + report
# ---------------------------------------------------------------------------

def _capture_record(record) -> dict:
    return {"plan": record.plan,
            "shard_models": dict(record.shard_models),
            # materialize lazy StackedRoundGlobals views into plain lists
            "round_globals": {s: list(v)
                              for s, v in record.round_globals.items()},
            "history_norms": dict(record.history_norms),
            "store": _capture_store(record.store)}


def _restore_record(st: dict):
    from repro.fl.simulator import StageRecord
    return StageRecord(plan=st["plan"], shard_models=st["shard_models"],
                       round_globals=st["round_globals"],
                       store=_restore_store(st["store"]),
                       history_norms=st["history_norms"])


def _capture_result(res, live_stats) -> dict:
    # the serving paths hand UnlearnResult the record store's LIVE StoreStats
    # object, so later reads mutate already-recorded results; a restored
    # report must re-alias (not copy) to stay bit-identical with the
    # uninterrupted run
    return {"framework": res.framework, "models": dict(res.models),
            "wall_time": float(res.wall_time),
            "cost_units": float(res.cost_units),
            "store_stats": res.store_stats,
            "stats_live": res.store_stats is live_stats,
            "impacted_shards": [int(s) for s in res.impacted_shards],
            "request_id": getattr(res, "request_id", "")}


def _restore_result(st: dict):
    from repro.fl.simulator import UnlearnResult
    return UnlearnResult(framework=st["framework"], models=st["models"],
                         wall_time=st["wall_time"],
                         cost_units=st["cost_units"],
                         store_stats=st["store_stats"],
                         impacted_shards=st["impacted_shards"],
                         request_id=st.get("request_id", ""))


def _capture_report(report, records) -> dict:
    return {"store_kind": report.store_kind,
            "stages": [{"stage": s.stage, "plan_stage": s.plan_stage,
                        "train_wall": float(s.train_wall),
                        "num_shards": int(s.num_shards),
                        "clients": [int(c) for c in s.clients],
                        "store_stats": s.store_stats,
                        "unlearn": [_capture_result(
                            u, records[s.stage].store.stats)
                            for u in s.unlearn]}
                       for s in report.stages]}


def _restore_report(st: dict, records):
    from repro.fl.experiment.session import SessionReport, StageReport
    report = SessionReport(store_kind=st["store_kind"])
    for s in st["stages"]:
        unlearn = []
        for u in s["unlearn"]:
            res = _restore_result(u)
            if u.get("stats_live"):
                res.store_stats = records[s["stage"]].store.stats
            unlearn.append(res)
        report.stages.append(StageReport(
            stage=s["stage"], plan_stage=s["plan_stage"],
            train_wall=s["train_wall"], num_shards=s["num_shards"],
            clients=s["clients"], store_stats=s["store_stats"],
            unlearn=unlearn))
    return report


# ---------------------------------------------------------------------------
# Session
# ---------------------------------------------------------------------------

def capture_session(session) -> dict:
    sim = session.sim
    return {
        "version": STATE_VERSION,
        "store_kind": session.store_kind,
        "seed": int(sim.seed),
        "num_stages": len(session.records),
        "rng_state": sim.mgr._rng.bit_generator.state,
        "mgr_stages": list(sim.mgr.stages),
        "records": [_capture_record(r) for r in session.records],
        "report": _capture_report(session.report, session.records),
        "served": sorted(session._served),
    }


def restore_session(session, state: dict) -> int:
    """Load ``state`` (from ``capture_session``) into ``session``; returns
    the number of completed stages restored.  The session must be freshly
    built with the same configuration the snapshot was taken under."""
    if state.get("version") != STATE_VERSION:
        raise ValueError(f"snapshot state version {state.get('version')!r} "
                         f"!= supported {STATE_VERSION}")
    if state["store_kind"] != session.store_kind:
        raise ValueError(
            f"snapshot was taken with store_kind={state['store_kind']!r} but "
            f"this session has store_kind={session.store_kind!r}; resume "
            f"needs an identically configured session")
    if state["seed"] != session.sim.seed:
        raise ValueError(f"snapshot seed {state['seed']} != simulator seed "
                         f"{session.sim.seed}; resumed training would "
                         f"diverge from the original run")
    session.sim.mgr._rng.bit_generator.state = state["rng_state"]
    session.sim.mgr.stages = list(state["mgr_stages"])
    session.records = [_restore_record(r) for r in state["records"]]
    session.report = _restore_report(state["report"], session.records)
    session._served = set(state["served"])
    return int(state["num_stages"])
