"""Intermediate-parameter stores — the storage substrate the paper optimizes.

``FullStore``      — FedEraser: central server keeps every participating
                     client's parameters for every round.
``UncodedShardStore`` — isolated sharding: each shard's server keeps only its
                     own clients' parameters (still uncoded).
``CodedStore``     — coded sharding: per round, the S shard-stacked parameter
                     vectors are Lagrange-encoded into C slices that live on
                     clients; the servers keep only the coding keys. Retrieval
                     reconstructs with any >=S intact slices and tolerates up
                     to (C-S)/2 corrupted ones.

Store API
---------
Every store implements the ``ParameterStore`` protocol with ONE write entry
point, ``put_round(RoundPayload)``.  A ``RoundPayload`` carries one round's
parameters in whichever of three forms the producer has on hand — per-client
trees, per-shard stacked ``(M, ...)`` trees, or per-shard pre-flattened
``(M, P)`` matrices — and each store consumes the richest form it supports
(``wants`` advertises the preferred one so the round step can compute it
in-jit).  ``CodedStore`` additionally accepts a whole stage of slices
already Lagrange-encoded *inside* the stage program's XLA program
(``put_stage_encoded`` — zero store-side encode dispatches).  Stores register themselves in the ``STORES`` registry under the
name used by ``FLSimulator``/``ScenarioConfig`` (``full`` / ``uncoded`` /
``coded``); third-party stores are one ``@register_store`` away.

Every store reports byte-level accounting (``StoreStats``) so the Fig. 5
benchmark can compare storage overhead and (modelled) communication time.
"""
from __future__ import annotations

import dataclasses
import threading
from dataclasses import dataclass
from typing import (Callable, Dict, List, Optional, Protocol, Sequence, Tuple,
                    runtime_checkable)

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import coding
from repro.telemetry import get_tracer


def tree_bytes(tree) -> int:
    return sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(tree))


@dataclass
class _StackedRow:
    """Lazy reference to row ``idx`` of a stacked (M, ...) parameter pytree —
    lets the uncoded stores accept device-resident stacked batches without a
    per-client unstack in the training hot loop; the row is materialized only
    if actually retrieved (unlearning preparation)."""
    stacked: object
    idx: int

    def materialize(self):
        return jax.tree.map(lambda a, i=self.idx: a[i], self.stacked)

    def stacked_rows(self) -> int:
        return jax.tree.leaves(self.stacked)[0].shape[0]

    def nbytes(self) -> int:
        """This row's share of the stacked batch's bytes."""
        return tree_bytes(self.stacked) // max(self.stacked_rows(), 1)


@dataclass
class StoreStats:
    server_bytes: int = 0
    client_bytes: int = 0
    encode_flops: int = 0
    decode_flops: int = 0
    comm_bytes_store: int = 0     # bytes moved client->server (or client<->client)
    comm_bytes_retrieve: int = 0
    # quorum-read recovery accounting (CodedStore fault path)
    reads: int = 0                # shard reads served
    recovered_reads: int = 0      # reads that had to decode around a fault
    erased_slices: int = 0        # unreachable slices tolerated across reads
    corrupted_slices: int = 0     # corrupted slices localized + excluded
    failed_reads: int = 0         # reads aborted: faults exceeded the budget
    # tiered-store accounting (repro.tiering.TieredStore); keyed by tier name.
    # tier_bytes is residency (bytes currently held in that tier's medium:
    # device / host RAM / disk); the rest are monotone counters.
    tier_bytes: Dict[str, int] = dataclasses.field(default_factory=dict)
    tier_hits: Dict[str, int] = dataclasses.field(default_factory=dict)
    tier_misses: Dict[str, int] = dataclasses.field(default_factory=dict)
    tier_evictions: Dict[str, int] = dataclasses.field(default_factory=dict)
    tier_promotions: Dict[str, int] = dataclasses.field(default_factory=dict)

    def merge(self, other: "StoreStats") -> "StoreStats":
        """Field-wise accumulate ``other`` into self (returns self) — the one
        aggregation point for session/benchmark reporting.  Dict-valued
        fields (the per-tier counters) accumulate key-wise."""
        for f in dataclasses.fields(self):
            mine, theirs = getattr(self, f.name), getattr(other, f.name)
            if isinstance(mine, dict):
                for k, v in theirs.items():
                    mine[k] = mine.get(k, 0) + v
            else:
                setattr(self, f.name, mine + theirs)
        return self

    def __iadd__(self, other: "StoreStats") -> "StoreStats":
        return self.merge(other)

    def __add__(self, other: "StoreStats") -> "StoreStats":
        return self.snapshot().merge(other)

    def snapshot(self) -> "StoreStats":
        out = dataclasses.replace(self)
        for f in dataclasses.fields(out):     # don't alias the dict fields
            v = getattr(out, f.name)
            if isinstance(v, dict):
                setattr(out, f.name, dict(v))
        return out

    def to_dict(self) -> Dict[str, int]:
        return dataclasses.asdict(self)


# ---------------------------------------------------------------------------
# Round payload + store protocol
# ---------------------------------------------------------------------------

@dataclass
class RoundPayload:
    """One FedAvg round's parameters, in producer-native form.

    Exactly one of ``client_params`` / ``stacked`` / ``flat`` is set:

    * ``client_params`` — {client_id: pytree} (the seed per-client path).
    * ``stacked``       — {shard: (M_s, ...) pytree}, rows in
                          ``shard_clients[shard]`` order (per-shard
                          round, uncoded stores: no per-client unstack).
    * ``flat``          — {shard: (M_s, P) matrix} + ``row_spec`` (per-shard
                          round, coded store: flattened in-jit by
                          ``coding.tree_to_flat_stacked``).

    ``shard_clients`` always carries the round's shard membership so every
    store can serve ``get_shard`` regardless of its internal layout.
    """
    rnd: int
    shard_clients: Dict[int, List[int]]
    client_params: Optional[Dict[int, object]] = None
    stacked: Optional[Dict[int, object]] = None
    flat: Optional[Dict[int, jnp.ndarray]] = None
    row_spec: object = None

    def __post_init__(self):
        forms = [x is not None for x in
                 (self.client_params, self.stacked, self.flat)]
        if sum(forms) != 1:
            raise ValueError("RoundPayload needs exactly one of "
                             "client_params / stacked / flat")
        if self.flat is not None and self.row_spec is None:
            raise ValueError("flat payload requires row_spec")

    # ------------------------------------------------------- constructors
    @classmethod
    def from_clients(cls, rnd: int, shard_clients: Dict[int, List[int]],
                     client_params: Dict[int, object]) -> "RoundPayload":
        return cls(rnd, {s: list(cs) for s, cs in shard_clients.items()},
                   client_params=client_params)

    @classmethod
    def from_stacked(cls, rnd: int, shard_clients: Dict[int, List[int]],
                     stacked: Dict[int, object]) -> "RoundPayload":
        return cls(rnd, {s: list(cs) for s, cs in shard_clients.items()},
                   stacked=stacked)

    @classmethod
    def from_flat(cls, rnd: int, shard_clients: Dict[int, List[int]],
                  flat: Dict[int, jnp.ndarray], row_spec) -> "RoundPayload":
        return cls(rnd, {s: list(cs) for s, cs in shard_clients.items()},
                   flat=flat, row_spec=row_spec)

    # ------------------------------------------------------------- views
    def iter_client_trees(self):
        """Yield (shard, client, lazy-or-real tree) for every client."""
        if self.client_params is not None:
            for s, cs in self.shard_clients.items():
                for c in cs:
                    if c in self.client_params:
                        yield s, c, self.client_params[c]
        elif self.stacked is not None:
            for s, cs in self.shard_clients.items():
                for i, c in enumerate(cs):
                    yield s, c, _StackedRow(self.stacked[s], i)
        else:
            raise ValueError("flat payload carries no per-client trees; "
                             "use a 'stacked' or 'client_params' payload")


@runtime_checkable
class ParameterStore(Protocol):
    """The single store interface stage training and the session target."""

    stats: StoreStats
    wants: str        # preferred payload form: "flat" | "stacked" | "tree"

    def put_round(self, payload: RoundPayload) -> None: ...

    def flush(self) -> None: ...

    def get(self, rnd: int, client: int): ...

    def get_shard(self, rnd: int, shard: int,
                  available: Optional[Sequence[int]] = None,
                  corrupt: Optional[np.ndarray] = None) -> Dict[int, object]: ...

    def clients_at(self, rnd: int) -> List[int]: ...


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

STORES: Dict[str, Callable[..., "ParameterStore"]] = {}


def register_store(name: str):
    """Register a store factory under ``name``.

    Factories are called as ``factory(shard_clients, **options)`` where
    ``options`` carries ``num_shards``, ``num_clients``, ``group_rounds``,
    ``slice_dtype``, ``use_kernel`` (factories ignore what they don't need).
    """
    def deco(fn):
        STORES[name] = fn
        return fn
    return deco


def make_store(kind: str, shard_clients: Dict[int, List[int]],
               **options) -> "ParameterStore":
    try:
        factory = STORES[kind]
    except KeyError:
        raise KeyError(f"unknown store {kind!r}; registered: "
                       f"{sorted(STORES)}") from None
    return factory(shard_clients, **options)


# ---------------------------------------------------------------------------
# Stores
# ---------------------------------------------------------------------------

class FullStore:
    """{(round, client_id): params} on the central server."""

    wants = "stacked"

    def __init__(self):
        self._data: Dict[Tuple[int, int], object] = {}
        self._shards: Dict[int, Dict[int, List[int]]] = {}  # rnd -> layout
        self.stats = StoreStats()
        # ``get`` materializes lazy stacked rows in place: serialize it so
        # interleaved serves (service worker threads) read safely
        self._lock = threading.RLock()

    def put_round(self, payload: RoundPayload) -> None:
        self._shards[payload.rnd] = payload.shard_clients
        for _s, c, p in payload.iter_client_trees():
            self._data[(payload.rnd, c)] = p
            b = p.nbytes() if isinstance(p, _StackedRow) else tree_bytes(p)
            self.stats.server_bytes += b
            self.stats.comm_bytes_store += b

    def flush(self) -> None:
        pass

    def get(self, rnd: int, client: int):
        with self._lock:
            p = self._data[(rnd, client)]
            if isinstance(p, _StackedRow):
                p = p.materialize()
                self._data[(rnd, client)] = p
            self.stats.comm_bytes_retrieve += tree_bytes(p)
        return p

    def get_shard(self, rnd: int, shard: int,
                  available: Optional[Sequence[int]] = None,
                  corrupt: Optional[np.ndarray] = None) -> Dict[int, object]:
        """Uncoded stores hold plaintext params: ``available``/``corrupt``
        model slice loss and are inapplicable here (ignored)."""
        return {c: self.get(rnd, c) for c in self._shards[rnd][shard]}

    def clients_at(self, rnd: int) -> List[int]:
        return sorted(c for (r, c) in self._data if r == rnd)


class UncodedShardStore(FullStore):
    """Same layout, but bytes are attributed per shard server (the shard's
    server only holds its own clients — server_bytes tracks the max shard)."""

    def __init__(self, shard_of: Dict[int, int]):
        super().__init__()
        self.shard_of = shard_of
        self._per_shard: Dict[int, int] = {}

    def put_round(self, payload: RoundPayload) -> None:
        self._shards[payload.rnd] = payload.shard_clients
        for s, c, p in payload.iter_client_trees():
            self._data[(payload.rnd, c)] = p
            b = p.nbytes() if isinstance(p, _StackedRow) else tree_bytes(p)
            self._per_shard[s] = self._per_shard.get(s, 0) + b
            self.stats.comm_bytes_store += b
        self.stats.server_bytes = max(self._per_shard.values(), default=0)


class CodedStore:
    """Lagrange-coded distributed store (paper Sec 3.3).

    Per (round): the S shard parameter vectors (concat of their clients'
    params) are encoded to C slices held by clients. The server side keeps
    only the CodingScheme (keys). Decode returns {client_id: params} for one
    shard.
    """

    wants = "flat"

    def __init__(self, scheme: coding.CodingScheme,
                 shard_clients: Dict[int, List[int]], use_kernel: bool = False,
                 slice_dtype=None, group_rounds: int = 1):
        self.scheme = scheme
        self.shard_clients = {s: list(cs) for s, cs in shard_clients.items()}
        self.use_kernel = use_kernel
        self.slice_dtype = slice_dtype        # e.g. bf16 coded slices
        self.group_rounds = max(int(group_rounds), 1)
        self._slices: Dict[int, jnp.ndarray] = {}    # round -> (C, P)
        self._specs: Dict[int, tuple] = {}
        self._layouts: Dict[int, list] = {}          # round -> client order per shard
        self._pending: List[Tuple[int, jnp.ndarray]] = []   # deferred rounds
        self._row_layout = None               # cached flat-path geometry
        self.faults = None                    # optional attached FaultPlan
        self.stats = StoreStats()
        self.stats.server_bytes = 16 * scheme.num_clients  # the keys
        # concurrent-read safety for interleaved serves: ``get_shard`` may
        # trigger ``flush`` (mutating _slices/_pending) and always mutates
        # stats, so the online service's worker threads reading different
        # shards of the same store must serialize through this lock.
        # Re-entrant because get_shard -> flush nests.
        self._lock = threading.RLock()

    def put_round(self, payload: RoundPayload) -> None:
        if payload.flat is not None:
            self._put_flat(payload.rnd, payload.flat, payload.row_spec)
        elif payload.client_params is not None:
            self._put_trees(payload.rnd, payload.client_params)
        else:
            # stacked trees: flatten host-side (slow path, kept for
            # completeness — the per-shard round hands the coded store ``flat``)
            flat = {}
            row_spec = None
            for s, cs in sorted(payload.shard_clients.items()):
                f, spec = coding.tree_to_flat_stacked(payload.stacked[s])
                flat[s] = f
                row_spec = spec
            self._put_flat(payload.rnd, flat, row_spec)

    def _put_trees(self, rnd: int, client_params: Dict[int, object]):
        """Encode this round's per-shard parameter sets into client slices."""
        shard_trees = []
        layout = []
        for s in sorted(self.shard_clients):
            cs = [c for c in self.shard_clients[s] if c in client_params]
            layout.append((s, cs))
            shard_trees.append({c: client_params[c] for c in cs})
        slices, specs = coding.encode_pytrees(self.scheme, shard_trees,
                                              use_kernel=self.use_kernel)
        with self._lock:
            self._slices[rnd] = slices
            self._specs[rnd] = specs
            self._layouts[rnd] = layout
            self._account_stored(slices)

    def _put_flat(self, rnd: int, shard_flats: Dict[int, jnp.ndarray],
                  row_spec):
        """Fast path for the per-shard round: per-shard *stacked, already
        flat* ``(M_s, P)`` client-parameter matrices (from
        ``coding.tree_to_flat_stacked`` inside the jitted round step).

        The per-shard vector is the client-major ``reshape(-1)`` of the
        stacked matrix — bit-identical to the tree path's concat of per-client
        flats. Re-assembly specs and padding geometry are computed ONCE per
        stage (not re-flattened per client per round), and the Lagrange encode
        itself is deferred and batched ``group_rounds`` rounds at a time into
        a single (S, G*P) coded matmul (see ``flush``).
        """
        with self._lock:
            if self._row_layout is None:
                layout, specs, lens = [], [], []
                for s in sorted(self.shard_clients):
                    cs = list(self.shard_clients[s])
                    f = shard_flats[s]
                    assert f.shape[0] == len(cs), (s, f.shape, cs)
                    layout.append((s, cs))
                    specs.append(coding.StackedRowSpec(tuple(cs),
                                                       int(f.shape[1]),
                                                       row_spec))
                    lens.append(int(f.shape[0]) * int(f.shape[1]))
                self._row_layout = (layout, tuple(specs), max(lens))
            layout, specs, pmax = self._row_layout
            rows = [shard_flats[s].reshape(-1) for s, _ in layout]
            w = jnp.stack([r if r.shape[0] == pmax
                           else jnp.pad(r, (0, pmax - r.shape[0]))
                           for r in rows])
            self._layouts[rnd] = layout
            self._specs[rnd] = specs
            self._pending.append((rnd, w))
            if len(self._pending) >= self.group_rounds:
                self.flush()

    def put_stage_encoded(self, coded: jnp.ndarray, row_spec,
                          row_len: int) -> None:
        """Whole-stage write for the stage program: ``coded`` is the
        ``(G, C, Pmax)`` slice tensor already Lagrange-encoded *inside* the
        training program (``coding.encode_rounds`` fused after the round
        scan), so the store does no encode dispatch at all — it only registers
        per-round views and accounts bytes/FLOPs exactly like the per-shard
        ``_put_flat``+``flush`` path (same shapes, same dtype).

        ``row_spec``/``row_len`` carry the per-client re-assembly geometry
        (every shard must have the same client count — the stage program's
        stackability precondition, which ``train_stage`` checks before
        selecting this path).
        """
        layout, specs = [], []
        for s in sorted(self.shard_clients):
            cs = list(self.shard_clients[s])
            layout.append((s, cs))
            specs.append(coding.StackedRowSpec(tuple(cs), row_len, row_spec))
        specs = tuple(specs)
        with self._lock, get_tracer().span("store.put_stage",
                                           rounds=int(coded.shape[0])):
            for g in range(int(coded.shape[0])):
                self._slices[g] = coded[g]
                self._layouts[g] = layout
                self._specs[g] = specs
                self._account_stored(coded[g])

    def flush(self):
        """Encode all deferred rounds in one batched coded matmul."""
        with self._lock:
            if not self._pending:
                return
            rounds = [r for r, _ in self._pending]
            mats = [w for _, w in self._pending]
            self._pending = []
            with get_tracer().span("store.encode", rounds=len(rounds),
                                   kernel=self.use_kernel):
                coded = coding.encode_batched(self.scheme, mats,
                                              use_kernel=self.use_kernel,
                                              out_dtype=self.slice_dtype)
            for rnd, slices in zip(rounds, coded):
                self._slices[rnd] = slices
                self._account_stored(slices)

    def _account_stored(self, slices: jnp.ndarray):
        p = slices.shape[1]
        self.stats.client_bytes += int(slices.size * slices.dtype.itemsize)
        # distribution traffic: every client receives its slice
        self.stats.comm_bytes_store += int(slices.size * slices.dtype.itemsize)
        s_dim = self.scheme.num_shards
        self.stats.encode_flops += 2 * self.scheme.num_clients * s_dim * p

    def attach_faults(self, plan) -> None:
        """Attach a ``repro.faults.FaultPlan``: its slice injectors fire on
        every subsequent ``get_shard`` (keyed per round — every reader of a
        round observes the same fault) and reads route through the
        quorum-read recovery path."""
        self.faults = plan

    def _injected_faults(self, rnd: int, slices: jnp.ndarray):
        """Ask the attached ``FaultPlan`` (if any) for this round's slice
        faults: ``(lost_ids, {row: noise})``.  Subclasses widen this — the
        tiered store additionally exposes offloaded (cold-tier) slices to
        ``cold_corrupt`` injectors."""
        if self.faults is None:
            return [], {}
        host = np.asarray(jax.device_get(slices)).astype(np.float32)
        return self.faults.slice_faults(
            rnd, self.scheme, int(slices.shape[1]),
            scale_ref=float(np.abs(host).mean()))

    def _decode_tol(self, rnd: int, slices: jnp.ndarray) -> float:
        """Corruption-detection tolerance for ``decode_robust``.  bf16 slices
        round-trip with ~4e-3 relative residual, so the tolerance scales with
        the storage dtype; the tiered store widens it further for rounds that
        passed through the lossy int8 tier."""
        return 1e-3 if slices.dtype.itemsize >= 4 else 3e-2

    def get(self, rnd: int, client: int):
        """Single-client retrieval decodes the client's shard and indexes it
        (the coded layout has no per-client granularity)."""
        for s, cs in self.shard_clients.items():
            if client in cs:
                return self.get_shard(rnd, s)[client]
        raise KeyError(client)

    def get_shard(self, rnd: int, shard: int,
                  available: Optional[Sequence[int]] = None,
                  corrupt: Optional[np.ndarray] = None) -> Dict[int, object]:
        """Reconstruct shard ``shard``'s stored params at round ``rnd``.

        ``available``: client ids whose slices are reachable (default: all).
        ``corrupt``: optional (C,P)-shaped noise to model erroneous slices —
        triggers the error-correcting decode path.

        With an attached ``FaultPlan`` (``attach_faults``) or explicit
        ``available``/``corrupt``, the read runs in quorum mode: missing and
        corrupt slices are detected and decoded around
        (``coding.decode_robust``) instead of raising, with per-read recovery
        accounting in ``StoreStats``; faults beyond eq. 11's budget raise
        ``coding.CodingBudgetExceeded``.
        """
        with get_tracer().span("store.read", round=rnd, shard=shard) as sp:
            with self._lock:
                if rnd not in self._slices:
                    self.flush()              # materialize deferred encodes
                slices = self._slices[rnd]
                layout = self._layouts[rnd]
                specs = self._specs[rnd]
                self.stats.reads += 1
                self.stats.comm_bytes_retrieve += int(
                    self.scheme.num_shards * slices.shape[1]
                    * slices.dtype.itemsize)
                self.stats.decode_flops += (2 * self.scheme.num_shards ** 2
                                            * slices.shape[1])
            # decode outside the lock: pure function of the slice tensor, so
            # interleaved serves decode different shards concurrently
            c = self.scheme.num_clients
            inj_lost, inj_noise = self._injected_faults(rnd, slices)
            if corrupt is None and available is None \
                    and not inj_lost and not inj_noise:
                ids = list(range(c))
                w = coding.decode_erasure(self.scheme,
                                          slices[jnp.asarray(ids)],
                                          ids, use_kernel=self.use_kernel)
            else:
                if inj_noise:
                    rows = sorted(inj_noise)
                    noise = np.stack([inj_noise[r] for r in rows])
                    slices = slices.at[jnp.asarray(rows)].add(
                        jnp.asarray(noise, slices.dtype))
                if corrupt is not None:
                    slices = slices + jnp.asarray(corrupt, slices.dtype)
                avail = (set(available) if available is not None
                         else set(range(c)))
                avail -= set(inj_lost)
                tol = self._decode_tol(rnd, slices)
                try:
                    w, lost, bad = coding.decode_robust(
                        self.scheme, slices, available=sorted(avail),
                        use_kernel=self.use_kernel, tol=tol)
                except coding.CodingBudgetExceeded:
                    with self._lock:
                        self.stats.failed_reads += 1
                    sp.annotate(failed=True)
                    raise
                if lost or bad:
                    with self._lock:
                        self.stats.recovered_reads += 1
                        self.stats.erased_slices += len(lost)
                        self.stats.corrupted_slices += len(bad)
                    sp.annotate(recovered=True, erased=len(lost),
                                corrupted=len(bad))
                    if self.faults is not None:
                        from repro.faults.events import RecoveryEvent
                        self.faults.ledger.record(RecoveryEvent(
                            "quorum_read",
                            site=("round", rnd, "shard", shard),
                            detail=(tuple(lost), tuple(bad))))
            for idx, (s, cs) in enumerate(layout):
                if s == shard:
                    spec = specs[idx]
                    if isinstance(spec, coding.StackedRowSpec):
                        return coding.flat_to_client_trees(w[idx], spec)
                    return coding.flat_to_tree(w[idx], spec)
            raise KeyError(f"shard {shard} not stored at round {rnd}")

    def clients_at(self, rnd: int) -> List[int]:
        return sorted(c for _, cs in self._layouts[rnd] for c in cs)


# ---------------------------------------------------------------------------
# Registered factories (the names FLSimulator / ScenarioConfig use)
# ---------------------------------------------------------------------------

@register_store("full")
def _make_full(shard_clients, **_options) -> FullStore:
    return FullStore()


@register_store("uncoded")
def _make_uncoded(shard_clients, **_options) -> UncodedShardStore:
    return UncodedShardStore({c: s for s, cs in shard_clients.items()
                              for c in cs})


@register_store("coded")
def _make_coded(shard_clients, *, num_shards: int, num_clients: int,
                group_rounds: int = 1, slice_dtype=None,
                use_kernel: bool = False, **_options) -> CodedStore:
    scheme = coding.CodingScheme(num_shards=num_shards,
                                 num_clients=num_clients)
    return CodedStore(scheme, shard_clients, group_rounds=group_rounds,
                      slice_dtype=slice_dtype, use_kernel=use_kernel)
