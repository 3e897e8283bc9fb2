"""Scenario runner: one config -> simulator -> multi-stage session -> report.

``ScenarioConfig`` captures everything the paper's experiments vary — and,
through three registries, everything they *didn't*: the task
(``TASKS``: classification / generation), the model family (``FAMILIES``:
cnn / transformer / mamba / rwkv6 / moe — the latter two training through
their Pallas kernel ops), the client partitioner (``PARTITIONERS``: iid /
primary-class / buckets / dirichlet / zipf), the store kind, stage count,
and the unlearning request schedule.  ``run_scenario`` executes it through
``FederatedSession``.  The benchmark suite (``benchmarks/common.py``) and
``examples/`` build on these helpers instead of hand-rolling
model/data/simulator setup.

Every registry key is validated in ``__post_init__`` with an actionable
error (unknown keys list the registered entries), so a typo'd name fails at
config construction instead of as a deep ``KeyError``.  The pre-registry
spellings — ``task="image" | "lm"`` and ``iid=True/False`` — keep working as
``DeprecationWarning`` shims that map onto the registries bit-identically.
"""
from __future__ import annotations

import os
import warnings
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple, Union

import numpy as np

from repro.stores.store import STORES
from repro.configs import FLConfig, OptimizerConfig
from repro.data.federated import get_partitioner
from repro.fl.experiment.frameworks import FRAMEWORKS
from repro.fl.experiment.session import (FederatedSession, RequestSchedule,
                                         SessionReport)
from repro.fl.families import get_model_family
from repro.fl.simulator import FLSimulator
from repro.fl.tasks import get_task

DTypeLike = Union[str, np.dtype, type]

_TASK_ALIASES = {"image": "classification", "lm": "generation"}


@dataclass
class ScenarioConfig:
    """One experiment scenario (defaults = the CPU-container scale)."""
    # task / model / data — registry keys (TASKS / FAMILIES / PARTITIONERS)
    task: str = "classification"
    model: str = ""                   # "" -> the task's default family
    partitioner: str = "iid"
    partitioner_kwargs: Dict[str, Any] = field(default_factory=dict)
    iid: Optional[bool] = None        # DEPRECATED -> partitioner=
    seed: int = 0
    samples_per_client: int = 80
    image_size: int = 14
    noise: float = 0.25
    seq_len: int = 48
    test_n: int = 400
    # federation
    num_clients: int = 20
    clients_per_round: int = 12
    num_shards: int = 4
    local_epochs: int = 4
    global_rounds: int = 6
    retrain_ratio: float = 2.0
    # optimizer (None -> per-family/per-task default)
    opt_name: str = "sgd"
    lr: Optional[float] = None
    local_batch: Optional[int] = None
    # orchestration
    store: str = "coded"
    # factory-specific store knobs passed through make_store verbatim (e.g.
    # store="tiered": hot_bytes / warm_bytes / eviction / offload_dir)
    store_options: Dict[str, Any] = field(default_factory=dict)
    slice_dtype: Optional[DTypeLike] = None
    num_stages: int = 1
    schedule: Optional[RequestSchedule] = None
    batch_requests: bool = False         # merge requests due after each stage
    strict_schedule: bool = False        # raise on never-served requests
    # durability (repro.durability): snapshot every N completed stages into
    # checkpoint_dir; 0 disables periodic snapshots (a dir alone implies 1)
    checkpoint_every: int = 0
    checkpoint_dir: Optional[str] = None

    # ------------------------------------------------------------ validation
    def __post_init__(self):
        self._apply_deprecated_spellings()
        task = get_task(self.task)           # raises listing TASKS
        self.task = task.name
        if not self.model:
            self.model = task.default_family
        family = get_model_family(self.model)  # raises listing FAMILIES
        self.model = family.name
        if family.task != task.kind:
            raise ValueError(
                f"model family {self.model!r} plays task {family.task!r}, "
                f"not {task.name!r}; pick a family whose task matches "
                f"(see repro.fl.families.FAMILIES)")
        # raises listing PARTITIONERS, or the accepted kwarg names on a
        # typo'd parameter (e.g. dirichlet alpha)
        get_partitioner(self.partitioner, **self.partitioner_kwargs)
        if self.store not in STORES:
            raise ValueError(f"unknown store {self.store!r}; registered: "
                             f"{sorted(STORES)}")
        if self.schedule is not None:
            for r in self.schedule.requests:
                if r.framework not in FRAMEWORKS:
                    raise ValueError(
                        f"scheduled request uses unknown unlearning "
                        f"framework {r.framework!r}; registered: "
                        f"{sorted(FRAMEWORKS)}")
        if self.clients_per_round > self.num_clients:
            raise ValueError(
                f"clients_per_round={self.clients_per_round} exceeds "
                f"num_clients={self.num_clients}")
        if self.num_shards < 1 or self.clients_per_round % self.num_shards:
            raise ValueError(
                f"num_shards={self.num_shards} must divide the "
                f"clients_per_round={self.clients_per_round} clients sampled "
                f"per stage (each shard gets clients_per_round/num_shards "
                f"clients)")
        if self.slice_dtype is not None:
            try:
                np.dtype(self.slice_dtype)
            except TypeError:
                raise ValueError(
                    f"slice_dtype {self.slice_dtype!r} is not a dtype; use "
                    f"e.g. 'bfloat16', 'float32', or np.float16") from None
        if self.checkpoint_every < 0:
            raise ValueError(
                f"checkpoint_every={self.checkpoint_every} must be >= 0 "
                f"(0 disables periodic snapshots)")
        if self.checkpoint_every and not self.checkpoint_dir:
            raise ValueError(
                f"checkpoint_every={self.checkpoint_every} needs a "
                f"checkpoint_dir to write snapshots to")
        if self.checkpoint_dir is not None:
            parent = os.path.dirname(
                os.path.abspath(self.checkpoint_dir)) or os.sep
            probe = self.checkpoint_dir if os.path.isdir(self.checkpoint_dir) \
                else parent
            if not os.path.isdir(probe) or not os.access(probe, os.W_OK):
                raise ValueError(
                    f"checkpoint_dir {self.checkpoint_dir!r} is not writable "
                    f"(nor creatable under {parent!r}); snapshots need a "
                    f"writable directory")

    def _apply_deprecated_spellings(self):
        if self.task in _TASK_ALIASES:
            new = _TASK_ALIASES[self.task]
            warnings.warn(
                f"ScenarioConfig(task={self.task!r}) is deprecated; use "
                f"task={new!r} (optionally with model=...)",
                DeprecationWarning, stacklevel=4)
            self.task = new
        if self.iid is not None:
            iid, self.iid = self.iid, None
            warnings.warn(
                "ScenarioConfig(iid=...) is deprecated; use partitioner= "
                "('iid', 'primary-class', 'buckets', 'dirichlet', 'zipf')",
                DeprecationWarning, stacklevel=4)
            if self.partitioner != "iid":
                raise ValueError(
                    "pass either the deprecated iid= flag or partitioner=, "
                    "not both")
            if not iid:
                self.partitioner = get_task(self.task).legacy_skew

    def fl_config(self) -> FLConfig:
        return FLConfig(num_clients=self.num_clients,
                        clients_per_round=self.clients_per_round,
                        num_shards=self.num_shards,
                        local_epochs=self.local_epochs,
                        global_rounds=self.global_rounds,
                        retrain_ratio=self.retrain_ratio)

    @classmethod
    def paper_full(cls, **overrides) -> "ScenarioConfig":
        """The paper's full setting (100 clients, G=30, L=10) — slow on CPU."""
        base = dict(num_clients=100, clients_per_round=20, num_shards=4,
                    local_epochs=10, global_rounds=30, samples_per_client=100,
                    image_size=28, seq_len=64, test_n=1000)
        base.update(overrides)
        return cls(**base)


TestData = Tuple[np.ndarray, np.ndarray]


def build_simulator(cfg: ScenarioConfig) -> Tuple[FLSimulator, TestData]:
    """Build the paper-protocol simulator + held-out test set for a scenario,
    resolving the task, model family, and partitioner registries."""
    task = get_task(cfg.task)
    family = get_model_family(cfg.model)
    model_cfg = family.build(cfg)
    partition = get_partitioner(cfg.partitioner, **cfg.partitioner_kwargs)
    clients, test = task.build_data(cfg, model_cfg, partition)
    opt = OptimizerConfig(name=cfg.opt_name,
                          lr=cfg.lr or family.default_lr or task.default_lr,
                          grad_clip=0.0)
    sim = FLSimulator(model_cfg, cfg.fl_config(), clients, task=task,
                      opt_cfg=opt,
                      local_batch=(cfg.local_batch or family.default_batch
                                   or task.default_batch),
                      seed=cfg.seed)
    return sim, test


def build_session(cfg: ScenarioConfig) -> Tuple[FederatedSession, TestData]:
    """Simulator wrapped in a session configured from the scenario."""
    sim, test = build_simulator(cfg)
    session = FederatedSession(sim, store_kind=cfg.store,
                               slice_dtype=cfg.slice_dtype,
                               batch_requests=cfg.batch_requests,
                               strict_schedule=cfg.strict_schedule,
                               checkpoint_every=cfg.checkpoint_every,
                               checkpoint_dir=cfg.checkpoint_dir,
                               store_options=cfg.store_options)
    return session, test


def run_scenario(cfg: ScenarioConfig) -> SessionReport:
    """Execute the scenario: K stages with the scheduled unlearning stream."""
    session, _test = build_session(cfg)
    return session.run(cfg.num_stages, schedule=cfg.schedule)
