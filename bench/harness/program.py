"""The system under test, built through its public entry points from a
configuration's JSON and the harness's own client data."""
from __future__ import annotations

import warnings


def simulator(cfg: dict, seed: int, clients):
    """An ``FLSimulator`` of ``cfg``'s model and federation over ``clients``;
    refuses a program configuration whose sizes differ from the JSON."""
    from repro.configs import FLConfig, OptimizerConfig, get_config
    from repro.fl.simulator import FLSimulator
    model = get_config(cfg["program_config"])
    for key, want in cfg["model"].items():
        have = getattr(model, key, None)
        if have is not None and list(have if isinstance(have, tuple) else [have]) \
                != list(want if isinstance(want, list) else [want]):
            raise ValueError(f"{cfg['name']}: the program's {key} is {have!r}, "
                             f"the configuration file says {want!r}")
    fed, opt = cfg["federation"], cfg["optimizer"]
    fl = FLConfig(num_clients=fed["num_clients"],
                  clients_per_round=fed["clients_per_stage"],
                  num_shards=fed["num_shards"],
                  local_epochs=fed["local_epochs"],
                  global_rounds=fed["global_rounds"],
                  retrain_ratio=fed["retrain_ratio"])
    return FLSimulator(model, fl, clients, task=cfg["task"],
                       opt_cfg=OptimizerConfig(name=opt["name"], lr=opt["lr"],
                                               grad_clip=0.0),
                       local_batch=opt["local_batch"], seed=seed)


def session(sim, cfg: dict):
    from repro.fl.experiment import FederatedSession
    fed = cfg["federation"]
    return FederatedSession(sim, store_kind=fed["store"], engine=fed["engine"])


def run_stage(sess):
    """``run_stage`` ended by ``block_until_ready``; the stage engine's
    fallback for ragged stages is an error, so the one-dispatch stage program
    is what ran."""
    import jax
    with warnings.catch_warnings():
        warnings.filterwarnings("error", message="ragged stage")
        record = sess.run_stage()
    jax.block_until_ready(record.shard_models)
    return record
