"""Stage training: back-to-back ``FederatedSession.run_stage`` calls of the
stage engine over one warmed simulator, each stage in a fresh session so one
stage's record is held at a time.

Set-up makes the client data, builds the simulator and trains the first
stage, which compiles the stage program; that stage is the one compared with
the reference.  The window trains stages until ``--seconds`` have passed, and
``stage_s`` is the window's wall time over the stages it completed.  A traced
run then trains ``trace_stages`` more stages under the profiler.

The comparison (``numbers``) covers the stage program's training and the
coded store: the reference trains the first ``check_rounds`` rounds of every
shard from its own weights and data plan; the program's round globals, its
stored update norms and its stored client models, decoded from the coded
slices, are held against it by norms leaf by leaf.
"""
from __future__ import annotations

import gc
import time

import jax
import numpy as np

from bench.harness import compare, program
from bench.harness.cell import annotate, profiled, trace_summary
from bench.harness.common import Check, Outcome
from bench.harness.reference import FedReference


def plan(cfg: dict, seed: int, stages: int = 1):
    """The program's client draw: ``clients_per_stage`` distinct clients per
    stage from ``default_rng(seed)``, split in order into equal shards."""
    fed = cfg["federation"]
    rng = np.random.default_rng(seed)
    per = fed["clients_per_stage"] // fed["num_shards"]
    out = []
    for _ in range(stages):
        chosen = rng.choice(fed["num_clients"], fed["clients_per_stage"],
                            replace=False)
        out.append({s: sorted(int(c) for c in chosen[s * per:(s + 1) * per])
                    for s in range(fed["num_shards"])})
    return out


def stacked_data(clients, shard_clients):
    shards = sorted(shard_clients)
    xs = np.stack([np.stack([clients[c][0] for c in shard_clients[s]])
                   for s in shards])
    ys = np.stack([np.stack([clients[c][1] for c in shard_clients[s]])
                   for s in shards])
    return xs, ys


def host(tree):
    return jax.tree.map(lambda a: np.asarray(a, np.float32), jax.device_get(tree))


# --------------------------------------------------------------- comparison
def program_side(record, rounds: int) -> dict:
    """What the stage record says about its first ``rounds`` rounds."""
    shard_clients = {s: list(cs) for s, cs in record.plan.shard_clients.items()}
    shards = sorted(shard_clients)
    side = {"plan": shard_clients,
            "globals": [{s: host(record.round_globals[s][g]) for s in shards}
                        for g in range(rounds + 1)],
            "norms": np.asarray([[[record.history_norms[(s, g, c)]
                                   for c in shard_clients[s]] for s in shards]
                                 for g in range(rounds)]),
            "stored": []}
    for g in range(rounds):
        side["stored"].append({s: {c: host(t) for c, t in
                                   record.store.get_shard(g, s).items()}
                               for s in shards})
    return side


def reference_side(cfg, model, clients, seeds, rounds, dtype=np.float32,
                   half_batch=False):
    """The same quantities from the reference (``dtype`` bfloat16: the
    control; ``half_batch``: a planted fault), plus the leaves kept for
    comparison."""
    shard_clients = plan(cfg, seeds["program"])[0]
    shards = sorted(shard_clients)
    ref = FedReference(model, cfg, dtype, half_batch)
    w0 = model.init(seeds["program"])
    xs, ys = stacked_data(clients, shard_clients)
    out = ref.train(w0, xs, ys, rounds, keep_locals=rounds)
    f32 = lambda t: jax.tree.map(lambda a: np.asarray(a, np.float32), t)  # noqa: E731
    glob = f32(out["globals"])
    loc = f32(out["locals"])
    side = {"plan": shard_clients,
            "globals": [{s: jax.tree.map(lambda a, g=g, i=i: a[g, i], glob)
                         for i, s in enumerate(shards)}
                        for g in range(rounds + 1)],
            "norms": out["norms"],
            "stored": [{s: {c: jax.tree.map(lambda a, g=g, i=i, m=m: a[g, i, m],
                                            loc)
                            for m, c in enumerate(shard_clients[s])}
                        for i, s in enumerate(shards)}
                       for g in range(rounds)]}
    keep = compare.kept_leaves(ref.first_gradient(w0, xs[0, 0], ys[0, 0]))
    return side, keep


def numbers(side: dict, ref: dict, keep) -> dict:
    """The numbers compared; see the module docstring."""
    rounds = len(side["norms"])
    shards = sorted(ref["plan"])
    out = {"plan": float(sum(side["plan"].get(s) != ref["plan"][s]
                             for s in shards))}
    if out["plan"]:
        return out
    out["init"] = compare.exact_gap(side["globals"][0][shards[0]],
                                    ref["globals"][0][shards[0]])
    out["update_norms"] = compare.scalar_gap(side["norms"], ref["norms"])
    out["first_norms"] = compare.scalar_gap(side["norms"][0], ref["norms"][0])
    out["change"] = max(compare.norm_gap(
        compare.leaf_norms(compare.tree_sub(side["globals"][rounds][s],
                                            side["globals"][0][s])),
        compare.leaf_norms(compare.tree_sub(ref["globals"][rounds][s],
                                            ref["globals"][0][s])), keep)
        for s in shards)
    stored = [[compare.leaf_gaps(
        compare.leaf_norms(compare.tree_sub(side["stored"][g][s][c],
                                            side["globals"][g][s])),
        compare.leaf_norms(compare.tree_sub(ref["stored"][g][s][c],
                                            ref["globals"][g][s])), keep)
        for s in shards for c in ref["plan"][s]] for g in range(rounds)]
    out["stored"] = max(max(gaps.values()) for by_g in stored for gaps in by_g)
    out["stored_first"] = max(max(gaps.values()) for gaps in stored[0])
    out["stored_median"] = max(float(np.median(list(gaps.values())))
                               for by_g in stored for gaps in by_g)
    out["change_median"] = max(float(np.median(list(compare.leaf_gaps(
        compare.leaf_norms(compare.tree_sub(side["globals"][rounds][s],
                                            side["globals"][0][s])),
        compare.leaf_norms(compare.tree_sub(ref["globals"][rounds][s],
                                            ref["globals"][0][s])),
        keep).values()))) for s in shards)
    return out


# --------------------------------------------------------------------- run
def setup(run):
    """Client data, the simulator, and the first (compiling) stage."""
    fed = run.cfg["federation"]
    clients = run.model.make_clients(run.seeds["data"], fed["num_clients"],
                                     run.cfg["data"]["samples_per_client"])
    run.log("client data made")
    sim = program.simulator(run.cfg, run.seeds["program"], clients)
    first = program.run_stage(program.session(sim, run.cfg))
    run.log("first stage trained")
    if first.expert_load is not None:
        # the routing this run's checkpoint and corpus give: held (token,
        # expert) pairs over the rows of the stage's whole pair buffers
        held = np.asarray(jax.device_get(first.expert_load))
        xs, _ = stacked_data(clients, first.plan.shard_clients)
        rows = sim.stage_pair_rows(xs.shape, fed["local_epochs"],
                                   fed["global_rounds"])
        pairs = float(held.sum())
        run.log(f"set-up stage: {pairs!r} held pairs, fill "
                f"{pairs / (rows * held.shape[0])!r}")
    return clients, sim, first


def execute(run) -> Outcome:
    w = run.workload
    clients, sim, first = setup(run)
    t0 = run.open_window()
    stages = 0
    while True:
        program.run_stage(program.session(sim, run.cfg))
        stages += 1
        if time.perf_counter() - t0 >= run.seconds:
            break
    window = time.perf_counter() - t0
    run.close_window()

    fed = run.cfg["federation"]
    flops = (fed["clients_per_stage"] * run.cfg["data"]["samples_per_client"]
             * fed["local_epochs"] * fed["global_rounds"]
             * run.model.flops_per_example())
    out = Outcome(attempted=stages, failed=0,
                  end_to_end={"stage_s": window / stages},
                  layer_data={"stages": stages, "window_s": window,
                              "flops_per_stage": flops,
                              "peak_flops": run.peak_flops})
    if run.trace:
        found = {}
        with profiled(found):
            for _ in range(int(w["trace_stages"])):
                with annotate("stage"):
                    program.run_stage(program.session(sim, run.cfg))
        out.trace = trace_summary(found["trace"])
        out.layer_data["trace"] = out.trace
        run.log("traced part done")

    rounds = int(w["check_rounds"])
    side = program_side(first, rounds)
    del sim, first
    gc.collect()
    run.log("program's answers read")
    ref, keep = reference_side(run.cfg, run.model, clients, run.seeds, rounds)
    got = numbers(side, ref, keep)
    run.log("reference compared; readings " + ", ".join(
        f"{k}={v!r}" for k, v in got.items()))
    limits = w["limits"]
    out.checks = [Check(k, got.get(k, float("inf")), float(limits[k]))
                  for k in limits]
    return out
