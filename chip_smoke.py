#!/usr/bin/env python3
"""Bring-up smoke: the federated train -> unlearn -> serve path on a TPU.

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # four chips: multi-device serving only

The one-chip run drives both paper tasks (generation: nanogpt-paper at its
published widths; classification: the paper's CNN) at
``ScenarioConfig.paper_full`` (100 clients, 20 sampled per stage, S=4 shards,
L=10 local epochs, G=30 rounds) through the normal entry points:
``build_session`` -> ``FederatedSession.run_stage`` (the stage program, coded
store) -> ``FederatedSession.unlearn`` (SE) -> ``UnlearningService.serve``.
Weights come from the scenario's seed.

Phases and their checks (errors are max|x - ref| / max|ref| over the flat
parameter vector):

* parity  - a G=1, L=1 stage on the chip, at default and at "highest"
            matmul precision, against the same stage on the host CPU under
            ``default_matmul_precision("highest")``;
* stage   - the whole-stage program, run twice (two stages);
* coding  - every stored round decoded from its coded slices against the
            same round of an uncoded-store session, and quorum reads of
            clean slices that must flag nothing;
* unlearn - one SE request, twice: only the victim's shard is impacted and
            every model is finite;
* service - an 8-request Poisson FIFO stream on one device, twice: all
            served, none aborted (generation);
* kernels - coded_matmul_rounds (the stage program's encode, through
            ``store_options={"use_kernel": True}``), calibrate, ssm_scan and
            wkv against their ``ref.py`` at the scenario's shapes, each
            compiled as a ``tpu_custom_call`` (generation).

Every phase prints its compile seconds, its first- and second-call wall
(ended by ``block_until_ready``) and the device's peak memory, labelled with
the chip they ran on.  With ``--chips 4`` only the 8-request stream is
served, over all four chips and over one, and the per-request models must
agree.  A failed check exits 1; no TPU exits 2 before any work.  The last
line of a passing run is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
import warnings
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent

# Bounds, all on the max-abs relative error described above, established on
# a TPU v5 lite chip.
# Training parity after one round (G=1, L=1).  At default precision the chip
# rounds every f32 matmul operand to bf16 (one pass, roundoff 2**-8); the
# nanogpt embedding table then lands 4.1e-2 of the weights (19% of its
# update) off the host's; the CNN 1.7e-3.
PARITY_BOUND = 1e-1
# At "highest" the chip still differs from the host by 1.2e-3 (nanogpt) and
# 1.4e-3 (CNN) of the weights, more than a 1e-7 perturbation of the initial
# weights grows to on the host (2e-5 to 5e-5): an empirical bound.
PARITY_EXACT_BOUND = 5e-3
CODING_BOUND = 1e-5          # the bound the CPU tests hold the encode to
CODED_MATMUL_BOUND = 1e-5    # coded_matmul_rounds vs ref.py, f32 both sides
# calibrate runs its dot at default precision (one bf16 pass), as does the
# jnp calibration it can stand in for; measured 1.0e-3 to 1.2e-3.
CALIBRATE_BOUND = 1e-2
SCAN_KERNEL_BOUND = 5e-4     # ssm_scan, wkv: the interpret tests' tolerance
PLACEMENT_BOUND = 1e-6       # same program on another chip of the same kind

N_REQUESTS = 8
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


def _die(msg: str, code: int = 2):
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def paper_config(task: str, **overrides):
    """The paper's full federation for ``task``, coded store."""
    from repro.fl.experiment import ScenarioConfig
    return ScenarioConfig.paper_full(
        **{"task": task, "store": "coded", **overrides})


# ---------------------------------------------------------------------------
# Measurement and checks
# ---------------------------------------------------------------------------

class Smoke:
    """Prints per-phase chip numbers and collects failed checks."""

    def __init__(self, device):
        import jax
        self.device = device
        self.label = f"chip {device.platform} {device.device_kind}"
        self.failed = []
        self._compile_s = 0.0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            with self._lock:
                self._compile_s += duration

    def timed(self, phase: str, first, second=None):
        """Run ``first`` (and ``second``), each ended by block_until_ready;
        print compile seconds (trace + lower + backend compile, persistent
        cache hits included) of the first call, both walls and peak memory."""
        import jax
        c0 = self._compile_s
        t0 = time.perf_counter()
        out1 = jax.block_until_ready(first())
        wall1 = time.perf_counter() - t0
        compile_s = self._compile_s - c0
        out2, wall2 = None, "n/a"
        if second is not None:
            t0 = time.perf_counter()
            out2 = jax.block_until_ready(second())
            wall2 = f"{time.perf_counter() - t0:.6f}"
        peak = (self.device.memory_stats() or {}).get("peak_bytes_in_use")
        print(f"[{self.label}] {phase}: compile_s={compile_s:.6f} "
              f"first_call_s={wall1:.6f} second_call_s={wall2} "
              f"peak_bytes_in_use={peak}", flush=True)
        return out1, out2

    def check(self, name: str, value, bound) -> None:
        ok = value <= bound
        print(f"check {name}: {value} (bound <= {bound}) "
              f"{'ok' if ok else 'FAILED'}", flush=True)
        if not ok:
            self.failed.append(name)

    def native(self, name: str, compiled) -> None:
        """A kernel that compiled to the interpreter fails the run."""
        found = "tpu_custom_call" in compiled.as_text()
        print(f"check {name} compiled as tpu_custom_call: {found}",
              flush=True)
        if not found:
            self.failed.append(f"{name} tpu_custom_call")


def _flat(tree):
    import jax
    return np.concatenate([np.ravel(np.asarray(x, np.float64))
                           for x in jax.tree.leaves(jax.device_get(tree))])


def rel_err(x, ref) -> float:
    a, b = _flat(x), _flat(ref)
    return float(np.max(np.abs(a - b)) / max(float(np.max(np.abs(b))), 1e-30))


def _finite(tree) -> bool:
    return bool(np.all(np.isfinite(_flat(tree))))


def run_stage_strict(session):
    """``run_stage`` with the per-shard fallback of a ragged stage raised as
    an error: the one-dispatch stage program must be what ran."""
    with warnings.catch_warnings():
        warnings.filterwarnings("error", message="ragged stage")
        return session.run_stage()


def round_matrix(record, rnd: int):
    """(S, M*P) host matrix of one stored round, read through the store's
    public ``get_shard`` (decoded from slices for a coded store)."""
    rows = []
    for s, clients in sorted(record.plan.shard_clients.items()):
        trees = record.store.get_shard(rnd, s)
        rows.append(_flat([trees[c] for c in clients]))
    return np.stack(rows)


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------

def phase_parity(smoke: Smoke, task: str) -> None:
    import jax
    from repro.fl.experiment import build_session
    cfg = paper_config(task, global_rounds=1, local_epochs=1)
    chip_session, _ = build_session(cfg)
    chip_models, _ = smoke.timed(
        f"{task} parity stage G=1 L=1",
        lambda: run_stage_strict(chip_session).shard_models)
    with jax.default_matmul_precision("highest"):
        exact_session, _ = build_session(cfg)
        exact_models = run_stage_strict(exact_session).shard_models
    with jax.default_device(jax.devices("cpu")[0]), \
            jax.default_matmul_precision("highest"):
        host_session, _ = build_session(cfg)
        host_models = run_stage_strict(host_session).shard_models
    for name, models, bound in (
            ("default", chip_models, PARITY_BOUND),
            ("highest", exact_models, PARITY_EXACT_BOUND)):
        err = max(rel_err(models[s], host_models[s]) for s in host_models)
        smoke.check(f"{task} training parity, chip at {name} precision vs "
                    f"host cpu at highest", err, bound)


def phase_coding(smoke: Smoke, name: str, record, ref_record) -> list:
    """Decode every stored round; returns the uncoded reference rounds."""
    if record.plan.shard_clients != ref_record.plan.shard_clients:
        raise RuntimeError("coded and uncoded sessions sampled different "
                           "stages; the seeds no longer line up")
    g_rounds = len(next(iter(ref_record.round_globals.values()))) - 1
    refs, worst = [], 0.0
    for g in range(g_rounds):
        ref = round_matrix(ref_record, g)
        worst = max(worst, rel_err(round_matrix(record, g), ref))
        refs.append(ref)
    smoke.check(f"{name}: decode of all {g_rounds} stored rounds vs uncoded",
                worst, CODING_BOUND)
    store = record.store
    before = store.stats.recovered_reads
    everyone = list(range(store.scheme.num_clients))
    for g in range(g_rounds):
        store.get_shard(g, 0, available=everyone)
    smoke.check(f"{name}: quorum reads of clean slices that recovered",
                store.stats.recovered_reads - before, 0)
    unpinned_round_trip(name, store.scheme, refs[-1])
    return refs


def unpinned_round_trip(name: str, scheme, ref) -> None:
    """Prints (checks nothing) what the encode -> decode round trip of one
    real round costs at each matmul precision, which is why ``core/coding``
    pins its dots to HIGHEST."""
    import jax
    import jax.numpy as jnp
    dec, used = scheme.decode_matrix(list(range(scheme.num_clients)))
    enc = jnp.asarray(scheme.encode_matrix()[np.asarray(used, int)],
                      jnp.float32)
    dec = jnp.asarray(dec, jnp.float32)
    w = jnp.asarray(ref, jnp.float32)
    for enc_p in ("default", "highest"):
        for dec_p in ("default", "highest"):
            out = jnp.matmul(dec, jnp.matmul(enc, w, precision=enc_p),
                             precision=dec_p)
            print(f"{name}: encode at {enc_p}, decode at {dec_p} precision, "
                  f"round trip vs round: {rel_err(out, ref)}", flush=True)


def phase_unlearn(smoke: Smoke, task: str, session) -> None:
    from repro.fl.experiment import UnlearnRequest
    plan0 = session.records[0].plan
    victims = [plan0.shard_clients[s][0]
               for s in sorted(plan0.shard_clients)[:2]]
    first, second = smoke.timed(
        f"{task} SE unlearn",
        lambda: session.unlearn(UnlearnRequest([victims[0]],
                                               framework="SE")),
        lambda: session.unlearn(UnlearnRequest([victims[1]],
                                               framework="SE")))
    for victim, results in zip(victims, (first, second)):
        stages = [rec for rec in session.records
                  if victim in rec.plan.clients]
        wrong = int(len(results) != len(stages))
        for rec, res in zip(stages, results):
            wrong += int(list(res.impacted_shards)
                         != [rec.plan.shard_of(victim)])
        smoke.check(f"{task} SE unlearn of client {victim}: results whose "
                    f"impacted shards are not the victim's shard alone",
                    wrong, 0)
        smoke.check(f"{task} SE unlearn of client {victim}: non-finite "
                    f"models", int(not all(_finite(r.models)
                                           for r in results)), 0)


def serve_stream(session, placement):
    """Serve the 8-request FIFO trace; returns the report and the unlearn
    results the serve landed in the session report."""
    from repro.service import UnlearningService, poisson_trace
    trace = poisson_trace(session.records[0].plan.clients, n=N_REQUESTS,
                          rate=8.0, seed=0, skew=1.5, deadline=30.0)
    landed = [len(st.unlearn) for st in session.report.stages]
    with placement:
        report = UnlearningService(session, policy="fifo",
                                   placement=placement).serve(trace)
    results = [r for st, n in zip(session.report.stages, landed)
               for r in st.unlearn[n:]]
    return report, results


def check_served(smoke: Smoke, name: str, report) -> None:
    smoke.check(f"{name}: requests not completed",
                N_REQUESTS - len(report.completed), 0)
    smoke.check(f"{name}: num_aborted", report.num_aborted, 0)
    print(f"[{smoke.label}] {name}: serve_wall_s={report.serve_wall:.6f} "
          f"latency_p50_s={report.p50:.6f} latency_p95_s={report.p95:.6f}",
          flush=True)


def phase_service(smoke: Smoke, session) -> None:
    from repro.service import single_device_placement
    (r1, _), (r2, _) = smoke.timed(
        "generation service fifo x8, one device",
        lambda: serve_stream(session, single_device_placement()),
        lambda: serve_stream(session, single_device_placement()))
    for i, report in enumerate((r1, r2), 1):
        check_served(smoke, f"generation service serve {i}", report)


def phase_kernels(smoke: Smoke, ref_record, refs) -> None:
    """The four main-path kernels, natively, against their ref.py."""
    import functools

    import jax
    import jax.numpy as jnp
    from repro.core import coding
    from repro.fl.experiment import build_session
    from repro.kernels.calibrate.ops import calibrate_update
    from repro.kernels.calibrate.ref import calibrate_update_ref
    from repro.kernels.coded_matmul.ref import coded_matmul_ref
    from repro.kernels.ssm_scan.ops import ssm_scan
    from repro.kernels.ssm_scan.ref import ssm_scan_ref
    from repro.kernels.wkv.ops import wkv
    from repro.kernels.wkv.ref import wkv_ref

    def against_ref(name, fn, ref_fn, args, bound):
        jitted = jax.jit(fn)
        out, _ = smoke.timed(f"kernel {name}", lambda: jitted(*args),
                             lambda: jitted(*args))
        smoke.native(name, jitted.lower(*args).compile())
        with jax.default_matmul_precision("highest"):
            ref = ref_fn(*args)
        smoke.check(f"kernel {name} vs ref.py", rel_err(out, ref), bound)

    # coded_matmul_rounds: the stage program's encode, end to end ...
    session, _ = build_session(paper_config(
        "generation", store_options={"use_kernel": True}))
    smoke.timed("generation stage, kernel encode in the stage program",
                lambda: run_stage_strict(session).shard_models)
    phase_coding(smoke, "generation kernel-encoded store",
                 session.records[0], ref_record)
    # ... and alone, on the real (G, S, M*P) round history
    enc = jnp.asarray(session.records[0].store.scheme.encode_matrix(),
                      jnp.float32)
    hist = jnp.asarray(np.stack(refs), jnp.float32)
    against_ref("coded_matmul_rounds",
                functools.partial(coding.encode_rounds, use_kernel=True),
                jax.vmap(coded_matmul_ref, in_axes=(None, 0)),
                (enc, hist), CODED_MATMUL_BOUND)
    del session, hist

    # calibrate: eq. (3) on a shard model and four retained clients' deltas
    rng = np.random.default_rng(0)
    s0 = sorted(ref_record.shard_models)[0]
    w = jnp.asarray(_flat(ref_record.shard_models[s0]), jnp.float32)
    rows = np.asarray(refs[0][0]).reshape(-1, w.shape[0])[1:]
    deltas = jnp.asarray(rows, jnp.float32) - w
    coeffs = jnp.asarray(rng.uniform(0.1, 0.4, len(rows)), jnp.float32)
    against_ref("calibrate", calibrate_update, calibrate_update_ref,
                (w, deltas, coeffs), CALIBRATE_BOUND)

    # ssm_scan / wkv at the mamba and rwkv6 families' paper_full shapes:
    # batch 10, sequence 64; d_inner 64 with state 8; 2 heads of 16
    bsz, seq = 10, 64
    d, n = 64, 8
    dt = jnp.asarray(np.abs(rng.standard_normal((bsz, seq, d))) * 0.1 + 0.01,
                     jnp.float32)
    b, c = (jnp.asarray(rng.standard_normal((bsz, seq, n)), jnp.float32)
            for _ in range(2))
    x = jnp.asarray(rng.standard_normal((bsz, seq, d)), jnp.float32)
    a = jnp.asarray(-np.abs(rng.standard_normal((d, n))) - 0.1, jnp.float32)
    h0 = jnp.asarray(rng.standard_normal((bsz, d, n)), jnp.float32) * 0.1
    against_ref("ssm_scan", ssm_scan, ssm_scan_ref, (dt, b, c, x, a, h0),
                SCAN_KERNEL_BOUND)

    heads, n = 2, 16
    shape = (bsz, seq, heads, n)
    r = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    k = jnp.asarray(rng.standard_normal(shape), jnp.float32) * 0.3
    v = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    lw = jnp.asarray(-np.abs(rng.standard_normal(shape)) - 0.05, jnp.float32)
    u = jnp.asarray(rng.standard_normal((heads, n)), jnp.float32) * 0.5
    h0 = jnp.asarray(rng.standard_normal((bsz, heads, n, n)),
                     jnp.float32) * 0.1
    against_ref("wkv", wkv,
                jax.vmap(wkv_ref, in_axes=(2, 2, 2, 2, 0, 1),
                         out_axes=(2, 1)),
                (r, k, v, lw, u, h0), SCAN_KERNEL_BOUND)


def one_chip(smoke: Smoke) -> None:
    from repro.fl.experiment import build_session
    for task in ("generation", "classification"):
        phase_parity(smoke, task)
        session, _ = build_session(paper_config(task))
        ref_session, _ = build_session(paper_config(task, store="uncoded"))
        smoke.timed(f"{task} stage, uncoded reference store",
                    lambda: run_stage_strict(ref_session).shard_models)
        smoke.timed(f"{task} stage",
                    lambda: run_stage_strict(session).shard_models,
                    lambda: run_stage_strict(session).shard_models)
        ref_record = ref_session.records[0]
        drift = rel_err(session.records[0].shard_models,
                        ref_record.shard_models)
        print(f"{task}: final shard models, coded vs uncoded session (same "
              f"stage, separately compiled programs): {drift}", flush=True)
        refs = phase_coding(smoke, f"{task} coded store",
                            session.records[0], ref_record)
        phase_unlearn(smoke, task, session)
        if task == "generation":
            phase_service(smoke, session)
            phase_kernels(smoke, ref_record, refs)
        del session, ref_session, ref_record, refs


def four_chips(smoke: Smoke, count: int) -> None:
    from repro.fl.experiment import build_session
    from repro.service import DevicePlacement, single_device_placement
    session, _ = build_session(paper_config("generation"))
    smoke.timed("generation stage",
                lambda: run_stage_strict(session).shard_models)
    (rep1, res1), _ = smoke.timed(
        "generation service fifo x8, one device",
        lambda: serve_stream(session, single_device_placement()))
    (rep4, res4), _ = smoke.timed(
        f"generation service fifo x8, {count} devices",
        lambda: serve_stream(session, DevicePlacement()))
    check_served(smoke, "one-device serve", rep1)
    check_served(smoke, f"{count}-device serve", rep4)
    print(f"placement: {json.dumps(rep4.placement)}", flush=True)
    used = sorted({d for e in rep4.entries for d in e.devices})
    print(f"ledger devices per request: "
          f"{[list(e.devices) for e in rep4.entries]}", flush=True)
    smoke.check("devices that served no job",
                count - len(set(used) & set(range(count))), 0)
    smoke.check("placement devices missing",
                count - rep4.placement["num_devices"], 0)
    if len(res1) != len(res4):
        raise RuntimeError(f"{len(res1)} one-device results against "
                           f"{len(res4)} {count}-device results")
    err = max(rel_err(a.models, b.models) for a, b in zip(res4, res1))
    smoke.check(f"per-request models, {count} devices vs one", err,
                PLACEMENT_BOUND)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: serve across four chips and compare with one "
                         "(only that phase)")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        _die(f"no repro package under {ROOT / 'src'}; run from a checkout")
    # the parity phase needs the host CPU backend next to the TPU
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    import jax
    device = jax.devices()[0]
    if device.platform != "tpu":
        _die(f"no TPU: JAX's first device is {device.platform!r}; this smoke "
             f"runs only on the chip and has no CPU fallback")
    count = len(jax.devices())
    if count < args.chips:
        _die(f"--chips {args.chips} but JAX sees {count} device(s)")

    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import configure_compile_cache
    print(f"compile cache: {configure_compile_cache()}", flush=True)
    print(f"jax {jax.__version__}; {count} x {device.device_kind}",
          flush=True)

    smoke = Smoke(device)
    if args.chips == 4:
        four_chips(smoke, count)
    else:
        one_chip(smoke)
    if smoke.failed:
        _die(f"{len(smoke.failed)} check(s) failed: {smoke.failed}", 1)
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": count}}), flush=True)


if __name__ == "__main__":
    main()
