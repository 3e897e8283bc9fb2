"""Moonlight-16B-A3B's block on the CPU at a small size, on seeded random
weights: the held-expert layer against the uncut layer and against a plain
per-token reference (no token dropped however uneven the routing), the
adapter federation (the stage program stores adapter rows only, and takes
the frozen base once, unbatched), and the normal path through
``run_scenario``, SE unlearning and a served deletion request.  The forward,
loss and adapter gradients against the configuration's plain reference are
in ``bench/tests/test_moonlight_cell.py``."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import FLConfig, OptimizerConfig, get_config
from repro.fl.families import get_model_family
from repro.models import init_adapters, init_params
from repro.models.moe import apply_moe_held, init_moe
from repro.models.params import RealInit


def _tiny(**kw):
    return dataclasses.replace(get_model_family("moonlight").build(None), **kw)


def _moe_params(cfg, seed=0):
    return init_moe(RealInit(jax.random.key(seed), jnp.float32), cfg)


def _plain_moe(p, x, cfg, held):
    """Per token: the shared SwiGLU plus, for each of its top-k experts
    that is held, gate weight times that expert's SwiGLU (NumPy, float64)."""
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), p)
    silu = lambda v: v / (1 + np.exp(-v))  # noqa: E731
    xs = np.asarray(x, np.float64).reshape(-1, cfg.d_model)
    out = np.zeros_like(xs)
    sp = p["shared"]
    for t, v in enumerate(xs):
        out[t] = (silu(v @ sp["wi_gate"]) * (v @ sp["wi_up"])) @ sp["wo"]
        s = 1 / (1 + np.exp(-(v @ p["router"])))
        top = np.argsort(-(s + p["router_bias"]), kind="stable")[
            :cfg.experts_per_token]
        w = s[top] / s[top].sum() * cfg.moe_routed_scale
        for e, we in zip(top, w):
            if e < held:
                out[t] += we * ((silu(v @ p["wi_gate"][e]) * (v @ p["wi_up"][e]))
                                @ p["wo"][e])
    return out.reshape(x.shape)


def test_held_shares_sum_to_the_uncut_layer():
    """Each share of held experts computes its part; over all shares, with
    the shared experts (which every share computes alike) counted once, the
    parts add up to the uncut layer."""
    full_cfg = _tiny(experts_held=0)
    p = _moe_params(full_cfg)
    x = jax.random.normal(jax.random.key(1), (2, 16, full_cfg.d_model))
    full, full_load = apply_moe_held(p, x, full_cfg)
    held = 4
    shared_only = apply_moe_held(
        dict(p, wi_gate=p["wi_gate"][:0], wi_up=p["wi_up"][:0], wo=p["wo"][:0]),
        x, full_cfg)[0]
    total, loads = 0.0, []
    for first in range(0, full_cfg.num_experts, held):
        part = dict(p, **{k: p[k][first:first + held]
                          for k in ("wi_gate", "wi_up", "wo")})
        y, load = apply_moe_held(part, x, full_cfg, first_expert=first)
        total = total + (y - shared_only)
        loads.append(load)
    np.testing.assert_allclose(np.asarray(total + shared_only),
                               np.asarray(full), rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.concatenate(loads), np.asarray(full_load))
    assert float(full_load.sum()) == 2 * 16 * full_cfg.experts_per_token
    np.testing.assert_allclose(
        np.asarray(full), _plain_moe(p, x, full_cfg, full_cfg.num_experts),
        rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("hot", [1, 3])
def test_no_token_dropped_under_uneven_routing(hot):
    """A correction bias that sends every token to the same ``hot`` held
    experts: each of them gets every token, and every token's output is
    the plain per-token sum."""
    cfg = _tiny()
    p = _moe_params(cfg, seed=2)
    bias = np.zeros(cfg.num_experts, np.float32)
    bias[:hot] = 10.0
    p["router_bias"] = jnp.asarray(bias)
    x = jax.random.normal(jax.random.key(3), (2, 32, cfg.d_model))
    y, load = apply_moe_held(p, x, cfg)
    tokens = 2 * 32
    np.testing.assert_array_equal(np.asarray(load[:hot]), tokens)
    assert float(load.sum()) <= tokens * cfg.experts_per_token
    np.testing.assert_allclose(np.asarray(y), _plain_moe(p, x, cfg, cfg.experts_held),
                               rtol=1e-4, atol=1e-5)


def _sim(cfg, clients=None, rounds=2):
    from repro.fl.simulator import FLSimulator
    fl = FLConfig(num_clients=8, clients_per_round=4, num_shards=2,
                  local_epochs=1, global_rounds=rounds)
    return FLSimulator(cfg, fl, clients or {}, task="generation",
                       opt_cfg=OptimizerConfig(name="sgd", lr=0.1, grad_clip=0.0),
                       local_batch=2, seed=5)


def _base_never_batched(jaxpr, base_shapes):
    """No value of the program has a base leaf's shape behind extra leading
    dimensions: the base is never broadcast or vmapped."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            shape = tuple(getattr(v.aval, "shape", ()))
            for b in base_shapes:
                assert not (len(shape) > len(b) and shape[-len(b):] == b), \
                    (eqn.primitive, shape)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _base_never_batched(sub, base_shapes)


def test_stage_program_stores_adapter_rows_over_an_unbatched_base():
    cfg = _tiny()
    sim = _sim(cfg)
    base, w0 = sim.base, sim.init_params(jax.random.key(5))
    p_adapters = sum(a.size for a in jax.tree.leaves(w0))
    assert p_adapters == cfg.lora_params()
    assert jax.tree.structure(base) == jax.tree.structure(
        init_params(cfg, jax.random.key(5)))
    xs = jax.ShapeDtypeStruct((2, 2, 4, 8), jnp.int32)   # seq 8: no width
    enc = jax.ShapeDtypeStruct((4, 2), jnp.float32)
    prog = sim._get_stage_program(1, "flat", 2, encode=True)
    jitted, bound = prog.func, prog.args
    assert bound == (base,)
    shapes = jax.eval_shape(jitted, base, w0, xs, xs, enc)
    final, round_in, coded, norms, load = shapes
    assert coded.shape == (2, 4, 2 * p_adapters)          # (G, C, M*P)
    assert jax.tree.structure(final) == jax.tree.structure(w0)
    assert norms.shape == (2, 2, 2)
    assert load.shape == (2, 2, 2, cfg.layers_in_stack, cfg.experts_held)
    # the base enters once, each leaf at its own shape
    lowered = jitted.lower(base, w0, xs, xs, enc)
    base_in = jax.tree.leaves(lowered.args_info[0][0])
    assert [a.shape for a in base_in] == [a.shape for a in jax.tree.leaves(base)]
    def core(shape):                    # without the stack's leading 1
        while shape and shape[0] == 1:
            shape = shape[1:]
        return shape
    big = {core(a.shape) for a in jax.tree.leaves(base)
           if len(core(a.shape)) >= 2 and min(core(a.shape)) >= 4}
    closed = jax.make_jaxpr(jitted)(base, w0, xs, xs, enc)
    _base_never_batched(closed.jaxpr, big)


def test_full_configuration_counts():
    cfg = get_config("moonlight-16b-a3b-fedlora")
    assert cfg.lora_params() == 657_920
    # 568,459,520 in the weights, 25,088 in the norm scales
    assert cfg.param_count() == 568_459_520 + 25_088
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == cfg.param_count()
    adapters = jax.eval_shape(lambda: init_adapters(cfg, jax.random.key(0)))
    assert sum(a.size for a in jax.tree.leaves(adapters)) == 657_920


def test_mla_is_trained_only_and_left_out_of_the_serving_archs():
    """MLA has no decode cache: the configuration says it cannot serve, so
    the architectures every dry-run and smoke test serves leave it out."""
    from repro.configs import ASSIGNED_ARCHS
    from repro.models import prefill_fn
    assert not get_config("moonlight-16b-a3b-fedlora").serves
    assert "moonlight-16b-a3b-fedlora" not in ASSIGNED_ARCHS
    assert all(get_config(a).serves for a in ASSIGNED_ARCHS)
    cfg = _tiny()
    tokens = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(ValueError, match="mla"):
        prefill_fn(cfg)(init_params(cfg, jax.random.key(0)),
                        {"tokens": tokens, "labels": tokens})


def test_scenario_stage_unlearning_and_a_served_request():
    """The tiny variant through ``run_scenario``'s path on the stage engine
    and the coded store: one stage, one SE deletion, then one deletion
    request served by ``UnlearningService``."""
    from repro.fl.experiment import (RequestSchedule, ScenarioConfig,
                                     UnlearnRequest, build_session)
    from repro.service import (UnlearningService, sequenced_trace,
                               single_device_placement)
    schedule = RequestSchedule([UnlearnRequest(
        lambda plan: [plan.shard_clients[0][0]], framework="SE", rounds=1)])
    cfg = ScenarioConfig(task="generation", model="moonlight", num_clients=8,
                         clients_per_round=4, num_shards=2, local_epochs=1,
                         global_rounds=2, samples_per_client=4, seq_len=16,
                         test_n=20, local_batch=2, store="coded",
                         engine="stage", num_stages=1, schedule=schedule)
    session, (tx, ty) = build_session(cfg)
    report = session.run(cfg.num_stages, schedule=cfg.schedule)
    record = session.records[-1]
    (res,) = report.stages[0].unlearn
    assert res.framework == "SE" and list(res.impacted_shards) == [0]
    assert record.expert_load.shape == (1, 4)
    row = sum(a.size for a in jax.tree.leaves(record.shard_models[0]))
    assert row == session.sim.cfg.lora_params()
    victim = record.plan.shard_clients[1][0]
    served = UnlearningService(
        session, policy="fifo", placement=single_device_placement()).serve(
            sequenced_trace([victim], rounds=1))
    assert [e.rid for e in served.entries] == [0]
    (res2,) = [u for st in session.report.stages for u in st.unlearn][1:]
    assert list(res2.impacted_shards) == [1]
    metrics = session.sim.evaluate(res2.models, tx, ty)
    assert all(np.isfinite(v) for v in metrics.values())
