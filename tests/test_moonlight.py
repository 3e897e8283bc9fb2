"""Moonlight-16B-A3B's block on the CPU at a small size, on seeded random
weights: the held-expert layer against the uncut layer and against a plain
per-token reference (no token dropped however uneven the routing), the
adapter federation (the stage program stores adapter rows only, and takes
the frozen base once, unbatched), and the normal path through
``run_scenario``, SE unlearning and a served deletion request.  The forward,
loss and adapter gradients against the configuration's plain reference are
in ``bench/tests/test_moonlight_cell.py``."""
import dataclasses
import functools

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


def _routed(cfg, routing, seed=2):
    """The layer's weights with a correction bias that makes ``routing``:
    ``balanced`` (no bias), ``hot1``/``hot3`` (every token to the first 1 or
    3 held experts), ``full`` (every choice held: a full pair buffer),
    ``cold`` (all choices but one on experts not held: a small pair buffer)
    or ``none`` (no token to a held expert: empty groups)."""
    p = _moe_params(cfg, seed=seed)
    bias = np.zeros(cfg.num_experts, np.float32)
    held, k = cfg.experts_held, cfg.experts_per_token
    hot = {"balanced": [], "hot1": [0], "hot3": [0, 1, 2],
           "full": range(held), "cold": range(held, held + k - 1),
           "none": range(held, 2 * held)}[routing]
    bias[list(hot)] = 10.0
    return dict(p, router_bias=jnp.asarray(bias))


# calling forms: a plain call, vmap over clients, vmap over shards of vmap
# over clients; each leading shape is batched, the rest is (B, S) = (2, 64).
# Pooled over a vmap they make pair buffers of two sizes, and the routings
# pick among them.
FORMS = {"plain": (), "clients": (3,), "shards_clients": (2, 3)}
B, S = 2, 64
ROUTINGS = ("balanced", "hot1", "hot3", "full", "cold", "none")


def _vmapped(fn, depth):
    for _ in range(depth):
        fn = jax.vmap(fn)
    return fn


@pytest.mark.parametrize("routing", ROUTINGS)
@pytest.mark.parametrize("form", FORMS)
def test_held_layer_matches_the_plain_layer(form, routing):
    """Every calling form pools its tokens into one grouped matmul a weight,
    in the smallest pair buffer that holds them; each token's output is
    still the plain per-token sum, and the load is its own sample's."""
    cfg = _tiny()
    p = _routed(cfg, routing)
    lead = FORMS[form]
    x = jax.random.normal(jax.random.key(3), lead + (B, S, cfg.d_model))
    y, load = _vmapped(lambda x: apply_moe_held(p, x, cfg), len(lead))(x)
    flat_x = np.asarray(x).reshape(-1, B, S, cfg.d_model)
    flat_y = np.asarray(y).reshape(flat_x.shape)
    flat_load = np.asarray(load).reshape(-1, cfg.experts_held)
    k = cfg.experts_per_token
    for xi, yi, li in zip(flat_x, flat_y, flat_load):
        np.testing.assert_allclose(yi, _plain_moe(p, xi, cfg, cfg.experts_held),
                                   rtol=1e-4, atol=1e-5)
        expect = {"full": B * S * k, "none": 0}.get(routing)
        if expect is not None:
            assert li.sum() == expect


def _dense_moe(p, x, cfg):
    """The layer in float32 as one einsum over every held expert, each
    weighted by its gate weight (0 where not chosen)."""
    from repro.models.moe import route_sigmoid
    held = p["wo"].shape[0]
    w, experts = route_sigmoid(p, x, cfg)
    wh = jnp.sum(jnp.where(experts[..., None] == jnp.arange(held),
                           w[..., None], 0.0), axis=-2)
    h = (jax.nn.silu(jnp.einsum("bsd,edf->bsef", x, p["wi_gate"]))
         * jnp.einsum("bsd,edf->bsef", x, p["wi_up"]))
    y = jnp.einsum("bsef,efd->bsd", h * wh[..., None], p["wo"])
    sp = p["shared"]
    return y + (jax.nn.silu(x @ sp["wi_gate"]) * (x @ sp["wi_up"])) @ sp["wo"]


@pytest.mark.parametrize("routing", ROUTINGS)
def test_held_layer_input_gradient(routing):
    """``vmap(grad(...))`` over shards and clients: the gradient for the
    tokens, through the experts and through the gate weights, is that of
    the dense formulation."""
    cfg = _tiny()
    p = _routed(cfg, routing)
    x = jax.random.normal(jax.random.key(4), (2, 3, B, S, cfg.d_model))
    probe = jax.random.normal(jax.random.key(5), x.shape[2:])

    def loss(layer):
        return lambda x: jnp.sum(jnp.sin(layer(x) * probe))
    grouped = _vmapped(jax.grad(loss(lambda x: apply_moe_held(p, x, cfg)[0])), 2)(x)
    dense = _vmapped(jax.grad(loss(lambda x: _dense_moe(p, x, cfg))), 2)(x)
    np.testing.assert_allclose(np.asarray(grouped), np.asarray(dense),
                               rtol=1e-4, atol=1e-5)


def _rounded_experts(x, hidx, w, dy, wg, wu, wo):
    """The held experts' routed sum ``y`` and its input gradient ``dx`` for
    the cotangent ``dy``, in float32, expert by expert over every token
    (masked), from bfloat16 inputs rounded where a bfloat16 matmul output or
    operand is: the projections, ``h`` and the backward's gate and up
    gradients.  A token's pairs add up in float32."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    bf = lambda a: f32(a.astype(jnp.bfloat16))   # noqa: E731
    mm = functools.partial(jnp.matmul, precision="highest")
    x, dy, wg, wu, wo = map(f32, (x, dy, wg, wu, wo))
    y = dx = 0.0
    for e in range(wo.shape[0]):
        chosen = jnp.any(hidx == e, axis=1)[:, None]
        we = jnp.sum(jnp.where(hidx == e, w, 0.0), axis=1)[:, None]
        gate, up = bf(mm(x, wg[e])), bf(mm(x, wu[e]))
        sg = jax.nn.silu(gate)
        y = y + jnp.where(chosen, mm(bf(sg * up * we), wo[e]), 0.0)
        dh = bf(mm(dy, wo[e].T)) * we
        dgate = dh * up * jax.grad(lambda v: jnp.sum(jax.nn.silu(v)))(gate)
        dxe = mm(bf(dgate), wg[e].T) + mm(bf(dh * sg), wu[e].T)
        dx = dx + jnp.where(chosen, dxe, 0.0)
    return y, dx


@pytest.mark.parametrize("direction", ["forward", "input_gradient"])
@pytest.mark.parametrize("form", ["plain", "clients"])
def test_held_pairs_add_up_in_float32_under_bfloat16(form, direction):
    """bfloat16 tokens and weights: each token's held pairs, and in the
    backward their input gradients, add up in float32 and are rounded
    once, so the layer gives the correctly rounded float32 sum in nearly
    every element and is never a bfloat16 step off it.  Rows rounded to
    bfloat16 before the sum would miss both."""
    from repro.models.moe import route_sigmoid, routed_experts
    cfg = _tiny()
    p = jax.tree.map(lambda a: a.astype(jnp.bfloat16), _routed(cfg, "balanced"))
    held, k, d = cfg.experts_held, cfg.experts_per_token, cfg.d_model
    lead = {"plain": (), "clients": (3,)}[form]
    x = jax.random.normal(jax.random.key(6), lead + (B * S, d), jnp.bfloat16)
    dy = jax.random.normal(jax.random.key(7), x.shape, jnp.bfloat16)
    w, experts = route_sigmoid(p, x, cfg)
    hidx = jnp.where(experts < held, experts, held)
    wgu = jnp.concatenate([p["wi_gate"], p["wi_up"]], axis=2)

    def layer(x, hidx, w, dy):
        y, vjp = jax.vjp(
            lambda x: routed_experts(jax.nn.silu, x, hidx, w, wgu, p["wo"]), x)
        return y, vjp(dy)[0]
    got = _vmapped(layer, len(lead))(x, hidx, w, dy)
    ref = _vmapped(lambda *a: _rounded_experts(
        *a, p["wi_gate"], p["wi_up"], p["wo"]), len(lead))(x, hidx, w, dy)
    i = {"forward": 0, "input_gradient": 1}[direction]
    got, ref = np.asarray(got[i], np.float32), np.asarray(ref[i])
    assert got.dtype == np.float32 and int((hidx < held).sum()) > 0
    rounded = np.asarray(jnp.asarray(ref).astype(jnp.bfloat16), np.float32)
    step = np.spacing(np.abs(rounded).astype(jnp.bfloat16)).astype(np.float32)
    assert np.mean(got == rounded) >= 0.99
    assert np.all(np.abs(got - rounded) <= step)


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
    # seq 6: neither a client's 12 tokens a step nor a shard's 24 (the
    # expert layer pools its clients' tokens) is a width of the base
    xs = jax.ShapeDtypeStruct((2, 2, 4, 6), jnp.int32)
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


def test_stage_gauges_how_full_the_pair_buffer_ran():
    """With a correction bias that holds every choice, each step's held
    pairs are its tokens times k: the stage's ``fill`` gauge reads that
    over the largest pair buffer's rows, and the load sums to it."""
    from repro.fl.experiment import ScenarioConfig, build_session
    from repro.models.moe import pair_buffer_sizes
    from repro.telemetry import configure, get_tracer
    cfg = ScenarioConfig(task="generation", model="moonlight", num_clients=8,
                         clients_per_round=4, num_shards=2, local_epochs=1,
                         global_rounds=2, samples_per_client=4, seq_len=16,
                         test_n=20, local_batch=2, store="coded",
                         num_stages=1)
    session, _ = build_session(cfg)
    model = session.sim.cfg
    held, k = model.experts_held, model.experts_per_token
    ffn = session.sim.base["stack"]["p0"]["ffn"]
    bias = np.zeros(ffn["router_bias"].shape, np.float32)
    bias[..., :held] = 10.0
    ffn["router_bias"] = jnp.asarray(bias, ffn["router_bias"].dtype)
    configure(enabled=True)
    try:
        session.run(1)
        gauges = get_tracer().metrics.snapshot()["gauges"]
    finally:
        configure(enabled=False)
    tokens = 2 * 2 * 2 * 16              # shards x clients x batch x seq
    steps = 2 * 2                        # rounds x batches
    rows = pair_buffer_sizes(tokens, k, held)[-1]
    assert gauges["moe.expert_tokens{stat=fill}"] == tokens * k / rows
    assert float(session.records[-1].expert_load.sum()) == steps * tokens * k


def test_scenario_stage_unlearning_and_a_served_request():
    """The tiny variant through ``run_scenario``'s path on the stage program
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
                         num_stages=1, schedule=schedule)
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
