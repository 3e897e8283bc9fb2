"""Mixture-of-Experts FFN: top-k router + capacity-based one-hot dispatch.

TPU-native design: dispatch/combine are einsums against one-hot tensors so the
whole layer is MXU matmuls; experts live on the ``expert`` logical axis
(sharded over ``model``), which makes the dispatch an explicit all-to-all in
the lowered HLO — exactly the collective the roofline wants to see.

Tokens are routed within fixed-size groups (``group_size``) so dispatch cost
is O(S * group * k) rather than O(S^2 * k).

The DeepSeek-V3 layer (``moe_gate="sigmoid"``, ``apply_moe_held``) is told
which experts it holds: routing runs over all ``num_experts``, and only the
held experts' part of the result is computed, for every token routed to them
(no capacity, no token dropped), plus the shared experts.  Its held experts
run on the routed (token, expert) pairs alone: the pairs of every client and
shard of a vmapped step are laid out expert by expert in one buffer, the
smaller of two sizes that holds them (the larger has room for every token's
held choices), and each expert weight is one grouped matmul over them
(megablox ``gmm``, whose grid visits only the tiles that hold pairs).
The expert weights are frozen (a base under LoRA adapters) and take no
gradient.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import gmm
from jax.extend.core import Primitive
from jax.interpreters import batching, mlir

from repro.configs.base import ModelConfig
from repro.kernels import on_tpu
from repro.models.layers import ACTS
from repro.models.params import ParamFactory


# scale of the drawn correction bias of the sigmoid gate (a trained
# checkpoint's is learnt; it only moves which experts are selected)
ROUTER_BIAS_STD = 0.05

# tiles of the grouped matmul: rows of the pair buffer, and the widest tile
# of a contracted or output width (v5e's scoped VMEM holds 512 x 1408)
PAIR_TILE = 512
WIDTH_TILE = 1408
# the pair buffers a layer can run: the whole and a quarter; the smaller
# that holds the step's held pairs runs, so the passes over the buffer follow
# the routed pairs and not the worst case.  Each further size adds a copy of
# the layer to the program, and set-up time with it
BUFFER_PARTS = (1, 4)


def init_moe(fac: ParamFactory, cfg: ModelConfig):
    d, f = cfg.d_model, cfg.moe_d_ff
    e = cfg.experts_held or cfg.num_experts
    with fac.scope("moe"):
        p = {
            "router": fac.param("router", (d, cfg.num_experts),
                                ("embed", "expert_router")),
            "wi_gate": fac.param("wi_gate", (e, d, f), ("expert", "embed", "mlp"),
                                 fan_in=d),
            "wi_up": fac.param("wi_up", (e, d, f), ("expert", "embed", "mlp"),
                               fan_in=d),
            "wo": fac.param("wo", (e, f, d), ("expert", "mlp", "embed"),
                            fan_in=f),
        }
        if cfg.moe_gate == "sigmoid":
            p["router_bias"] = fac.param("router_bias", (cfg.num_experts,),
                                         ("expert_router",),
                                         scale=ROUTER_BIAS_STD, fan_in=1)
        if cfg.moe_shared_d_ff:
            fs = cfg.moe_shared_d_ff
            p["shared"] = {
                "wi_gate": fac.param("shared.wi_gate", (d, fs), ("embed", "mlp")),
                "wi_up": fac.param("shared.wi_up", (d, fs), ("embed", "mlp")),
                "wo": fac.param("shared.wo", (fs, d), ("mlp", "embed"))}
        return p


def route_sigmoid(p, x, cfg: ModelConfig):
    """DeepSeek-V3's noaux_tc gate with one group: ``(weights, experts)``,
    each ``(..., k)``.  Scores are ``sigmoid(x @ router)`` in float32; the
    experts are the top k of scores plus the correction bias; the weights
    are their scores, normalised to sum to one, times ``moe_routed_scale``."""
    logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32),
                        p["router"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, experts = jax.lax.top_k(
        scores + p["router_bias"].astype(jnp.float32), cfg.experts_per_token)
    w = jnp.take_along_axis(scores, experts, axis=-1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return w * cfg.moe_routed_scale, experts


def pair_buffer_sizes(t: int, k: int, held: int) -> list:
    """The rows of the pair buffers a layer of ``t`` tokens can run,
    smallest first, each whole tiles of the grouped matmul: the last holds
    every token's ``min(k, held)`` held choices, the others
    ``BUFFER_PARTS`` of it."""
    whole = t * min(k, held)
    return sorted({-(-whole // (part * PAIR_TILE)) * PAIR_TILE
                   for part in BUFFER_PARTS})


def _pair_layout(hidx, held: int):
    """The (token, choice) pairs expert by expert.  ``hidx`` (T, k) names
    each pair's held expert, ``held`` where its expert is not held.
    Returns ``order`` (T*k,), the pairs sorted by held expert (those not
    held last), so that a pair buffer's rows hold ``order``'s first pairs;
    ``row`` (T, k), each pair's place in ``order``; and ``sizes`` (held,),
    the groups' sizes."""
    t, k = hidx.shape
    flat = hidx.reshape(-1)
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    # the inverse of ``order`` by a second sort: on the TPU a sort of the
    # pairs costs less than a scatter of them
    row = jnp.argsort(order).astype(jnp.int32).reshape(t, k)
    sizes = jnp.sum(flat[:, None] == jnp.arange(held), axis=0, dtype=jnp.int32)
    return order, row, sizes


def _tile(width: int) -> int:
    """The widest tile of ``width`` up to ``WIDTH_TILE``: the width itself,
    or its largest divisor that is a multiple of the 128 lanes."""
    if width <= WIDTH_TILE:
        return width
    return max(t for t in range(128, WIDTH_TILE + 1, 128) if width % t == 0)


def _tiling(m: int, k: int, n: int):
    """Tiles of the grouped matmul: ``PAIR_TILE`` rows of the pair buffer
    (``m`` is a multiple), and the contracted and the output width."""
    return (PAIR_TILE, _tile(k), _tile(n))


def _grouped(lhs, rhs, sizes, transpose=False, dtype=None):
    """Rows of group ``e`` of ``lhs`` times ``rhs[e]`` (or its transpose),
    accumulated in float32 and given in ``dtype`` (``lhs``'s by default).
    Only the tiles that hold a group's rows run; rows past the groups'
    total are left unwritten."""
    return gmm(lhs, rhs, sizes, dtype or lhs.dtype, tiling=_tiling,
               transpose_rhs=transpose, interpret=not on_tpu())


def _combine(buf, row, held_pair):
    """(T, d): the sum over each token's held pairs of their rows of the
    float32 ``buf``, one choice at a time so that no (T, k, d) gather is
    made; pairs not held add nothing (masked, never multiplied)."""
    out = 0.0
    for j in range(row.shape[1]):
        picked = buf[jnp.where(held_pair[:, j], row[:, j], 0)]
        out = out + jnp.where(held_pair[:, j, None], picked, 0.0)
    return out


def _smallest_buffer(fn, x, hidx, *args):
    """``fn(pair, row, sizes, x, hidx, *args)`` with ``pair`` the pairs of
    the smallest pair buffer that holds the held pairs, one a row (the rows
    past the groups' total hold pairs that are not held): exact and dropless
    whatever the routing, as the largest holds every token's held choices.
    The layout is made once, outside the buffers' branches."""
    held = args[-1].shape[0]
    order, row, sizes = _pair_layout(hidx, held)
    buffers = pair_buffer_sizes(x.shape[0], hidx.shape[1], held)
    order = jnp.pad(order, (0, max(buffers[-1] - order.shape[0], 0)))

    def first(rows):
        return lambda order, *a: fn(order[:rows], *a)
    branch = jnp.searchsorted(jnp.asarray(buffers), jnp.sum(sizes))
    return jax.lax.switch(branch, [first(r) for r in buffers],
                          order, row, sizes, x, hidx, *args)


def _gate_up(pair, sizes, x, w, wgu, k: int):
    """Each buffer row's token and gate weight, and the gate and up
    projections of the rows, (rows, f) each in float32."""
    tok = pair // k
    gu = _grouped(x[tok], wgu, sizes)
    f = gu.shape[1] // 2
    return (tok, w.reshape(-1)[pair][:, None],
            gu[:, :f].astype(jnp.float32), gu[:, f:].astype(jnp.float32))


def _experts_forward(act, pair, row, sizes, x, hidx, w, wgu, wo):
    """The held experts' part of the routed sum, (T, d): one grouped matmul
    over the held (token, expert) pairs for the gate and up projections
    together (``wgu``, (held, d, 2f)) and one for ``wo``, whose rows stay in
    float32 until a token's pairs are summed."""
    _, wr, gate, up = _gate_up(pair, sizes, x, w, wgu, hidx.shape[1])
    h = (act(gate) * up * wr).astype(x.dtype)
    out = _grouped(h, wo, sizes, dtype=jnp.float32)
    return _combine(out, row, hidx < wo.shape[0]).astype(x.dtype)


def _experts_backward(act, pair, row, sizes, x, hidx, w, dy, wgu, wo):
    """(dx, dw): the gradients of ``_experts_forward`` for the tokens and
    the gate weights; the weights are frozen and take none."""
    tok, wr, gate, up = _gate_up(pair, sizes, x, w, wgu, hidx.shape[1])
    sg, act_vjp = jax.vjp(act, gate)
    g = _grouped(dy[tok], wo, sizes, True).astype(jnp.float32)
    dwr = jnp.sum(sg * up * g, axis=1)
    dh = g * wr
    (dgate,) = act_vjp(dh * up)
    dgu = jnp.concatenate([dgate, dh * sg], axis=1).astype(x.dtype)
    dxr = _grouped(dgu, wgu, sizes, True, jnp.float32)
    held_pair = hidx < wo.shape[0]
    dx = _combine(dxr, row, held_pair).astype(x.dtype)
    dw = jnp.where(held_pair, dwr[jnp.where(held_pair, row, 0)], 0.0)
    return dx, dw.astype(w.dtype)


def _pooled_call(*args, act, backward: bool):
    """The held experts' forward ``(y,)`` or backward ``(dx, dw)`` over token
    arrays (T, ...) and the expert weights."""
    fn = _experts_backward if backward else _experts_forward
    out = _smallest_buffer(functools.partial(fn, act), *args)
    return out if backward else (out,)


def _pooled_shapes(x, hidx, w, *rest, act, backward: bool):
    """The outputs' shapes: ``y`` or ``dx`` like ``x``, ``dw`` like ``w``."""
    return [x, w] if backward else [x]


def _pooled_batch(args, dims, *, act, backward: bool):
    """Folds the batch axis into the token axis: every member of a vmap
    (clients, shards) shares one call.  The weights are never batched."""
    tokens = 4 if backward else 3
    if any(d is not None for d in dims[tokens:]):
        raise NotImplementedError(
            "the held experts' weights are shared by the whole batch")
    size = next(a.shape[d] for a, d in zip(args, dims) if d is not None)
    toks = [jnp.moveaxis(a, d, 0) if d is not None
            else jnp.broadcast_to(a, (size,) + a.shape)
            for a, d in zip(args[:tokens], dims)]
    out = pooled_experts_p.bind(
        *(a.reshape((-1,) + a.shape[2:]) for a in toks), *args[tokens:],
        act=act, backward=backward)
    return [o.reshape((size, -1) + o.shape[1:]) for o in out], [0] * len(out)


# The pooled forward and backward are a primitive of their own, so that the
# layer is traced once, when the program is lowered; a ``custom_vmap`` would
# trace it again at each level of vmap, which lengthens set-up.
pooled_experts_p = Primitive("pooled_experts")
pooled_experts_p.multiple_results = True
pooled_experts_p.def_impl(_pooled_call)
pooled_experts_p.def_abstract_eval(_pooled_shapes)
mlir.register_lowering(pooled_experts_p,
                       mlir.lower_fun(_pooled_call, multiple_results=True))
batching.primitive_batchers[pooled_experts_p] = _pooled_batch


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def routed_experts(act, x, hidx, w, wgu, wo):
    """x (T, d), hidx (T, k) the held expert of each choice (``held`` where
    not held), w (T, k) its gate weight, ``wgu`` the gate and up weights
    side by side (held, d, 2f) -> (T, d): the sum over each token's held
    choices of gate weight times the expert's output.  Only the held pairs
    are computed, all tokens of a vmapped batch in one grouped matmul a
    weight; the expert weights take no gradient."""
    (y,) = pooled_experts_p.bind(x, hidx, w, wgu, wo, act=act, backward=False)
    return y


def _routed_fwd(act, x, hidx, w, wgu, wo):
    return routed_experts(act, x, hidx, w, wgu, wo), (x, hidx, w, wgu, wo)


def _routed_bwd(act, res, dy):
    x, hidx, w, wgu, wo = res
    with jax.named_scope("moe.experts"):
        dx, dw = pooled_experts_p.bind(x, hidx, w, dy, wgu, wo, act=act,
                                       backward=True)
    return dx, None, dw, None, None


routed_experts.defvjp(_routed_fwd, _routed_bwd)


def apply_moe_held(p, x, cfg: ModelConfig, first_expert: int = 0):
    """x: (B, S, d) -> (y, load): the held experts' part of the routed
    experts' sum plus the shared experts, and ``load`` (held,), the
    tokens routed to each held expert.  Experts ``first_expert + j`` are
    held; each runs on the tokens routed to it and no other, with no
    capacity, so no token is dropped whatever the routing."""
    act = ACTS[cfg.act]
    held = p["wo"].shape[0]
    b, s, d = x.shape
    with jax.named_scope("moe.route"):
        w, experts = route_sigmoid(p, x, cfg)
        local = experts - first_expert
        hidx = jnp.where((local >= 0) & (local < held), local, held)
        chosen = hidx[..., None] == jnp.arange(held)
        load = chosen.sum(axis=tuple(range(chosen.ndim - 1))).astype(jnp.float32)
    with jax.named_scope("moe.experts"):
        y = jnp.zeros_like(x)
        if held:
            k = cfg.experts_per_token
            wgu = jnp.concatenate([p["wi_gate"], p["wi_up"]], axis=2)
            y = routed_experts(
                act, x.reshape(b * s, d), hidx.reshape(b * s, k),
                w.reshape(b * s, k), wgu.astype(x.dtype),
                p["wo"].astype(x.dtype)).reshape(x.shape)
    if "shared" in p:
        with jax.named_scope("moe.shared"):
            sp = p["shared"]
            hs = act(x @ sp["wi_gate"].astype(x.dtype)) * (x @ sp["wi_up"].astype(x.dtype))
            y = y + hs @ sp["wo"].astype(x.dtype)
    return y, load


def _route(p, xg, cfg: ModelConfig, cap: int):
    """Shared router: returns (gate_vals, expert_idx, pos_in_expert, keep, aux).

    pos_in_expert: (N,T,k) slot of each (token, k-choice) in its expert's
    capacity buffer (token-major priority, overflow dropped via ``keep``).
    """
    e, k = cfg.num_experts, cfg.experts_per_token
    n, g, _ = xg.shape
    logits = (xg.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)                # (N,T,k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)
    onehot = jax.nn.one_hot(expert_idx, e, dtype=jnp.float32)      # (N,T,k,E)
    flat = onehot.reshape(n, g * k, e)                             # token-major
    pos = (jnp.cumsum(flat, axis=1) - 1.0).reshape(n, g, k, e)
    pos_in_expert = jnp.sum(pos * onehot, axis=-1).astype(jnp.int32)  # (N,T,k)
    keep = pos_in_expert < cap
    me = probs.mean(axis=(0, 1))
    ce = onehot.sum(axis=2).mean(axis=(0, 1)) / k
    aux = cfg.router_aux_weight * e * jnp.sum(me * ce)
    return gate_vals, expert_idx, pos_in_expert, keep, onehot, aux


def _moe_einsum(p, xg, cfg: ModelConfig, cap: int, ctx=None):
    """Paper-baseline one-hot dispatch: materialises (N,T,E,C) dispatch/
    combine tensors. §Perf-optimized from the naive form: the k dim is
    contracted INSIDE the einsum (never materialising (N,T,k,E,C)) and the
    one-hots are compute-dtype, not f32."""
    e, k = cfg.num_experts, cfg.experts_per_token
    act = ACTS[cfg.act]
    cdt = jnp.dtype(cfg.compute_dtype)
    gate_vals, expert_idx, pos, keep, onehot, aux = _route(p, xg, cfg, cap)
    pos_oh = jnp.where(keep[..., None],
                       jax.nn.one_hot(pos, cap, dtype=cdt), 0)     # (N,T,k,C)
    oh = onehot.astype(cdt)
    dispatch_t = jnp.einsum("ntke,ntkc->ntec", oh, pos_oh)         # (N,T,E,C)
    combine_t = jnp.einsum("ntke,ntkc,ntk->ntec", oh, pos_oh,
                           gate_vals.astype(cdt))
    expert_in = jnp.einsum("ntec,ntd->ecnd", dispatch_t,
                           xg.astype(cdt))                         # (E,C,N,d)
    if ctx is not None:
        expert_in = ctx.constrain(expert_in, ("expert", None, "moe_group", None))
    h = act(jnp.einsum("ecnd,edf->ecnf", expert_in, p["wi_gate"])) * \
        jnp.einsum("ecnd,edf->ecnf", expert_in, p["wi_up"])
    expert_out = jnp.einsum("ecnf,efd->ecnd", h, p["wo"])          # (E,C,N,d)
    if ctx is not None:
        expert_out = ctx.constrain(expert_out, ("expert", None, "moe_group", None))
    yg = jnp.einsum("ntec,ecnd->ntd", combine_t, expert_out)
    return yg, aux


def _moe_gather(p, xg, cfg: ModelConfig, cap: int, ctx=None):
    """Index-based dispatch (beyond-paper §Perf optimization): the one-hot
    tensors are replaced by O(T*k) integer indices + gathers, so dispatch HBM
    traffic is ~(k/E*cf) of the einsum path's. Routing identical to _route.

    Gathers stay LOCAL to each token group (indices < T), so sharding over
    the batch/group dim is preserved; the expert dim materialises sharded over
    ``model`` via the expert-weight einsum (all-to-all in HLO, as expected).
    """
    e, k = cfg.num_experts, cfg.experts_per_token
    act = ACTS[cfg.act]
    n, g, d = xg.shape
    cdt = jnp.dtype(cfg.compute_dtype)
    gate_vals, expert_idx, pos, keep, _onehot, aux = _route(p, xg, cfg, cap)

    # slot id of each (token, k) in the flattened (E*C) buffer; dropped -> E*C
    slot = jnp.where(keep, expert_idx * cap + pos, e * cap)        # (N,T,k)
    # token id feeding each buffer slot: scatter token ids into (N, E*C+1)
    tok_ids = jnp.broadcast_to(jnp.arange(g, dtype=jnp.int32)[None, :, None],
                               slot.shape)                          # (N,T,k)
    src = jnp.full((n, e * cap + 1), g, jnp.int32)                 # g = pad row
    src = src.at[jnp.arange(n)[:, None, None], slot].set(tok_ids, mode="drop")
    buf_tok = src[:, : e * cap]                                    # (N, E*C)
    xg_pad = jnp.concatenate([xg.astype(cdt),
                              jnp.zeros((n, 1, d), cdt)], axis=1)  # pad row
    expert_in = jnp.take_along_axis(xg_pad, buf_tok[..., None],
                                    axis=1)                        # (N,E*C,d)
    expert_in = expert_in.reshape(n, e, cap, d).transpose(1, 2, 0, 3)  # (E,C,N,d)
    if ctx is not None:
        expert_in = ctx.constrain(expert_in, ("expert", None, "moe_group", None))

    h = act(jnp.einsum("ecnd,edf->ecnf", expert_in, p["wi_gate"])) * \
        jnp.einsum("ecnd,edf->ecnf", expert_in, p["wi_up"])
    expert_out = jnp.einsum("ecnf,efd->ecnd", h, p["wo"])          # (E,C,N,d)
    if ctx is not None:
        expert_out = ctx.constrain(expert_out, ("expert", None, "moe_group", None))

    # combine: gather each (token, k)'s slot output, weight by gate
    flat_out = expert_out.transpose(2, 0, 1, 3).reshape(n, e * cap, d)
    if ctx is not None:
        flat_out = ctx.constrain(flat_out, ("moe_group", None, None))
    flat_out = jnp.concatenate([flat_out, jnp.zeros((n, 1, d), flat_out.dtype)],
                               axis=1)
    slot_c = jnp.minimum(slot, e * cap)                            # dropped -> 0 row
    picked = jnp.take_along_axis(flat_out,
                                 slot_c.reshape(n, g * k)[..., None], axis=1)
    picked = picked.reshape(n, g, k, d)
    yg = jnp.einsum("ntk,ntkd->ntd", gate_vals.astype(cdt) *
                    keep.astype(cdt), picked)
    return yg, aux


def apply_moe(p, x, cfg: ModelConfig, *, group_size: int = 512,
              capacity_factor: float = None, ctx=None):
    """x: (B, S, d) -> (y, aux_loss)."""
    capacity_factor = capacity_factor or cfg.moe_capacity_factor
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    g = min(group_size, s)
    pad = (-s) % g
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    ng = (s + pad) // g
    xg = x.reshape(b * ng, g, d)                                   # (N, T, d)
    if ctx is not None:
        xg = ctx.constrain(xg, ("moe_group", None, None))
    cap = max(int(g * k / e * capacity_factor), 4)
    impl = _moe_gather if cfg.moe_impl == "gather" else _moe_einsum
    yg, aux = impl(p, xg, cfg, cap, ctx=ctx)
    y = yg.reshape(b, s + pad, d)[:, :s].astype(x.dtype)
    return y, aux
