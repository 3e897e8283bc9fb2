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
(no capacity, no token dropped), plus the shared experts.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import ACTS
from repro.models.params import ParamFactory


# scale of the drawn correction bias of the sigmoid gate (a trained
# checkpoint's is learnt; it only moves which experts are selected)
ROUTER_BIAS_STD = 0.05


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


def held_weights(w, experts, first: int, held: int):
    """``(..., held)`` weight of each held expert ``first + j`` for each
    token: its gate weight where chosen, else 0."""
    ids = first + jnp.arange(held)
    return jnp.sum(jnp.where(experts[..., None] == ids, w[..., None], 0.0),
                   axis=-2)


def apply_moe_held(p, x, cfg: ModelConfig, first_expert: int = 0):
    """x: (B, S, d) -> (y, load): the held experts' part of the routed
    experts' sum plus the shared experts, and ``load`` (held,), the
    tokens routed to each held expert.  Every held expert runs on every
    token, weighted by its gate weight (0 where not chosen), so no token is
    dropped whatever the routing; experts ``first_expert + j`` are held."""
    act = ACTS[cfg.act]
    held = p["wo"].shape[0]
    with jax.named_scope("moe.route"):
        w, experts = route_sigmoid(p, x, cfg)
        wh = held_weights(w, experts, first_expert, held)
        chosen = (experts[..., None] == first_expert + jnp.arange(held))
        load = chosen.sum(axis=tuple(range(chosen.ndim - 1))).astype(jnp.float32)
    with jax.named_scope("moe.experts"):
        h = act(jnp.einsum("bsd,edf->bsef", x, p["wi_gate"].astype(x.dtype))) * \
            jnp.einsum("bsd,edf->bsef", x, p["wi_up"].astype(x.dtype))
        y = jnp.einsum("bsef,efd->bsd", h * wh[..., None].astype(x.dtype),
                       p["wo"].astype(x.dtype))
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
