"""Block stacks: decoder LM (dense / MoE / hybrid / ssm), encoder-decoder
(audio), vision-prefix LM (vlm).

Layers are grouped into *superblocks* — one repetition of ``cfg.layer_pattern``
— and the full repetitions are executed under a single ``lax.scan`` over
parameter stacks (remainder layers unrolled). This keeps HLO size ~constant in
depth, which matters for 62-72 layer models compiled on the CPU dry-run host.

Three modes:
  train    -> logits over the full sequence (plus MoE aux loss and the
              tokens routed to each held expert)
  prefill  -> logits + a populated decode cache
  decode   -> one-token step against the cache (``serve_step``'s body)
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import mamba as mb
from repro.models import mla
from repro.models import rwkv6 as rw
from repro.models.layers import (apply_embed, apply_mlp, apply_norm,
                                 apply_unembed, init_embed, init_mlp, init_norm)
from repro.models.moe import apply_moe, apply_moe_held, init_moe
from repro.models.params import ParamFactory


# ---------------------------------------------------------------------------
# Sharding context (activation constraints)
# ---------------------------------------------------------------------------

class ShardCtx:
    """Applies with_sharding_constraint from logical activation axes.

    ``rules`` maps logical axis -> ordered mesh-axis candidates; divisibility
    is checked per-dim (same policy as params.spec_for). mesh=None => no-op.
    """

    def __init__(self, mesh=None, rules: Optional[Dict[str, tuple]] = None):
        self.mesh = mesh
        self.rules = rules or {}

    def constrain(self, x, axes: Tuple[Optional[str], ...]):
        if self.mesh is None or x is None:
            return x
        from jax.sharding import NamedSharding
        from repro.models.params import spec_for
        spec = spec_for(tuple(x.shape), axes, self.rules, self.mesh)
        return jax.lax.with_sharding_constraint(x, NamedSharding(self.mesh, spec))


NULL_CTX = ShardCtx()


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def pattern_info(cfg: ModelConfig) -> Tuple[int, int, int]:
    """(pattern length, full repetitions, remainder) of the layers after
    the leading dense ones (``first_dense_layers``, unrolled first)."""
    plen = len(cfg.layer_pattern)
    n_full = cfg.layers_in_stack // plen
    rem = cfg.layers_in_stack % plen
    if cfg.num_experts and n_full > 1:
        assert plen % cfg.moe_every == 0, (
            "layer_pattern length must be a multiple of moe_every so the "
            "MoE placement is identical across scanned superblocks")
    return plen, n_full, rem


class _Stacked(ParamFactory):
    """Wraps a factory, prepending a (n,) 'layers' dim to every param."""

    def __init__(self, fac: ParamFactory, n: int):
        self.fac, self.n = fac, n
        self._path = fac._path

    def param(self, name, shape, axes, init="normal", scale=1.0, in_dims=1,
              fan_in=None):
        if fan_in is None and init == "normal":
            fan_in = (int(np.prod(shape[:in_dims])) if len(shape) > 1
                      else max(shape[-1], 1))
        return self.fac.param(name, (self.n,) + tuple(shape),
                              ("layers",) + tuple(axes), init=init, scale=scale,
                              fan_in=fan_in)

    def scope(self, name):
        return self.fac.scope(name)


def _init_block(fac: ParamFactory, cfg: ModelConfig, kind: str, pat_idx: int,
                cross: bool = False, dense: bool = False):
    p: Dict[str, Any] = {}
    if kind == "mla":
        p["ln1"] = init_norm(fac, cfg, "ln1")
        p["attn"] = mla.init_mla(fac, cfg)
        p["ln2"] = init_norm(fac, cfg, "ln2")
        p["ffn"] = (init_moe(fac, cfg) if cfg.ffn_is_moe(pat_idx) and not dense
                    else init_mlp(fac, cfg))
    elif kind in ("global", "local"):
        p["ln1"] = init_norm(fac, cfg, "ln1")
        p["attn"] = init_attention_wrap(fac, cfg)
        if cross:
            p["lnx"] = init_norm(fac, cfg, "lnx")
            p["xattn"] = attn.init_attention(fac, cfg, cross=True)
        p["ln2"] = init_norm(fac, cfg, "ln2")
        p["ffn"] = (init_moe(fac, cfg) if cfg.ffn_is_moe(pat_idx) else init_mlp(fac, cfg))
    elif kind == "mamba":
        p["ln1"] = init_norm(fac, cfg, "ln1")
        p["mamba"] = mb.init_mamba(fac, cfg)
        p["ln2"] = init_norm(fac, cfg, "ln2")
        p["ffn"] = (init_moe(fac, cfg) if cfg.ffn_is_moe(pat_idx) else init_mlp(fac, cfg))
    elif kind == "rwkv":
        p["ln1"] = init_norm(fac, cfg, "ln1")
        p["ln2"] = init_norm(fac, cfg, "ln2")
        p["rwkv"] = rw.init_rwkv(fac, cfg)
    else:
        raise ValueError(kind)
    return p


def init_attention_wrap(fac, cfg):
    return attn.init_attention(fac, cfg)


def init_lm(fac: ParamFactory, cfg: ModelConfig):
    """Full parameter tree for any LM family."""
    plen, n_full, rem = pattern_info(cfg)
    cross = cfg.family == "audio"
    params: Dict[str, Any] = {"embed": init_embed(fac, cfg)}
    if cfg.first_dense_layers:
        params["lead"] = {}
        for j in range(cfg.first_dense_layers):
            with fac.scope(f"lead{j}"):
                params["lead"][f"l{j}"] = _init_block(
                    fac, cfg, cfg.layer_pattern[0], 0, dense=True)
    if cfg.frontend:
        with fac.scope("frontend_proj"):
            params["frontend_proj"] = fac.param(
                "w", (cfg.d_model, cfg.d_model), ("embed", "mlp"))
    stack: Dict[str, Any] = {}
    if n_full:
        sfac = _Stacked(fac, n_full)
        for pidx, kind in enumerate(cfg.layer_pattern):
            with fac.scope(f"stack_p{pidx}"):
                stack[f"p{pidx}"] = _init_block(sfac, cfg, kind, pidx, cross)
    params["stack"] = stack
    remp = {}
    for j in range(rem):
        pidx = n_full * plen + j
        kind = cfg.layer_kinds[pidx]
        with fac.scope(f"rem{j}"):
            remp[f"r{j}"] = _init_block(fac, cfg, kind, j % plen, cross)
    params["rem"] = remp
    if cfg.family == "audio":
        enc = {}
        for j in range(cfg.encoder_layers):
            with fac.scope(f"enc{j}"):
                enc[f"e{j}"] = {
                    "ln1": init_norm(fac, cfg, "ln1"),
                    "attn": attn.init_attention(fac, cfg),
                    "ln2": init_norm(fac, cfg, "ln2"),
                    "ffn": init_mlp(fac, cfg),
                }
        params["encoder"] = enc
        params["enc_ln"] = init_norm(fac, cfg, "enc_ln")
    params["final_ln"] = init_norm(fac, cfg, "final_ln")
    return params


def init_lora(fac: ParamFactory, cfg: ModelConfig):
    """The LoRA adapter tree: the base tree's ``lead`` and ``stack`` blocks,
    each MLA block's ``attn`` holding only its ``lora`` entry (``merge_lora``
    puts it in place)."""
    plen, n_full, rem = pattern_info(cfg)
    if rem or any(k != "mla" for k in cfg.layer_pattern):
        raise ValueError("LoRA adapters are defined for MLA stacks without "
                         "remainder layers")
    out: Dict[str, Any] = {}
    if cfg.first_dense_layers:
        out["lead"] = {}
        for j in range(cfg.first_dense_layers):
            with fac.scope(f"lead{j}"):
                out["lead"][f"l{j}"] = mla.init_mla_lora(fac, cfg)
    sfac = _Stacked(fac, n_full)
    out["stack"] = {}
    for pidx in range(plen):
        with fac.scope(f"stack_p{pidx}"):
            out["stack"][f"p{pidx}"] = mla.init_mla_lora(sfac, cfg)
    return out


def merge_lora(base, adapters):
    """The base tree with the adapter tree's entries added in place."""
    if not isinstance(adapters, dict):
        return adapters
    out = dict(base)
    for k, v in adapters.items():
        out[k] = merge_lora(base.get(k, {}), v)
    return out


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------

def _attn_cache_len(cfg: ModelConfig, kind: str, cache_len: int) -> int:
    if kind == "local" and cfg.sliding_window:
        return min(cfg.sliding_window, cache_len)
    return cache_len


def init_layer_cache(cfg: ModelConfig, kind: str, batch: int, cache_len: int,
                     dtype, lead: Tuple[int, ...] = ()):
    kvh, hd = cfg.num_kv_heads, cfg.head_dim
    if kind in ("global", "local"):
        s = _attn_cache_len(cfg, kind, cache_len)
        return {
            "k": jnp.zeros(lead + (batch, s, kvh, hd), dtype),
            "v": jnp.zeros(lead + (batch, s, kvh, hd), dtype),
        }
    if kind == "mamba":
        di = mb.d_inner(cfg)
        return {
            "conv": jnp.zeros(lead + (batch, cfg.ssm_conv_width - 1, di), dtype),
            "h": jnp.zeros(lead + (batch, di, cfg.ssm_state_dim), jnp.float32),
        }
    if kind == "rwkv":
        h, n = rw.rwkv_heads(cfg)
        return {
            "tm_prev": jnp.zeros(lead + (batch, cfg.d_model), dtype),
            "h": jnp.zeros(lead + (batch, h, n, n), jnp.float32),
            "cm_prev": jnp.zeros(lead + (batch, cfg.d_model), dtype),
        }
    raise ValueError(kind)


def init_cache(cfg: ModelConfig, batch: int, cache_len: int, dtype=jnp.bfloat16,
               enc_len: int = 0):
    """Decode cache for the whole stack."""
    plen, n_full, rem = pattern_info(cfg)
    cache: Dict[str, Any] = {"pos": jnp.zeros((), jnp.int32)}
    stack = {}
    for pidx, kind in enumerate(cfg.layer_pattern):
        if n_full:
            lc = init_layer_cache(cfg, kind, batch, cache_len, dtype, lead=(n_full,))
            if cfg.family == "audio":
                lc["xk"] = jnp.zeros((n_full, batch, enc_len, cfg.num_kv_heads,
                                      cfg.head_dim), dtype)
                lc["xv"] = jnp.zeros_like(lc["xk"])
            stack[f"p{pidx}"] = lc
    cache["stack"] = stack
    remc = {}
    for j in range(rem):
        kind = cfg.layer_kinds[n_full * plen + j]
        lc = init_layer_cache(cfg, kind, batch, cache_len, dtype)
        if cfg.family == "audio":
            lc["xk"] = jnp.zeros((batch, enc_len, cfg.num_kv_heads, cfg.head_dim), dtype)
            lc["xv"] = jnp.zeros_like(lc["xk"])
        remc[f"r{j}"] = lc
    cache["rem"] = remc
    return cache


def _ring_positions(cache_slots: int, pos, window: int):
    """Original position of each ring-buffer slot given current length ``pos``.

    Slot i holds the latest position p < pos with p % slots == i. -1 if empty
    or expired (p <= pos - window).
    """
    idx = jnp.arange(cache_slots, dtype=jnp.int32)
    last = pos - 1 - ((pos - 1 - idx) % cache_slots)
    valid = (last >= 0) & (last >= pos - window) & (pos > 0)
    return jnp.where(valid, last, -1)


def _full_positions(cache_slots: int, pos):
    idx = jnp.arange(cache_slots, dtype=jnp.int32)
    return jnp.where(idx < pos, idx, -1)


# ---------------------------------------------------------------------------
# Block application
# ---------------------------------------------------------------------------

def _apply_ffn(p, x, cfg: ModelConfig, is_moe: bool, ctx: ShardCtx):
    """(y, aux loss, load): ``load`` (held,) counts the tokens routed to
    each held expert of a sigmoid-gated layer, else None."""
    load = None
    if is_moe and cfg.moe_gate == "sigmoid":
        y, load = apply_moe_held(p, x, cfg)
        aux = jnp.zeros((), jnp.float32)
    elif is_moe:
        y, aux = apply_moe(p, x, cfg, ctx=ctx)
    else:
        y, aux = apply_mlp(p, x, cfg), jnp.zeros((), jnp.float32)
    return y, jnp.asarray(aux, jnp.float32), load


def apply_block_train(p, x, cfg: ModelConfig, kind: str, pat_idx: int,
                      ctx: ShardCtx, memory=None, positions=None,
                      want_kv: bool = False, dense: bool = False):
    """Train/prefill. Returns (x, aux, kv|None, load|None); ``dense`` forces
    the dense FFN (a leading dense layer)."""
    kv = None
    if positions is None:
        positions = jnp.arange(x.shape[1], dtype=jnp.int32)[None]
    is_moe = cfg.ffn_is_moe(pat_idx) and not dense
    if kind == "mla":
        if want_kv:
            raise ValueError("MLA layers have no decode cache here")
        x = x + mla.apply_mla(p["attn"], apply_norm(p["ln1"], x, cfg), cfg,
                              positions)
        h2 = apply_norm(p["ln2"], x, cfg)
        y, aux, load = _apply_ffn(p["ffn"], h2, cfg, is_moe, ctx)
        return ctx.constrain(x + y, ("batch", "seq", "embed")), aux, None, load
    if kind in ("global", "local"):
        h = apply_norm(p["ln1"], x, cfg)
        q = jnp.einsum("bsd,dhe->bshe", h, p["attn"]["wq"])
        k = jnp.einsum("bsd,dke->bske", h, p["attn"]["wk"])
        v = jnp.einsum("bsd,dke->bske", h, p["attn"]["wv"])
        from repro.models.layers import apply_rope
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        q = ctx.constrain(q, ("batch", "seq", "heads", "head_dim"))
        if kind == "local" and cfg.sliding_window and x.shape[1] > cfg.sliding_window:
            o = attn.local_blockwise_attention(q, k, v, window=cfg.sliding_window)
        else:
            win = cfg.sliding_window if kind == "local" else 0
            if cfg.attn_block_skip:
                o = attn.causal_skip_attention(q, k, v, window=win)
            else:
                bq = cfg.attn_block_q or x.shape[1]
                o = attn.blockwise_attention(q, k, v, causal=True, window=win,
                                             block_q=bq)
        x = x + jnp.einsum("bshe,hed->bsd", o, p["attn"]["wo"])
        if want_kv:
            kv = (k, v)
        if memory is not None:  # cross-attention (audio decoder)
            hx = apply_norm(p["lnx"], x, cfg)
            qx = jnp.einsum("bsd,dhe->bshe", hx, p["xattn"]["wq"])
            kx = jnp.einsum("bsd,dke->bske", memory, p["xattn"]["wk"])
            vx = jnp.einsum("bsd,dke->bske", memory, p["xattn"]["wv"])
            ox = attn.blockwise_attention(qx, kx, vx, causal=False)
            x = x + jnp.einsum("bshe,hed->bsd", ox, p["xattn"]["wo"])
        h2 = apply_norm(p["ln2"], x, cfg)
        y, aux, load = _apply_ffn(p["ffn"], h2, cfg, is_moe, ctx)
        x = x + y
        return ctx.constrain(x, ("batch", "seq", "embed")), aux, kv, load
    if kind == "mamba":
        h = apply_norm(p["ln1"], x, cfg)
        y, state = mb.mamba_block(p["mamba"], h, cfg)
        x = x + y
        h2 = apply_norm(p["ln2"], x, cfg)
        y, aux, load = _apply_ffn(p["ffn"], h2, cfg, is_moe, ctx)
        x = x + y
        if want_kv:  # prefill: carry final (conv, ssm) states into the cache
            kv = {"conv": state[0], "h": state[1]}
        return ctx.constrain(x, ("batch", "seq", "embed")), aux, kv, load
    if kind == "rwkv":
        b = x.shape[0]
        hh, nn = rw.rwkv_heads(cfg)
        zeros = (jnp.zeros((b, cfg.d_model), x.dtype),
                 jnp.zeros((b, hh, nn, nn), jnp.float32))
        a, (tm_prev, h_new) = rw.time_mix(p["rwkv"], apply_norm(p["ln1"], x, cfg),
                                          cfg, zeros)
        x = x + a
        cmz = jnp.zeros((b, cfg.d_model), x.dtype)
        y, cm_prev = rw.channel_mix(p["rwkv"], apply_norm(p["ln2"], x, cfg),
                                    cfg, cmz)
        x = x + y
        if want_kv:
            kv = {"tm_prev": tm_prev, "h": h_new, "cm_prev": cm_prev}
        return (ctx.constrain(x, ("batch", "seq", "embed")),
                jnp.zeros((), jnp.float32), kv, None)
    raise ValueError(kind)


def apply_block_decode(p, x, cfg: ModelConfig, kind: str, pat_idx: int,
                       cache, pos, ctx: ShardCtx):
    """One-token decode. x: (B,1,d). Returns (x, new_cache)."""
    from repro.models.layers import apply_rope
    new_cache = dict(cache)
    if kind in ("global", "local"):
        h = apply_norm(p["ln1"], x, cfg)
        q = jnp.einsum("bsd,dhe->bshe", h, p["attn"]["wq"])
        k = jnp.einsum("bsd,dke->bske", h, p["attn"]["wk"])
        v = jnp.einsum("bsd,dke->bske", h, p["attn"]["wv"])
        posv = pos[None, None] if pos.ndim == 0 else pos[:, None]
        q = apply_rope(q, posv.astype(jnp.int32), cfg.rope_theta)
        k = apply_rope(k, posv.astype(jnp.int32), cfg.rope_theta)
        slots = cache["k"].shape[1]
        if kind == "local" and cfg.sliding_window:
            slot = jnp.mod(pos, slots)
            kv_pos = _ring_positions(slots, pos + 1, cfg.sliding_window)
        else:
            slot = jnp.minimum(pos, slots - 1)
            kv_pos = _full_positions(slots, pos + 1)
        kc = jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype),
                                                 slot, axis=1)
        vc = jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype),
                                                 slot, axis=1)
        kc = ctx.constrain(kc, ("batch", "kvseq", "kv_heads", "head_dim"))
        vc = ctx.constrain(vc, ("batch", "kvseq", "kv_heads", "head_dim"))
        new_cache["k"], new_cache["v"] = kc, vc
        o = attn.decode_attention(q, kc, vc, kv_pos,
                                  window=cfg.sliding_window if kind == "local" else 0)
        x = x + jnp.einsum("bshe,hed->bsd", o, p["attn"]["wo"])
        if "xk" in cache:  # cross-attention against cached encoder KV
            hx = apply_norm(p["lnx"], x, cfg)
            qx = jnp.einsum("bsd,dhe->bshe", hx, p["xattn"]["wq"])
            enc_pos = jnp.arange(cache["xk"].shape[1], dtype=jnp.int32)
            ox = attn.decode_attention(qx, cache["xk"], cache["xv"], enc_pos)
            x = x + jnp.einsum("bshe,hed->bsd", ox, p["xattn"]["wo"])
        h2 = apply_norm(p["ln2"], x, cfg)
        y, _aux, _load = _apply_ffn(p["ffn"], h2, cfg, cfg.ffn_is_moe(pat_idx), ctx)
        return x + y, new_cache
    if kind == "mamba":
        h = apply_norm(p["ln1"], x, cfg)
        y, (conv2, h2s) = mb.mamba_decode_step(p["mamba"], h, cfg,
                                               (cache["conv"], cache["h"]))
        new_cache["conv"], new_cache["h"] = conv2, h2s
        x = x + y
        h2 = apply_norm(p["ln2"], x, cfg)
        y, _aux, _load = _apply_ffn(p["ffn"], h2, cfg, cfg.ffn_is_moe(pat_idx), ctx)
        return x + y, new_cache
    if kind == "rwkv":
        a, (tmp2, hs2) = rw.time_mix_step(
            p["rwkv"], apply_norm(p["ln1"], x, cfg), cfg,
            (cache["tm_prev"], cache["h"]))
        x = x + a
        y, cmp2 = rw.channel_mix(p["rwkv"], apply_norm(p["ln2"], x, cfg), cfg,
                                 cache["cm_prev"])
        new_cache.update(tm_prev=tmp2, h=hs2, cm_prev=cmp2)
        return x + y, new_cache
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Full-stack forward
# ---------------------------------------------------------------------------

def _frontend_prefix(params, cfg: ModelConfig, batch) -> Optional[jnp.ndarray]:
    """VLM patch prefix (projected)."""
    if cfg.family == "vlm" and "patches" in batch:
        return batch["patches"] @ params["frontend_proj"]
    return None


def encode_audio(params, cfg: ModelConfig, frames, ctx: ShardCtx):
    """Bidirectional encoder over (stubbed) post-conv frame embeddings."""
    x = frames @ params["frontend_proj"]
    for j in range(cfg.encoder_layers):
        p = params["encoder"][f"e{j}"]
        h = apply_norm(p["ln1"], x, cfg)
        q = jnp.einsum("bsd,dhe->bshe", h, p["attn"]["wq"])
        k = jnp.einsum("bsd,dke->bske", h, p["attn"]["wk"])
        v = jnp.einsum("bsd,dke->bske", h, p["attn"]["wv"])
        o = attn.blockwise_attention(q, k, v, causal=False)
        x = x + jnp.einsum("bshe,hed->bsd", o, p["attn"]["wo"])
        x = x + apply_mlp(p["ffn"], apply_norm(p["ln2"], x, cfg), cfg)
        x = ctx.constrain(x, ("batch", "seq", "embed"))
    return apply_norm(params["enc_ln"], x, cfg)


def forward_train(params, cfg: ModelConfig, batch, ctx: ShardCtx = NULL_CTX,
                  remat: str = "block"):
    """Returns (logits, aux_loss, load): ``load`` counts the tokens routed
    to each held expert, (MoE layers, held), or is None without held
    experts.  batch: tokens (B,S) [+ patches/frames]."""
    plen, n_full, rem = pattern_info(cfg)
    tokens = batch["tokens"]
    x = apply_embed(params["embed"], tokens, cfg).astype(jnp.dtype(cfg.compute_dtype))
    memory = None
    if cfg.family == "audio":
        memory = encode_audio(params, cfg, batch["frames"].astype(x.dtype), ctx)
    prefix = _frontend_prefix(params, cfg, batch)
    n_prefix = 0
    if prefix is not None:
        x = jnp.concatenate([prefix.astype(x.dtype), x], axis=1)
        n_prefix = prefix.shape[1]
    x = ctx.constrain(x, ("batch", "seq", "embed"))
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)[None]
    aux_total = 0.0
    loads = []

    def lead_block(x, block_params):
        x, aux, _, _ = apply_block_train(block_params, x, cfg,
                                         cfg.layer_pattern[0], 0, ctx,
                                         positions=positions, dense=True)
        return x, aux

    for j in range(cfg.first_dense_layers):
        body = jax.checkpoint(lead_block) if remat in ("block", "full") else lead_block
        x, aux = body(x, params["lead"][f"l{j}"])
        aux_total = aux_total + aux

    def superblock(x, block_params):
        aux_sb = jnp.zeros((), jnp.float32)
        loads_sb = []
        for pidx, kind in enumerate(cfg.layer_pattern):
            x, aux, _, ld = apply_block_train(block_params[f"p{pidx}"], x, cfg,
                                              kind, pidx, ctx, memory=memory,
                                              positions=positions)
            aux_sb = aux_sb + aux
            loads_sb.append(ld)
        return x, aux_sb, loads_sb

    if n_full:
        body = superblock
        if remat in ("block", "full"):
            body = jax.checkpoint(superblock)

        def scan_body(carry, block_params):
            x, aux_acc = carry
            x, aux_sb, loads_sb = body(x, block_params)
            return (x, aux_acc + aux_sb), loads_sb

        aux_lead = aux_total
        (x, aux_total), stack_loads = jax.lax.scan(
            scan_body, (x, jnp.zeros((), jnp.float32)), params["stack"])
        if cfg.first_dense_layers:
            aux_total = aux_total + aux_lead
        loads += [ld for ld in stack_loads if ld is not None]
    for j in range(rem):
        kind = cfg.layer_kinds[n_full * plen + j]
        x, aux, _, ld = apply_block_train(params["rem"][f"r{j}"], x, cfg, kind,
                                          j % plen, ctx, memory=memory,
                                          positions=positions)
        aux_total = aux_total + aux
        if ld is not None:
            loads.append(ld[None])
    x = apply_norm(params["final_ln"], x, cfg)
    if n_prefix:
        x = x[:, n_prefix:]
    logits = apply_unembed(params["embed"], x, cfg)
    logits = ctx.constrain(logits, ("batch", "seq", "vocab"))
    return logits, aux_total, jnp.concatenate(loads) if loads else None


def forward_prefill(params, cfg: ModelConfig, batch, ctx: ShardCtx = NULL_CTX,
                    max_len: Optional[int] = None):
    """Prefill: full forward that also materialises the decode cache.

    Returns (last_token_logits, cache). ``max_len`` sets the cache allocation
    (>= prefill length; default exactly the prefill length) so subsequent
    decode steps have headroom. Local layers keep ring-truncated windows;
    SSM/RWKV layers store final states.
    """
    plen, n_full, rem = pattern_info(cfg)
    tokens = batch["tokens"]
    bsz, seq = tokens.shape
    cdt = jnp.dtype(cfg.compute_dtype)
    x = apply_embed(params["embed"], tokens, cfg).astype(cdt)
    memory = None
    if cfg.family == "audio":
        memory = encode_audio(params, cfg, batch["frames"].astype(x.dtype), ctx)
    prefix = _frontend_prefix(params, cfg, batch)
    n_prefix = 0
    if prefix is not None:
        x = jnp.concatenate([prefix.astype(x.dtype), x], axis=1)
        n_prefix = prefix.shape[1]
    x = ctx.constrain(x, ("batch", "seq", "embed"))
    total = x.shape[1]
    cache_len = max(max_len or total, total)
    positions = jnp.arange(total, dtype=jnp.int32)[None]
    cache = init_cache(cfg, bsz, cache_len, dtype=cdt,
                       enc_len=memory.shape[1] if memory is not None else 0)
    cache["pos"] = jnp.full((), total, jnp.int32)

    def run_block(p, x, kind, pidx, lead_cache):
        x, _aux, kv, _load = apply_block_train(p, x, cfg, kind, pidx, ctx,
                                               memory=memory,
                                               positions=positions,
                                               want_kv=True)
        new_lc = dict(lead_cache)
        if isinstance(kv, dict):       # mamba/rwkv final states
            for name, val in kv.items():
                new_lc[name] = val.astype(lead_cache[name].dtype)
            kv = None
        if kv is not None:
            k, v = kv
            slots = lead_cache["k"].shape[1]
            if slots < total:  # local ring: keep the last ``slots`` entries
                k, v = k[:, -slots:], v[:, -slots:]
                # ring layout: entry at position p lives in slot p % slots
                roll = (total % slots)
                k = jnp.roll(k, roll, axis=1)
                v = jnp.roll(v, roll, axis=1)
            elif slots > total:  # headroom for subsequent decode steps
                pad = ((0, 0), (0, slots - total), (0, 0), (0, 0))
                k, v = jnp.pad(k, pad), jnp.pad(v, pad)
            new_lc["k"] = k.astype(lead_cache["k"].dtype)
            new_lc["v"] = v.astype(lead_cache["v"].dtype)
        if memory is not None and "xk" in lead_cache:
            new_lc["xk"] = jnp.einsum("bsd,dke->bske", memory,
                                      p["xattn"]["wk"]).astype(lead_cache["xk"].dtype)
            new_lc["xv"] = jnp.einsum("bsd,dke->bske", memory,
                                      p["xattn"]["wv"]).astype(lead_cache["xv"].dtype)
        return x, new_lc

    if n_full:
        def scan_body(x, xs):
            block_params, block_cache = xs
            new_bc = {}
            for pidx, kind in enumerate(cfg.layer_pattern):
                x, new_bc[f"p{pidx}"] = run_block(
                    block_params[f"p{pidx}"], x, kind, pidx, block_cache[f"p{pidx}"])
            return x, new_bc

        x, new_stack = jax.lax.scan(scan_body, x, (params["stack"], cache["stack"]))
        cache["stack"] = new_stack
    for j in range(rem):
        kind = cfg.layer_kinds[n_full * plen + j]
        x, cache["rem"][f"r{j}"] = run_block(params["rem"][f"r{j}"], x, kind,
                                             j % plen, cache["rem"][f"r{j}"])
    x = apply_norm(params["final_ln"], x, cfg)
    logits = apply_unembed(params["embed"], x[:, -1:], cfg)
    return logits, cache


def forward_decode(params, cfg: ModelConfig, tokens, cache,
                   ctx: ShardCtx = NULL_CTX):
    """One decode step. tokens: (B,1). Returns (logits (B,1,V), new_cache)."""
    plen, n_full, rem = pattern_info(cfg)
    pos = cache["pos"]
    x = apply_embed(params["embed"], tokens, cfg).astype(jnp.dtype(cfg.compute_dtype))
    x = ctx.constrain(x, ("batch", "seq", "embed"))

    new_cache = {"pos": pos + 1, "stack": cache["stack"], "rem": dict(cache["rem"])}
    if n_full:
        def scan_body(x, xs):
            block_params, block_cache = xs
            new_bc = {}
            for pidx, kind in enumerate(cfg.layer_pattern):
                x, new_bc[f"p{pidx}"] = apply_block_decode(
                    block_params[f"p{pidx}"], x, cfg, kind, pidx,
                    block_cache[f"p{pidx}"], pos, ctx)
            return x, new_bc

        x, new_stack = jax.lax.scan(scan_body, x, (params["stack"], cache["stack"]))
        new_cache["stack"] = new_stack
    for j in range(rem):
        kind = cfg.layer_kinds[n_full * plen + j]
        x, new_cache["rem"][f"r{j}"] = apply_block_decode(
            params["rem"][f"r{j}"], x, cfg, kind, j % plen,
            cache["rem"][f"r{j}"], pos, ctx)
    x = apply_norm(params["final_ln"], x, cfg)
    logits = apply_unembed(params["embed"], x, cfg)
    return logits, new_cache
