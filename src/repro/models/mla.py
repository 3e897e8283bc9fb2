"""Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 Sec 2.1) in its
training form, with optional LoRA adapters (arXiv:2106.09685) on all four of
its projections.

Without a query compression (``q_lora_rank`` null), per token ``x``::

    q        = x Wq                          (heads, nope + rope)
    [c, kr]  = x Wkv_a                       (kv_lora_rank + rope)
    [kn, v]  = rmsnorm(c) Wkv_b              (heads, nope + v)
    k        = [kn, rope(kr)]                the RoPE key is shared by the heads
    o        = softmax(q k^T / sqrt(nope + rope), causal) v
    out      = o Wo

RoPE rotates the last ``rope`` dims of queries and keys in the rotate-half
layout (DeepSeek's checkpoints store them interleaved: a fixed permutation of
the weights' columns).  An adapted projection computes ``y = x W + (alpha /
r) (x A) B`` with ``A`` of rank ``r``.  Scores, the softmax and the latent
norm are float32; the matmuls run in the compute dtype with float32
accumulation.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import apply_rope
from repro.models.params import ParamFactory

# the latent norm keeps DeepSeek's RMSNorm default (not the config's eps)
KV_NORM_EPS = 1e-6
# scale of the adapters' B at init: small and nonzero, the state of a stage
# after the first of a running fine-tune (a zero B would give A no gradient)
LORA_B_STD = 0.01

# target -> (weight einsum, A einsum, B einsum)
_EINSUMS = {
    "q_proj": ("bsd,dhe->bshe", "bsd,dr->bsr", "bsr,rhe->bshe"),
    "kv_a_proj_with_mqa": ("bsd,dc->bsc", "bsd,dr->bsr", "bsr,rc->bsc"),
    "kv_b_proj": ("bsc,che->bshe", "bsc,cr->bsr", "bsr,rhe->bshe"),
    "o_proj": ("bshv,hvd->bsd", "bshv,hvr->bsr", "bsr,rd->bsd"),
}


def _shapes(cfg: ModelConfig):
    """target -> (input dims, output dims) of each projection."""
    d, h, r = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    nope, rope, v = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    return {"q_proj": ((d,), (h, nope + rope)),
            "kv_a_proj_with_mqa": ((d,), (r + rope,)),
            "kv_b_proj": ((r,), (h, nope + v)),
            "o_proj": ((h, v), (d,))}


def init_mla(fac: ParamFactory, cfg: ModelConfig):
    shapes = _shapes(cfg)
    with fac.scope("attn"):
        p = {t: fac.param(t, i + o, (None,) * (len(i) + len(o)),
                          in_dims=len(i))
             for t, (i, o) in shapes.items()}
        p["kv_a_layernorm"] = {"scale": fac.param(
            "kv_a_layernorm.scale", (cfg.kv_lora_rank,), (None,), init="ones")}
    return p


def init_mla_lora(fac: ParamFactory, cfg: ModelConfig):
    """The adapters of the four projections: ``a`` (in, r) drawn at
    1/sqrt(fan-in), ``b`` (r, out) at ``LORA_B_STD``."""
    r = cfg.lora_rank
    out = {}
    with fac.scope("attn"), fac.scope("lora"):
        for t, (i, o) in _shapes(cfg).items():
            out[t] = {
                "a": fac.param(f"{t}.a", i + (r,), (None,) * (len(i) + 1),
                               fan_in=math.prod(i)),
                "b": fac.param(f"{t}.b", (r,) + o, (None,) * (len(o) + 1),
                               scale=LORA_B_STD, fan_in=1)}
    return {"attn": {"lora": out}}


def _proj(p, x, target: str, cfg: ModelConfig):
    w_eq, a_eq, b_eq = _EINSUMS[target]
    y = jnp.einsum(w_eq, x, p[target].astype(x.dtype))
    if "lora" not in p:
        return y
    adapter = p["lora"][target]
    with jax.named_scope("lora.adapter"):
        xa = jnp.einsum(a_eq, x, adapter["a"].astype(x.dtype))
        delta = jnp.einsum(b_eq, xa, adapter["b"].astype(x.dtype))
        return y + (cfg.lora_alpha / cfg.lora_rank) * delta


def _rmsnorm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def apply_mla(p, x, cfg: ModelConfig, positions):
    """x: (B, S, d) -> (B, S, d), causal."""
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    with jax.named_scope("mla.attention"):
        q = _proj(p, x, "q_proj", cfg)
        ckv = _proj(p, x, "kv_a_proj_with_mqa", cfg)
        c, k_rope = ckv[..., :cfg.kv_lora_rank], ckv[..., cfg.kv_lora_rank:]
        c = _rmsnorm(c, p["kv_a_layernorm"]["scale"], KV_NORM_EPS)
        kv = _proj(p, c, "kv_b_proj", cfg)
        k_nope, v = kv[..., :nope], kv[..., nope:]
        q = jnp.concatenate([q[..., :nope],
                             apply_rope(q[..., nope:], positions, cfg.rope_theta)],
                            -1)
        k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, k_nope.shape[:-1] + (rope,))], -1)
        s = x.shape[1]
        scores = jnp.einsum("bqhe,bkhe->bhqk", q, k,
                            preferred_element_type=jnp.float32)
        scores = scores / math.sqrt(nope + rope)
        causal = jnp.tril(jnp.ones((s, s), bool))
        probs = jax.nn.softmax(jnp.where(causal, scores, -1e30), axis=-1)
        o = jnp.einsum("bhqk,bkhv->bqhv", probs.astype(x.dtype), v)
        return _proj(p, o, "o_proj", cfg)
